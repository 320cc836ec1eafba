"""Causal attention over packed documents as ONE fused kernel a call: the
scores of a ``(block_q, block_kv)`` tile live in VMEM, the softmax carries a
running maximum and sum, and only the output and one log-sum-exp a query
are written (``jax.experimental.pallas.ops.tpu.splash_attention``).

The kernel is the package's, with its own VJP (residuals q, k, v, o and the
log-sum-exp; its backward kernels compute the scores again in VMEM), its
``CausalMask`` / ``LocalMask`` whose wholly masked blocks are left out of a
static block list, ``SegmentIds`` for the documents of a packed row and
grouped heads as this repo writes them (query head ``h`` reads key head
``h // (H // Hk)``).  Same work, same precision as the XLA blocks of
``models/layers.py::packed_causal_attention``: products in the operands'
type accumulated in float32, a float32 softmax, every (query, key) pair the
mask allows and no other.  The kernel takes no scale: q is scaled before it,
in float32, and cast back, which under a 2-byte compute type rounds q once
more than the XLA blocks do (they scale the float32 scores).

- :func:`kernel_applicable`: the rule, from the backend and the shapes.
- :func:`default_impl`: ``"kernel"`` where the rule holds, ``"jnp"`` (the XLA
  blocks) elsewhere; what the models' counters ask.
- :func:`fused_causal_attention`: the call, ``vmap``-ped over the rows of the
  batch (a block's ``vmap`` over lanes batches it once more).
- :func:`scores_computed`: the (query, key) positions the forward kernel
  scores a head, from its own block list.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

# Tiles of the kernels (``BlockSizes``), chosen on the chip at the two
# language-model cells' shapes (``tools/chip_kernels.py --attention``, PR
# 34; PERF.md has the sweep): (block_q, block_kv, block_kv_compute) of the
# forward kernel and of the dk/dv kernel, which with ``FUSED_BWD`` computes
# dq too.  A sequence shorter than a tile takes itself as the tile.
BLOCKS = (512, 512, 512, 1024, 1024, 512)
FUSED_BWD = True
_LANES = 128


def kernel_applicable(s: int, dk: int, dv: int) -> bool:
    """Whether attention over rows of ``s`` tokens with keys of ``dk`` and
    values of ``dv`` features takes the fused kernel: on a TPU, ``s`` whole
    tiles (whole lane tiles where it is shorter than a tile), and head
    widths of at least a lane tile in whole half tiles (Mosaic tiles keys
    of 192 as they are: padded to 256 they time the same)."""
    return (jax.default_backend() == "tpu" and _tiles(s, BLOCKS)
            and min(dk, dv) >= _LANES and dk % 64 == 0 and dv % 64 == 0)


def default_impl(s: int, dk: int, dv: int) -> str:
    return "kernel" if kernel_applicable(s, dk, dv) else "jnp"


def _tiles(s: int, blocks) -> bool:
    return s % _LANES == 0 and all(s % min(b, s) == 0 for b in blocks)


@lru_cache(maxsize=None)
def _kernel(s: int, heads: int, window, blocks, fused_bwd: bool,
            interpret: bool):
    """The package's kernel for ``heads`` query heads over ``s`` tokens,
    built once a shape: its block lists are numpy work."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    if not _tiles(s, blocks):
        raise ValueError(f"a sequence of {s} is no whole number of the "
                         f"kernel's tiles {blocks}")
    bq, bkv, bkvc, bq_dkv, bkv_dkv, bkvc_dkv = (min(b, s) for b in blocks)
    sizes = sa.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
        block_q_dkv=bq_dkv, block_kv_dkv=bkv_dkv,
        block_kv_dkv_compute=bkvc_dkv,
        block_q_dq=None if fused_bwd else bq_dkv,
        block_kv_dq=None if fused_bwd else bkv_dkv,
        use_fused_bwd_kernel=fused_bwd)
    mask = sa.CausalMask((s, s)) if window is None else \
        sa.LocalMask((s, s), (window - 1, 0), 0)
    # Called inside a trace too: the block lists must be constants, never
    # that trace's values, to be kept from one trace to the next.
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha(
            sa.MultiHeadMask([mask] * heads), head_shards=1, q_seq_shards=1,
            block_sizes=sizes, interpret=interpret)


def scores_computed(s: int, window=None, blocks=BLOCKS) -> int:
    """(query, key) positions of one row of ``s`` tokens that the forward
    kernel scores, a head: the blocks of its list that the mask does not
    empty, each computed whole."""
    kernel = _kernel(s, 1, window, tuple(blocks), FUSED_BWD, False)
    live = int((np.asarray(kernel.fwd_mask_info.block_mask)[0] > 0).sum())
    return live * min(blocks[0], s) * min(blocks[1], s)


def fused_causal_attention(q, k, v, segment, scale: float, window=None, *,
                           blocks=BLOCKS, fused_bwd: bool = FUSED_BWD,
                           interpret: bool = False):
    """``q`` ``(B, S, H, dk)``, ``k`` ``(B, S, Hk, dk)``, ``v`` ``(B, S, Hk,
    dv)``, ``segment`` ``(B, S)`` -> ``(B, S, H, dv)`` in ``q``'s type:
    softmax attention, causal within a document, under ``window`` only the
    keys less than ``window`` positions behind the query."""
    from jax.experimental.pallas.ops.tpu.splash_attention import SegmentIds

    kernel = _kernel(q.shape[1], q.shape[2], window, tuple(blocks),
                     fused_bwd, interpret)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def one_row(q, k, v, seg):
        return kernel(q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                      SegmentIds(seg, seg)).swapaxes(0, 1)

    return jax.vmap(one_row)(q, k, v, segment.astype(jnp.int32))

"""An expert share computed as routing: the (token, expert) pairs whose
expert is held here are gathered by expert and go through ONE grouped
matrix product a projection, forward and backward, in static shapes.

The buffer holds a row for every selected pair, ``P = tokens x top-k``
(the most a share can be sent: no capacity, no dropped token); the pairs
whose expert is held are sorted to its front by expert, and the grouped
product walks only the row tiles its ``group_sizes`` cover, so the work
follows the pairs routed here and not ``tokens x experts held``.  Rows past
``group_sizes.sum()`` are never computed: what they hold is unspecified
and nothing reads it.

- :func:`grouped_matmul`: ``out[rows of group g] = lhs[rows of group g] @
  rhs[g]``.  On a TPU ``jax.experimental.pallas.ops.tpu.megablox``'s
  ``gmm`` with its own VJP (``gmm`` forward and for the left operand's
  cotangent, ``tgmm`` for the right's; their grid is the tiles in use, a
  dynamic bound); elsewhere a plain ``jnp`` form (a weight gathered a row:
  small sizes only).  Both batch under ``vmap`` (the trained lanes of a
  block).
- :func:`sort_pairs`: the sorted order, its inverse and the group sizes.
- :func:`gather_rows`: ``x[idx]`` whose cotangent is a gather too (the
  caller hands it the inverse map), so that neither the dispatch nor the
  combine scatters.
- :func:`routed_ffn`: dispatch -> SwiGLU experts -> weighted combine,
  under the device scopes ``blades/dispatch`` (the sort and the gather into
  the buffer), ``blades/experts`` (the grouped products and the SwiGLU) and
  ``blades/combine`` (the gather back and the weighted sum), each with its
  cotangents.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

# Row tile of the grouped product: a group's last tile is computed whole,
# so each held expert costs up to one tile of rows no pair asked for.
ROW_TILE = 256
# The k and n tile of the package's kernels.  Its ``gmm`` takes ONE tiling
# for its three products (forward; ``gmm`` and ``tgmm`` for the cotangents):
# 896 x 896 times as a tiling searched for each product did (4.07 against
# 4.02 ms for an up and a down projection, forward + backward, at 8192
# pairs; 14.0 against 14.5 at 65 536), 128 x 128 three times slower, 1024 x
# 1024 a tenth slower (``tools/chip_kernels.py --grouped``, PR 33).
_TILE_KN = 896


def rows_computed(group_sizes, tile: int):
    """Pair rows the grouped product works on, padding to its row tiles
    included: every tile a group touches is computed for that group."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = (ends + tile - 1) // tile - starts // tile
    return (jnp.where(group_sizes > 0, tiles, 0) * tile).sum()


def _group_of_row(group_sizes, rows: int):
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(rows)
    gid = jnp.searchsorted(ends, row, side="right")
    return jnp.minimum(gid, group_sizes.shape[0] - 1), row < ends[-1]


def _grouped_matmul_jnp(lhs, rhs, group_sizes, transpose_rhs):
    gid, valid = _group_of_row(group_sizes, lhs.shape[0])
    w = rhs[gid]
    out = jnp.einsum("mk,mnk->mn" if transpose_rhs else "mk,mkn->mn",
                     lhs, w.astype(lhs.dtype))
    return jnp.where(valid[:, None], out, 0)


def kernel_applicable(k: int, n: int) -> bool:
    """Whether the product of these widths takes the Pallas kernels: on a
    TPU, and both widths whole lane tiles."""
    return jax.default_backend() == "tpu" and k % 128 == 0 and n % 128 == 0


def grouped_matmul(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
                   tile: int = ROW_TILE, impl=None):
    """``lhs`` ``(rows, k)`` in groups of consecutive rows (``group_sizes``
    ``(G,)`` int32, summing to at most ``rows``) times ``rhs`` ``(G, k,
    n)`` (``(G, n, k)`` with ``transpose_rhs``) -> ``(rows, n)`` in
    ``lhs``'s type.  Rows past the groups are unspecified with the kernel
    (zeros in the ``jnp`` form).  ``impl``: ``"kernel"`` (the default where
    :func:`kernel_applicable`), ``"interpret"`` (the kernel in Pallas's
    interpreter, for the tests) or ``"jnp"`` (the default elsewhere)."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if impl is None:
        impl = "kernel" if kernel_applicable(k, n) else "jnp"
    if impl == "jnp":
        return _grouped_matmul_jnp(lhs, rhs, group_sizes, transpose_rhs)
    if lhs.shape[0] % tile:
        raise ValueError(f"{lhs.shape[0]} rows are no multiple of the row "
                         f"tile {tile}")
    from jax.experimental.pallas.ops.tpu import megablox

    kn = min(_TILE_KN, k, n)
    return megablox.gmm(lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
                        (tile, kn, kn), None, None, transpose_rhs,
                        impl == "interpret")


def sort_pairs(expert, held, num_groups: int):
    """``expert`` ``(P,)`` local expert of every selected pair and ``held``
    ``(P,)`` whether this share holds it -> ``(order, pos, group_sizes)``:
    sorted row ``r`` is pair ``order[r]`` (held pairs first, by expert,
    pairs of one expert in token order), pair ``p`` lies at row
    ``pos[p]``, and ``group_sizes[g]`` pairs selected held expert ``g``."""
    key = jnp.where(held, expert, num_groups).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32)
    sizes = (key[:, None] == jnp.arange(num_groups)).sum(0, dtype=jnp.int32)
    return order, pos, sizes


@jax.custom_vjp
def gather_rows(x, idx, back_idx, back_mask):
    """``x[idx]`` ``(R, ...)``.  The caller states the inverse map: row
    ``t`` of ``x`` was read by the output rows ``back_idx[t, :]`` where
    ``back_mask[t, :]``, so the cotangent is gathered, never scattered."""
    return x.at[idx].get(mode="promise_in_bounds")


def _gather_fwd(x, idx, back_idx, back_mask):
    return gather_rows(x, idx, back_idx, back_mask), (back_idx, back_mask)


def _gather_bwd(res, g):
    back_idx, back_mask = res
    got = jnp.where(back_mask[..., None],
                    g.at[back_idx].get(mode="promise_in_bounds"), 0)
    return got.sum(1, dtype=jnp.float32).astype(g.dtype), None, None, None


gather_rows.defvjp(_gather_fwd, _gather_bwd)


def routed_ffn(x, expert, held, weight, gate, up, down, *, impl=None):
    """The held experts' part of a routed SwiGLU layer.

    ``x`` ``(T, h)`` tokens; ``expert`` ``(T, K)`` int32 local index of
    each selected expert, ``held`` ``(T, K)`` whether it is held here,
    ``weight`` ``(T, K)`` its routing weight; ``gate``/``up`` ``(G, h,
    f)``, ``down`` ``(G, f, h)``.  Returns ``(y (T, h), group_sizes (G,),
    rows_computed)``: ``y[t] = sum over held pairs of weight *
    down_e(silu(gate_e x_t) * up_e x_t)``."""
    tokens, top_k = expert.shape
    pairs = tokens * top_k
    tile = math.gcd(pairs, ROW_TILE)
    with jax.named_scope("blades/dispatch"):
        order, pos, sizes = sort_pairs(expert.reshape(pairs),
                                       held.reshape(pairs), gate.shape[0])
        xs = gather_rows(x, order // top_k, pos.reshape(tokens, top_k), held)
    with jax.named_scope("blades/experts"):
        mm = partial(grouped_matmul, group_sizes=sizes, tile=tile, impl=impl)
        hs = jax.nn.silu(mm(xs, gate)) * mm(xs, up)
        ys = mm(hs, down)
    with jax.named_scope("blades/combine"):
        # Pair p reads its row; sorted row r is read by pair order[r] alone.
        got = gather_rows(ys, pos, order[:, None],
                          jnp.ones((pairs, 1), bool)).reshape(
                              tokens, top_k, -1)
        # A pair whose expert is absent reads a row nothing wrote: take its
        # value out before anything multiplies it.
        got = jnp.where(held[..., None], got, 0)
        y = (weight[..., None].astype(got.dtype) * got).sum(
            1, dtype=jnp.float32).astype(x.dtype)
    return y, sizes, rows_computed(sizes, tile)

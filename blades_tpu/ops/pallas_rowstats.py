"""Fused pallas row-statistics kernel for the streamed pass planner.

The row-geometry defenses take their statistics over the stored
``(n, d)`` update matrix as full HBM traversals
(:mod:`blades_tpu.parallel.streamed_geometry`).  The pass planner fuses
the requests that are live together into one traversal; on a TPU backend
this kernel executes that traversal as ONE HBM read: each grid step
loads a full-height ``(n, block_d)`` column stripe into VMEM, casts to
f32, and accumulates every requested statistic in-core —

- row squared norms ``(n, 1)`` (VPU row reduction);
- the Gram matrix ``(n, n)`` as an MXU ``x @ x.T`` stripe contraction
  (the n^2 * block_d flops ride the systolic array while the stripe
  load is in flight);
- per-row positive/negative sign counts ``(n, 2)`` (zero counts derive
  from the true width afterwards, so stripe-alignment padding columns
  never miscount);
- dots against ``R`` replicated vectors ``(n, R)`` (MXU);
- ``W`` weighted row sums ``(W, block_d)`` written per stripe
  (overwrite — each stripe owns its columns);
- ``G`` Gram-vector products ``(buf buf^T) w`` ``(n, G)`` via two MXU
  contractions per stripe — the Weiszfeld/centered-clipping fusion lever.

Numerics: all statistics are plain f32 sums — no order statistics — so
ZERO padding (rows to the sublane multiple, columns to the stripe
multiple) is invisible to every accumulator, and results differ from the
``lax.scan`` chunk path only by f32 reduction reassociation (the MXU
contractions accumulate in f32).  Equivalence is tested in interpret
mode against the chunk path per the ``test_pallas_*`` convention
(tests/test_pass_fusion.py).

**Integer input (wire-domain aggregation).**  The bundle also accepts a
packed int8 matrix (the deferred-decode wire payload of
:mod:`blades_tpu.comm.codecs`): each stripe then loads ONE byte per
coordinate from HBM — a 4x traffic cut against the f32 matrix the
f32-domain path traverses — and the self-contractions ride the MXU's
int8 path: Gram stripes and row squared norms accumulate int8*int8 ->
int32 EXACTLY (|q| <= 127 over a 512-wide stripe is ~8.3e6 << 2^31)
before joining the cross-stripe f32 accumulator.  Mixed contractions (dots
against replicated f32 vectors, f32 row weights) cast the resident
stripe to f32 in VMEM — the HBM read is still one byte.  Per-row scale
algebra (``s_i s_j`` on the Gram, ``s_i²`` on the norms, weight folding)
is the CALLER's job (the pass planner applies it to the accumulated
statistics); this kernel computes raw integer geometry.

Gated by the same envelope as :func:`blades_tpu.ops.pallas_select.
kernel_applicable` plus a no-copy row alignment requirement and a
tighter height bound when the Gram accumulator is requested (the
``(n, n)`` f32 block must share VMEM with the stripe).  The planner's
``lax.scan`` chunk loop is the fallback for CPU/ineligible shapes.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from blades_tpu.ops.pallas_select import _BLOCK_D, stripe_compiler_params
from blades_tpu.ops.pallas_select import kernel_applicable as _select_gate

# VMEM height bound when the (n, n) f32 Gram accumulator is in the
# bundle: 1024^2 f32 = 4 MiB a buffer beside the stripe and a 4 MiB dot
# temporary; past it the planner chunk-loops the bundle instead.
_GRAM_MAX_N = 1024


def kernel_applicable(n: int, d: int, *, gram: bool = False,
                      elem_bits: int = 32,
                      integer: bool = False) -> bool:
    """Can the fused row-stats kernel serve an ``(n, d)`` bundle?

    The shared rank-select envelope (TPU backend, VMEM height bound,
    size floor, ``BLADES_TPU_NO_PALLAS`` escape hatch) plus a no-copy
    row alignment requirement — ``n % 8 == 0`` for float stripes,
    ``n % 32 == 0`` for int8 ones (the int8 native tile is 32 sublanes;
    padding here would copy the giant matrix) — and the tighter Gram
    height bound when the bundle carries a Gram request.  ``elem_bits``
    names the element width of the stored matrix (int8 stripes read a
    quarter of the f32 bytes, so a smaller width only relaxes the VMEM
    envelope — the f32 gate stays the conservative bound).
    """
    del elem_bits  # narrower elements only shrink the stripe footprint
    if not _select_gate(n, d):
        return False
    if n % (32 if integer else 8):
        return False
    if gram and n > _GRAM_MAX_N:
        return False
    return True


def _rowstats_kernel(*refs, want_sq: bool, want_gram: bool, want_signs: bool,
                     n_dots: int, n_wsum: int, n_gd: int):
    it = iter(refs)
    x_ref = next(it)
    dv_ref = next(it) if n_dots else None
    w_ref = next(it) if n_wsum else None
    g_ref = next(it) if n_gd else None
    sq_ref = next(it) if want_sq else None
    gram_ref = next(it) if want_gram else None
    signs_ref = next(it) if want_signs else None
    dots_ref = next(it) if n_dots else None
    wsum_ref = next(it) if n_wsum else None
    gd_ref = next(it) if n_gd else None

    i = pl.program_id(0)
    raw = x_ref[...]                     # (npad, block_d) stripe
    integer = jnp.issubdtype(raw.dtype, jnp.integer)
    x = raw.astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        for ref in (sq_ref, gram_ref, signs_ref, dots_ref, gd_ref):
            if ref is not None:
                ref[...] = jnp.zeros_like(ref)

    if sq_ref is not None:
        if integer:
            # int8 stripes: exact int32 per-stripe sums (|q| <= 127 over
            # a 512-wide stripe is far below 2^31), f32 across stripes.
            xi = raw.astype(jnp.int32)
            sq_ref[...] += jnp.sum(xi * xi, axis=1,
                                   keepdims=True).astype(jnp.float32)
        else:
            sq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)
    if gram_ref is not None:
        if integer:
            # The MXU's native int8 path: int8 x int8 -> int32 stripe
            # contraction, EXACT, cast once into the f32 accumulator.
            gram_ref[...] += jax.lax.dot_general(
                raw, raw, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        else:
            gram_ref[...] += jax.lax.dot_general(
                x, x, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    if signs_ref is not None:
        # Compared on the f32 cast: Mosaic refuses comparisons on packed
        # bf16/int8 vectors on a v5e ("Target does not support this
        # comparison"), and the cast is exact for both.
        pos = jnp.sum((x > 0).astype(jnp.float32), axis=1, keepdims=True)
        neg = jnp.sum((x < 0).astype(jnp.float32), axis=1, keepdims=True)
        signs_ref[...] += jnp.concatenate([pos, neg], axis=1)
    if dots_ref is not None:
        v = dv_ref[...]  # (R, block_d) stripe of the replicated vectors
        dots_ref[...] += jax.lax.dot_general(
            x, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    if wsum_ref is not None:
        w = w_ref[...]  # (W, npad) row weights, replicated per stripe
        wsum_ref[...] = jax.lax.dot_general(
            w, x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    if gd_ref is not None:
        g = g_ref[...]  # (G, npad)
        t = jax.lax.dot_general(
            g, x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (G, block_d)
        gd_ref[...] += jax.lax.dot_general(
            x, t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (npad, G)


def row_stats_bundle(
    buf: jax.Array,
    *,
    sq: bool = False,
    gram: bool = False,
    signs: bool = False,
    dots: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    gram_dot: Optional[jax.Array] = None,
    d_true: Optional[int] = None,
    interpret: bool = False,
) -> Dict[str, jax.Array]:
    """Compute a fused statistics bundle in one HBM pass over ``buf``.

    Args:
        buf: ``(n, d_alloc)`` matrix, any float dtype (bf16 reads at half
            bandwidth; compute is f32) or int8 (the deferred-decode wire
            payload: one-byte stripes, int8 MXU self-contractions; the
            caller owns the per-row scale algebra).  Columns past
            ``d_true`` must be zero (stripe-alignment padding).
        sq/gram/signs: request the respective accumulator.
        dots: ``(R, d_true)`` replicated vectors to dot every row against.
        weights: ``(W, n)`` row-weight vectors for weighted row sums.
        gram_dot: ``(G, n)`` row-weight vectors for ``(buf buf^T) w``.
        d_true: true model width (zero counts and weighted-sum slicing);
            defaults to ``buf.shape[1]``.

    Returns a dict holding only the requested results: ``sq (n,)``,
    ``gram (n, n)``, ``signs (n, 3)`` (pos/neg/zero over the true
    width), ``dots (n, R)``, ``wsum (W, d_true)``, ``gram_dot (n, G)``.

    Small inputs are padded here (rows to a sublane multiple, columns to
    the stripe width) — ZERO padding, invisible to every accumulator; at
    giant scale callers allocate the buffer pre-padded (the streamed
    round does) so no copy happens.
    """
    n, d_alloc = buf.shape
    d_true = d_alloc if d_true is None else int(d_true)
    n_dots = 0 if dots is None else dots.shape[0]
    n_wsum = 0 if weights is None else weights.shape[0]
    n_gd = 0 if gram_dot is None else gram_dot.shape[0]
    if not (sq or gram or signs or n_dots or n_wsum or n_gd):
        raise ValueError("empty row-stats bundle")

    x = buf
    # int8 tiles are 32 sublanes tall (f32/bf16: 8); at giant scale the
    # gate (kernel_applicable integer=) makes this pad a no-op.
    sub = 32 if jnp.issubdtype(buf.dtype, jnp.integer) else 8
    npad = -(-n // sub) * sub
    if npad != n:
        x = jnp.concatenate(
            [x, jnp.zeros((npad - n, d_alloc), x.dtype)], axis=0)
    dpad = -(-d_alloc // _BLOCK_D) * _BLOCK_D
    if dpad != d_alloc:
        x = jnp.pad(x, ((0, 0), (0, dpad - d_alloc)))

    inputs = [x]
    in_specs = [pl.BlockSpec((npad, _BLOCK_D), lambda i: (0, i),
                             memory_space=pltpu.VMEM)]
    if n_dots:
        dv = dots.astype(jnp.float32)
        if dv.shape[1] != dpad:
            dv = jnp.pad(dv, ((0, 0), (0, dpad - dv.shape[1])))
        inputs.append(dv)
        in_specs.append(pl.BlockSpec((n_dots, _BLOCK_D), lambda i: (0, i),
                                     memory_space=pltpu.VMEM))
    for mat, count in ((weights, n_wsum), (gram_dot, n_gd)):
        if count:
            wm = mat.astype(jnp.float32)
            if wm.shape[1] != npad:
                wm = jnp.pad(wm, ((0, 0), (0, npad - wm.shape[1])))
            inputs.append(wm)
            in_specs.append(pl.BlockSpec((count, npad), lambda i: (0, 0),
                                         memory_space=pltpu.VMEM))

    out_specs, out_shapes, names = [], [], []

    def _out(name, shape, spec):
        names.append(name)
        out_shapes.append(jax.ShapeDtypeStruct(shape, jnp.float32))
        out_specs.append(spec)

    col_spec = pl.BlockSpec((npad, 1), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    if sq:
        _out("sq", (npad, 1), col_spec)
    if gram:
        _out("gram", (npad, npad),
             pl.BlockSpec((npad, npad), lambda i: (0, 0),
                          memory_space=pltpu.VMEM))
    if signs:
        _out("signs", (npad, 2),
             pl.BlockSpec((npad, 2), lambda i: (0, 0),
                          memory_space=pltpu.VMEM))
    if n_dots:
        _out("dots", (npad, n_dots),
             pl.BlockSpec((npad, n_dots), lambda i: (0, 0),
                          memory_space=pltpu.VMEM))
    if n_wsum:
        _out("wsum", (n_wsum, dpad),
             pl.BlockSpec((n_wsum, _BLOCK_D), lambda i: (0, i),
                          memory_space=pltpu.VMEM))
    if n_gd:
        _out("gram_dot", (npad, n_gd),
             pl.BlockSpec((npad, n_gd), lambda i: (0, 0),
                          memory_space=pltpu.VMEM))

    kernel = functools.partial(
        _rowstats_kernel, want_sq=sq, want_gram=gram, want_signs=signs,
        n_dots=n_dots, n_wsum=n_wsum, n_gd=n_gd,
    )
    raw = pl.pallas_call(
        kernel,
        grid=(dpad // _BLOCK_D,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        # The (n, n) f32 Gram block and its dot temporary share VMEM
        # with the stripe.
        compiler_params=stripe_compiler_params(
            npad, extra_bytes=4 * npad * npad * 4 if gram else 0),
        interpret=interpret,
    )(*inputs)

    out: Dict[str, jax.Array] = {}
    for name, val in zip(names, raw):
        if name == "sq":
            out["sq"] = val[:n, 0]
        elif name == "gram":
            out["gram"] = val[:n, :n]
        elif name == "signs":
            pn = val[:n]
            zero = d_true - pn.sum(axis=1, keepdims=True)
            out["signs"] = jnp.concatenate([pn, zero], axis=1)
        elif name == "dots":
            out["dots"] = val[:n]
        elif name == "wsum":
            out["wsum"] = val[:, :d_true]
        else:
            out["gram_dot"] = val[:n]
    return out

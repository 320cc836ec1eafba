"""Fused pallas finish for the streamed giant-federation round.

The streamed round's finish phase (:mod:`blades_tpu.parallel.streamed`)
is a chain of O(n*d) passes over the stored update matrix: cast the
bf16 chunk to f32, sanitize, forge the malicious rows, aggregate, and
accumulate row norms.  Chained as XLA ops those are ~10 full HBM round
trips over a ~10 GB matrix — at n=1000 x d=4.9M the finish costs ~300 ms
against a ~12 ms single-read floor.

This kernel fuses the whole finish into ONE HBM pass: each grid step
loads a full-height ``(n, block_d)`` column stripe into VMEM and, fully
in-core, (a) casts to f32, (b) zeroes rows with non-finite values
(stripe-local, the health-detection semantics of
:func:`blades_tpu.core.health.sanitize_updates` at stripe granularity;
the stripe is :func:`~blades_tpu.ops.pallas_select.stripe_cols` of the
matrix's height wide: 512 columns from 64 rows up, 3072 at 8 rows),
(c) computes the benign column statistics and overwrites malicious rows
with the forged row (ALIE ``mean + z*std``, IPM ``-scale*mean``, or the
Fang/Adaptive directed deviation with pre-drawn uniforms — the
coordinate-wise forges; ref: blades/adversaries/alie_adversary.py:27-45,
ipm_adversary.py:15-23, adaptive_adversary.py:23-67),
(d) reduces the column to the aggregate (Mean over clients, exact
radix-select Median, or Trimmedmean — same selection networks as
:mod:`blades_tpu.ops.pallas_select`), and (e) accumulates per-row
squared norms for the round metrics.

Two layouts of the matrix, one body (:func:`_compact_kernel`).  Tall
matrices (hundreds of clients) are ``(n, d)``: the rows lie on the
sublanes, a stripe ``(n, cols)`` fills its vregs, and a count over the
rows is a reduction across sublanes.  At 8-32 rows that is the wrong way
round: every count of the 16 radix steps is a sublane reduction over one
vreg, and the search's one-row values (``res``, ``cnt``, the forged key)
fill as many vregs as the whole stripe at an eighth of their lanes.  A
matrix whose blocks lie under a storage tile is therefore kept as row
planes, ``(rows, d // 128, 128)`` (parallel/streamed.py::compact_matrix,
which the one-row store needs for its own reasons: ops/pallas_store.py),
and the compact finish takes blocks ``(rows, s, 128)``,
``s x 128`` = :func:`~blades_tpu.ops.pallas_select.plane_cols` columns:
a count over the rows is then a plain add of whole vregs and a one-row
value is an eighth of the data (on a v5e, 8 x 4.1e8 bf16: 275 ms -> 81
ms; PERF.md §6, PR 32).  The body does not know which it has: per-column
values are ``(1,) + x.shape[1:]``, per-row values ``(rows, 1[, 1])``, and
the float sums over the rows are added in one order (:func:`_sum_rows`),
so both layouts of one matrix give the same aggregate and forged row to
the bit.  ``sanitize`` is local to a grid step's block in either.

Numerics: statistics run in f32 inside the kernel in the same formulas
as :func:`blades_tpu.adversaries.base.benign_mean_std` (ddof=1), but
reduction *order* differs from the XLA chunk path, so forged values can
differ in the last ulp — the selection aggregators then pick among
values containing those ulps.  Equivalence tests therefore use
tolerances (tests/test_pallas_round.py); the chunked path remains the
fallback for every configuration the kernel does not cover (DP, the
keyed Noise forge, row-geometry aggregators, n > 2048).  For the
Adaptive forge specifically, the caller pre-draws the ``(d,)`` uniforms
with the round's adversary key, so the FUSED path reproduces the DENSE
round's draw exactly — the chunked finish, which folds the key per
d-chunk, draws differently (both are valid attack streams).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from blades_tpu.ops.pallas_select import (
    _keys_of,
    _kth_key,
    _next_key_above,
    _vals_of,
    kernel_applicable,
    plane_cols,
    stripe_cols,
    stripe_compiler_params,
)


def _keys16_of(x):
    """Monotone uint32 keys living in the low 16 bits, for f32 values
    that are bf16-representable (low 16 mantissa bits zero).

    Such values carry 16 bits of entropy, so the radix select over them
    needs 16 bit-search steps, not 32 — the fused kernel's dominant cost
    halves.  Derived from :func:`_keys_of` by dropping the low half: for
    bf16-representable values the low 16 key bits are constant per sign
    (zeros for positives, ones for negatives), so the order lives
    entirely in the top half.  Stays in uint32 throughout — Mosaic has
    no 16-bit bitcasts/compares.
    """
    return _keys_of(x) >> 16


def _vals16_of(k):
    """Inverse of :func:`_keys16_of` (uint32 key -> f32 value).

    Negative values' dropped low key bits were all-ones (``~b`` of a
    zero low half), so reconstruct them before inverting.
    """
    k32 = k << 16
    neg = (k >> 15) == 0  # top bit of the 16-bit key clear => negative
    return _vals_of(jnp.where(neg, k32 | jnp.uint32(0xFFFF), k32))


def _kth_key16(keys, k: int):
    """16-step variant of :func:`_kth_key` for keys in [0, 0xFFFF]."""
    c = keys.shape[1]
    res = jnp.zeros((1, c), jnp.uint32)
    for bit in range(15, -1, -1):
        cand = res | jnp.uint32(1 << bit)
        cnt = jnp.sum((keys < cand).astype(jnp.int32), axis=0, keepdims=True)
        res = jnp.where(cnt <= k, cand, res)
    return res


def _next_key16_above(keys, v):
    """Smallest key strictly greater than ``v`` per column."""
    masked = jnp.where(keys > v, keys, jnp.uint32(0x10000)).astype(jnp.int32)
    return jnp.min(masked, axis=0, keepdims=True).astype(jnp.uint32)

def should_use(n: int, d: int) -> bool:
    """Use the fused finish for this round?  The shared kernel gate
    (backend / VMEM height bound / size floor / escape hatch, see
    :func:`blades_tpu.ops.pallas_select.kernel_applicable`) plus a
    sublane-alignment requirement: row padding inside ``fused_finish``
    would copy the giant matrix."""
    return kernel_applicable(n, d) and n % 8 == 0


def _count_lt_vpu(keys, cand):
    """Per-column count of rows below ``cand`` — VPU sublane reduction."""
    return jnp.sum((keys < cand).astype(jnp.int32), axis=0, keepdims=True)


def _count_lt_mxu(keys, cand):
    """Per-column count of rows below ``cand`` — MXU formulation.

    The radix select is VPU-bound (PERF_NOTES_r4: ~43 ms of the ~80 ms
    compact finish; 16 steps x compare+reduce over all rows).  The
    reduce half of each step is a plain row-sum of an indicator, which
    the MXU does as ``ones(1, n) @ indicator(n, c)`` at systolic-array
    throughput while the VPU only pays the compare+select.  Counts are
    exact in f32 far beyond the n <= 2048 kernel gate."""
    ind = jnp.where(keys < cand, 1.0, 0.0).astype(jnp.float32)
    ones = jnp.ones((1, keys.shape[0]), jnp.float32)
    cnt = jax.lax.dot_general(ones, ind, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return cnt.astype(jnp.int32)


def _kth_key16_mult(keys, k, fkey, mult: int, count=_count_lt_vpu):
    """:func:`_kth_key16` over the multiset ``keys + mult x fkey`` —
    ``fkey`` is a (1, c) virtual key counted ``mult`` times per column.
    ``k`` may be a static int or a (1, c) per-column rank vector.
    Indifferent to the trailing shape, as every helper of the compact
    kernel is: a per-column value is ``(1,) + keys.shape[1:]``."""
    res = jnp.zeros((1,) + keys.shape[1:], jnp.uint32)
    for bit in range(15, -1, -1):
        cand = res | jnp.uint32(1 << bit)
        cnt = count(keys, cand)
        cnt = cnt + mult * (fkey < cand).astype(jnp.int32)
        res = jnp.where(cnt <= k, cand, res)
    return res


def _next_key16_above_mult(keys, v, fkey):
    """Smallest key strictly greater than ``v`` over keys + the virtual
    forged key.  Mosaic has no unsigned min; 16-bit keys (<= 0x10000)
    fit int32 with order preserved."""
    nxt = _next_key16_above(keys, v)
    fnext = jnp.where(fkey > v, fkey, jnp.uint32(0x10000))
    m = jnp.minimum(
        jax.lax.bitcast_convert_type(nxt, jnp.int32),
        jax.lax.bitcast_convert_type(fnext, jnp.int32),
    )
    return jax.lax.bitcast_convert_type(m, jnp.uint32)


def _kth_key_mult(keys, k, fkey, mult: int, count=_count_lt_vpu):
    """32-step :func:`_kth_key16_mult` for full uint32 keys (f32 data)."""
    res = jnp.zeros((1,) + keys.shape[1:], jnp.uint32)
    for bit in range(31, -1, -1):
        cand = res | jnp.uint32(1 << bit)
        cnt = count(keys, cand)
        cnt = cnt + mult * (fkey < cand).astype(jnp.int32)
        res = jnp.where(cnt <= k, cand, res)
    return res


def _next_key_above_mult(keys, v, fkey):
    """Full-width variant; the min runs in int32 space via the
    order-preserving ``u ^ 0x8000_0000`` bias (no unsigned min in
    Mosaic)."""
    nxt = _next_key_above(keys, v)
    fnext = jnp.where(fkey > v, fkey, jnp.uint32(0xFFFFFFFF))
    bias = jnp.uint32(0x80000000)
    m = jnp.minimum(
        jax.lax.bitcast_convert_type(nxt ^ bias, jnp.int32),
        jax.lax.bitcast_convert_type(fnext ^ bias, jnp.int32),
    )
    return jax.lax.bitcast_convert_type(m, jnp.uint32) ^ bias


def _over_row(reduce, x):
    """``reduce`` (``jnp.sum``, ``jnp.all``) over all of a row, which is
    every axis but the first, to ``(rows, 1[, 1])``: one axis at a time,
    because Mosaic aborts on a reduction over two axes at once
    (``layout.h:320 Check failed: arr.size() >= layout_rank``), and the
    sublane axis of a plane first, where it is whole-vreg arithmetic."""
    for axis in range(1, x.ndim):
        x = reduce(x, axis=axis, keepdims=True)
    return x


def _sum_rows(x):
    """The FLOAT sum over the rows, ``(1,) + x.shape[1:]``, with the bits
    it has where the rows lie on the sublanes.

    Float addition is not associative, and the forged row is rounded to
    bf16 from a float32 mean and deviation: one last place of a sum flips
    that rounding in about one column of 10 000.  Mosaic's reduction
    across sublanes (measured on a v5e, libtpu 0.0.34, on 2048 columns
    each at 8, 10, 16 and 24 rows: all equal; PERF.md §6, PR 32) adds the
    vregs of 8 rows elementwise, in order, the rows past the last as
    zeros, and then folds the 8 sublanes as a butterfly of shifts 4, 2,
    1: ``((x0 + x4) + (x2 + x6)) + ((x1 + x5) + (x3 + x7))``.  A sum
    over the major axis of row planes would add them first to last; so
    there it is written out in the sublanes' order, the same count of
    adds, and the two layouts of one matrix give one aggregate and one
    forged row to the bit.  (Counts, minima and maxima have no order.)"""
    if x.ndim == 2:
        return jnp.sum(x, axis=0, keepdims=True)
    rows = x.shape[0]
    zero = jnp.zeros_like(x[:1])
    lane = [x[i:i + 1] if i < rows else zero for i in range(8)]
    for j in range(8, -(-rows // 8) * 8):
        lane[j % 8] = lane[j % 8] + (x[j:j + 1] if j < rows else zero)
    for shift in (4, 2, 1):
        lane = [lane[i] + lane[i + shift] for i in range(shift)]
    return lane[0]


def _row_weighted_colsum(m, wb, mxu: bool):
    """``sum(m * wb, axis=0)`` as (1, c): VPU reduction or an MXU
    ``wb.T @ m`` contraction — f32 accumulate, but the MXU multiplies
    f32 operands at its default (bf16-pass) precision."""
    if mxu:
        return jax.lax.dot_general(
            wb.reshape(1, -1), m, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return _sum_rows(m * wb)


def _forged_stripe(xs, wb, r_ref, forge, keys16: bool, mxu: bool = False):
    """The (1, c) forged row for this stripe from benign statistics —
    shared between the full kernel (which scatters it into malicious
    rows) and the compact kernel (which counts it with multiplicity).
    ``xs``: (rows, c) f32 with non-benign rows zeroed; ``wb``: (rows, 1)
    benign weights."""
    kind = forge[0]
    nb = jnp.maximum(jnp.sum(wb), 1.0)
    mean = _row_weighted_colsum(xs, wb, mxu) / nb
    if kind == "alie":
        z = forge[1]
        var = _row_weighted_colsum((xs - mean) ** 2, wb, mxu)
        std = jnp.sqrt(var / jnp.maximum(nb - 1.0, 1.0))
        forged = mean + z * std
    elif kind == "ipm":
        forged = -forge[1] * mean
    elif kind == "adaptive":
        # Fang directed deviation (the four sign-cases of
        # AdaptiveAdversary.on_updates_ready); r_ref carries the
        # pre-drawn per-coordinate uniforms.
        b = forge[1]
        r = r_ref[...]
        mx = jnp.max(jnp.where(wb > 0, xs, -jnp.inf), axis=0, keepdims=True)
        mn = jnp.min(jnp.where(wb > 0, xs, jnp.inf), axis=0, keepdims=True)
        s = jnp.sign(mean)
        neg_pos = r * ((b - 1.0) * mx) + mx
        neg_neg = r * ((1.0 / b - 1.0) * mx) + mx
        pos_pos = r * ((1.0 - 1.0 / b) * mn) + mn / b
        pos_neg = r * ((1.0 - b) * mn) + mn * b
        forged = jnp.where(
            s == -1.0,
            jnp.where(mx > 0, neg_pos, neg_neg),
            jnp.where(s == 1.0,
                      jnp.where(mn > 0, pos_pos, pos_neg),
                      mean),
        )
    else:  # pragma: no cover - guarded by the callers
        raise ValueError(f"unknown forge {kind!r}")
    if keys16:
        # bf16 storage: round the forged row to storage precision so
        # every matrix value is bf16-representable — the semantics of an
        # adversary writing into the same bf16 buffer, and what lets the
        # rank search run 16 steps instead of 32.
        forged = forged.astype(jnp.bfloat16).astype(jnp.float32)
    return forged


def _fused_kernel(x_ref, wb_ref, fm_ref, r_ref, o_ref, sq_ref, bad_ref, *,
                  n_true: int, forge: Optional[tuple], agg: tuple,
                  sanitize: bool, keys16: bool):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (n, c) stripe
    wb = wb_ref[...]                            # (n, 1) benign weight
    fm = fm_ref[...]                            # (n, 1) forge mask
    real = jnp.minimum(wb + fm, 1.0)            # real (non-padding) rows

    @pl.when(i == 0)
    def _init():
        sq_ref[...] = jnp.zeros_like(sq_ref)
        bad_ref[...] = jnp.zeros_like(bad_ref)

    if sanitize:
        row_ok = jnp.isfinite(x).all(axis=1, keepdims=True)
        row_bad = real * (1.0 - row_ok.astype(jnp.float32))
        x = jnp.where(row_bad > 0, 0.0, x)
        bad_ref[...] = jnp.maximum(bad_ref[...], row_bad)

    # Zeroed view of the padding rows for every summation (0 * inf = nan
    # otherwise); the rank computations re-mask them to +inf below.
    xs = jnp.where(real > 0, x, 0.0)

    if forge is not None:
        forged = _forged_stripe(xs, wb, r_ref, forge, keys16)
        xs = jnp.where(fm > 0, forged, xs)

    sq_ref[...] += jnp.sum(xs * xs, axis=1, keepdims=True)

    if keys16:
        # Every value in xs is bf16-representable here: benign rows come
        # from bf16 storage, forged rows were rounded above, padding is
        # +/-inf — so the 16-bit key space is exact.
        kth, nxt, vals, keys_of = (
            _kth_key16, _next_key16_above, _vals16_of, _keys16_of
        )
    else:
        kth, nxt, vals, keys_of = _kth_key, _next_key_above, _vals_of, _keys_of

    akind = agg[0]
    if akind == "mean":
        o_ref[...] = jnp.sum(xs, axis=0, keepdims=True) / n_true
    elif akind == "median":
        keys = keys_of(jnp.where(real > 0, xs, jnp.inf))
        k1, k2 = (n_true - 1) // 2, n_true // 2
        v1 = kth(keys, k1)
        if k2 == k1:
            o_ref[...] = vals(v1)
        else:
            cnt_le = jnp.sum((keys <= v1).astype(jnp.int32), axis=0,
                             keepdims=True)
            v2 = jnp.where(cnt_le >= k2 + 1, v1, nxt(keys, v1))
            o_ref[...] = (vals(v1) + vals(v2)) * 0.5
    elif akind == "trimmed":
        k_cut = agg[1]
        xm = jnp.where(real > 0, xs, jnp.inf)
        keys = keys_of(xm)
        vlo = kth(keys, k_cut)
        vhi = kth(keys, n_true - 1 - k_cut)
        flo, fhi = vals(vlo), vals(vhi)
        between = (keys > vlo) & (keys < vhi)
        sum_mid = jnp.sum(jnp.where(between, xm, 0.0), axis=0, keepdims=True)
        cnt_lt_lo = jnp.sum((keys < vlo).astype(jnp.int32), axis=0,
                            keepdims=True)
        eq_lo = jnp.sum((keys == vlo).astype(jnp.int32), axis=0,
                        keepdims=True)
        cnt_lt_hi = jnp.sum((keys < vhi).astype(jnp.int32), axis=0,
                            keepdims=True)
        eq_hi = jnp.sum((keys == vhi).astype(jnp.int32), axis=0,
                        keepdims=True)
        lo_keep = jnp.clip(
            jnp.minimum(cnt_lt_lo + eq_lo, n_true - k_cut)
            - jnp.maximum(cnt_lt_lo, k_cut), 0, None)
        hi_keep = jnp.clip(
            jnp.minimum(cnt_lt_hi + eq_hi, n_true - k_cut)
            - jnp.maximum(cnt_lt_hi, k_cut), 0, None)
        kept = n_true - 2 * k_cut
        total = sum_mid + lo_keep.astype(jnp.float32) * flo \
            + hi_keep.astype(jnp.float32) * fhi
        total = jnp.where(vlo == vhi, flo * kept, total)
        o_ref[...] = total / kept
    else:  # pragma: no cover - guarded by fused_finish
        raise ValueError(f"unknown aggregator {akind!r}")


def _compact_kernel(x_ref, wb_ref, *refs,
                    nb_true: int, mult: int, forge: tuple, agg: tuple,
                    sanitize: bool, keys16: bool,
                    radix_mxu: bool = False, stats_mxu: bool = False):
    """The benign-compacted finish: the matrix holds ONLY benign rows
    (malicious training was elided), and the forged row participates in
    the order statistics as a VIRTUAL row of multiplicity ``mult`` —
    every per-row pass (load, keys, radix counts) runs over ``nb`` rows
    instead of ``nb + mult``.

    One body for both layouts of the matrix.  A block is ``(rows, c)``,
    the rows on the sublanes, or ``(rows, s, 128)``, a row a plane of
    whole vregs: every per-column value is ``(1,) + x.shape[1:]``, every
    reduction over the rows is ``axis=0`` (across sublanes there, plain
    adds of vregs here), every per-row value ``(rows, 1[, 1])``.  ``refs``:
    the forge's uniforms where there are any (always in two dimensions,
    only for the adaptive forge on planes), then the four outputs."""
    *r_ref, o_ref, sq_ref, bad_ref, fr_ref = refs
    r_ref = r_ref[0] if r_ref else None
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (nbpad, c) benign stripe
    wb = wb_ref[...]                            # (nbpad, 1) real-row mask

    @pl.when(i == 0)
    def _init():
        sq_ref[...] = jnp.zeros_like(sq_ref)
        bad_ref[...] = jnp.zeros_like(bad_ref)

    if sanitize:
        row_ok = _over_row(jnp.all, jnp.isfinite(x))
        row_bad = wb * (1.0 - row_ok.astype(jnp.float32))
        x = jnp.where(row_bad > 0, 0.0, x)
        bad_ref[...] = jnp.maximum(bad_ref[...], row_bad)

    xs = jnp.where(wb > 0, x, 0.0)
    forged = _forged_stripe(xs, wb, r_ref, forge, keys16, mxu=stats_mxu)
    fr_ref[...] = forged
    if stats_mxu:
        # Row squared norms as an MXU contraction: (n, c) @ ones(c, 1).
        sq_ref[...] += jax.lax.dot_general(
            xs * xs, jnp.ones((xs.shape[1], 1), jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    else:
        sq_ref[...] += _over_row(jnp.sum, xs * xs)

    count = _count_lt_mxu if radix_mxu else _count_lt_vpu
    if keys16:
        kth = functools.partial(_kth_key16_mult, count=count)
        nxt, vals, keys_of = _next_key16_above_mult, _vals16_of, _keys16_of
    else:
        kth = functools.partial(_kth_key_mult, count=count)
        nxt, vals, keys_of = _next_key_above_mult, _vals_of, _keys_of

    n_tot = nb_true + mult
    akind = agg[0]
    if akind == "mean":
        o_ref[...] = (_sum_rows(xs) + mult * forged) / n_tot
        return
    keys = keys_of(jnp.where(wb > 0, xs, jnp.inf))
    fkey = keys_of(forged)
    if akind == "median":
        k1, k2 = (n_tot - 1) // 2, n_tot // 2
        v1 = kth(keys, k1, fkey, mult)
        if k2 == k1:
            o_ref[...] = vals(v1)
        else:
            cnt_le = (jnp.sum((keys <= v1).astype(jnp.int32), axis=0,
                              keepdims=True)
                      + mult * (fkey <= v1).astype(jnp.int32))
            v2 = jnp.where(cnt_le >= k2 + 1, v1, nxt(keys, v1, fkey))
            o_ref[...] = (vals(v1) + vals(v2)) * 0.5
    elif akind == "trimmed":
        k_cut = agg[1]
        xm = jnp.where(wb > 0, xs, jnp.inf)
        vlo = kth(keys, k_cut, fkey, mult)
        vhi = kth(keys, n_tot - 1 - k_cut, fkey, mult)
        flo, fhi = vals(vlo), vals(vhi)
        between = (keys > vlo) & (keys < vhi)
        f_between = ((fkey > vlo) & (fkey < vhi)).astype(jnp.float32)
        sum_mid = (_sum_rows(jnp.where(between, xm, 0.0))
                   + mult * forged * f_between)
        cnt_lt_lo = (jnp.sum((keys < vlo).astype(jnp.int32), axis=0,
                             keepdims=True)
                     + mult * (fkey < vlo).astype(jnp.int32))
        eq_lo = (jnp.sum((keys == vlo).astype(jnp.int32), axis=0,
                         keepdims=True)
                 + mult * (fkey == vlo).astype(jnp.int32))
        cnt_lt_hi = (jnp.sum((keys < vhi).astype(jnp.int32), axis=0,
                             keepdims=True)
                     + mult * (fkey < vhi).astype(jnp.int32))
        eq_hi = (jnp.sum((keys == vhi).astype(jnp.int32), axis=0,
                         keepdims=True)
                 + mult * (fkey == vhi).astype(jnp.int32))
        lo_keep = jnp.clip(
            jnp.minimum(cnt_lt_lo + eq_lo, n_tot - k_cut)
            - jnp.maximum(cnt_lt_lo, k_cut), 0, None)
        hi_keep = jnp.clip(
            jnp.minimum(cnt_lt_hi + eq_hi, n_tot - k_cut)
            - jnp.maximum(cnt_lt_hi, k_cut), 0, None)
        kept = n_tot - 2 * k_cut
        total = sum_mid + lo_keep.astype(jnp.float32) * flo \
            + hi_keep.astype(jnp.float32) * fhi
        total = jnp.where(vlo == vhi, flo * kept, total)
        o_ref[...] = total / kept
    else:  # pragma: no cover - guarded by fused_finish_compact
        raise ValueError(f"unknown aggregator {akind!r}")


def _pad_to_stripes(updates, rbuf, cols: int):
    """Zero-pad the matrix's and ``rbuf``'s axis 1 (the columns, or a
    plane's sublanes) to a whole number of ``cols``-wide blocks (padding
    columns aggregate to values the callers slice off).  Padding the
    matrix COPIES it: callers at giant scale allocate it aligned
    (parallel/streamed.py::compact_matrix) and only the one-row ``rbuf``
    is padded here.  Returns ``(updates, rbuf, dpad)``; ``rbuf`` may be
    ``None``."""
    def pad(a, to):
        widths = [(0, 0)] * a.ndim
        widths[1] = (0, to - a.shape[1])
        return jnp.pad(a, widths)

    d = updates.shape[1]
    dpad = -(-d // cols) * cols
    if dpad != d:
        updates = pad(updates, dpad)
    if rbuf is not None and rbuf.shape[1] != dpad:
        rbuf = pad(rbuf, dpad)
    return updates, rbuf, dpad


def _block_specs(npad: int, *tail: int):
    """The three block shapes of a fused finish: the ``(npad, cols)``
    stripe and the ``(1, cols)`` row, both walking the columns with the
    grid, and the resident ``(npad, 1)`` per-row column.  With a
    ``tail`` of ``(s, 128)`` the same three over planes: ``(npad, s,
    128)``, ``(1, s, 128)`` and ``(npad, 1, 1)``."""
    zeros = (0,) * (len(tail) - 1)
    return (
        pl.BlockSpec((npad,) + tail, lambda i: (0, i) + zeros,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((npad,) + (1,) * len(tail), lambda i: (0, 0) + zeros,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1,) + tail, lambda i: (0, i) + zeros,
                     memory_space=pltpu.VMEM),
    )


def parse_mxu_mode(mode: str) -> Tuple[bool, bool]:
    """``(radix_mxu, stats_mxu)`` from a finish-mode string: ``""``
    (VPU reductions), ``"counts"`` (radix counts on the MXU — bit-exact,
    small integers are exact in f32) or ``"all"`` (also the forged-row
    mean/var and row-norm reductions — at the MXU's default bf16-pass
    precision: measured 4.9e-3 relative on the forged row and 3e-4 on
    the row norms on a v5e, tools/chip_kernels.py)."""
    return mode in ("counts", "all"), mode == "all"


def _mxu_mode_resolve(mxu_finish: Optional[str]) -> Tuple[bool, bool]:
    """``(radix_mxu, stats_mxu)`` for the un-jitted
    :func:`fused_finish_compact` wrapper, resolved at CALL time.

    Precedence: the ``BLADES_TPU_MXU_FINISH`` env var when SET (the
    explicit per-process override, kept from the PR 4 fix) beats the
    caller's config-resolved ``mxu_finish`` (the first-class
    ``resources(mxu_finish=...)`` field the autotuner selects per
    plan), which beats the ``""`` default."""
    import os

    env = os.environ.get("BLADES_TPU_MXU_FINISH")  # blades-lint: disable=jit-purity — read per call by the un-jitted dispatch wrapper, never traced (the r5 fix)
    if env is not None:
        return parse_mxu_mode(env)
    return parse_mxu_mode(mxu_finish or "")


def fused_finish_compact(
    updates: jax.Array,
    forge_noise: Optional[jax.Array] = None,
    *,
    forged_mult: int,
    forge: tuple,
    agg: tuple = ("median",),
    sanitize: bool = False,
    num_real: Optional[int] = None,
    interpret: bool = False,
    radix_mxu: Optional[bool] = None,
    stats_mxu: Optional[bool] = None,
    mxu_finish: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Forge + aggregate over a BENIGN-ONLY update matrix in one pass.

    Thin un-jitted wrapper: ``radix_mxu``/``stats_mxu`` default to the
    resolved finish mode — the ``BLADES_TPU_MXU_FINISH`` env var when
    set (explicit per-process override), else the caller's
    config-resolved ``mxu_finish`` string (``resources(mxu_finish=...)``,
    selectable per plan by the execution autotuner), else ``""`` —
    resolved HERE — outside the jit — on every call, then passed to the
    jitted body as concrete static booleans.  Resolving inside the
    traced body (the previous design) cached the first call's mode
    under the ``None`` statics, so toggling the env after first call
    silently kept the stale mode (ADVICE r5 #1).  Callers that jit
    AROUND this wrapper (the streamed round's ``_finish_fused_compact``)
    still pin the mode at their own trace time — that is their cache,
    not this one.  See :func:`_fused_finish_compact_jit` for the full
    contract.
    """
    if radix_mxu is None or stats_mxu is None:
        env_radix, env_stats = _mxu_mode_resolve(mxu_finish)
        if radix_mxu is None:
            radix_mxu = env_radix
        if stats_mxu is None:
            stats_mxu = env_stats
    return _fused_finish_compact_jit(
        updates, forge_noise, forged_mult=forged_mult, forge=forge, agg=agg,
        sanitize=sanitize, num_real=num_real, interpret=interpret,
        radix_mxu=bool(radix_mxu), stats_mxu=bool(stats_mxu),
    )


@functools.partial(
    jax.jit,
    static_argnames=("forged_mult", "forge", "agg", "sanitize", "num_real",
                     "interpret", "radix_mxu", "stats_mxu", "cols"),
)
def _fused_finish_compact_jit(
    updates: jax.Array,
    forge_noise: Optional[jax.Array] = None,
    *,
    forged_mult: int,
    forge: tuple,
    agg: tuple = ("median",),
    sanitize: bool = False,
    num_real: Optional[int] = None,
    interpret: bool = False,
    radix_mxu: bool = False,
    stats_mxu: bool = False,
    cols: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The jitted body of :func:`fused_finish_compact`.

    The malicious lanes' training was elided (parallel/streamed.py's
    ``malicious_prefix``), so the stored matrix holds just the ``nb``
    benign rows; the forged row enters the aggregation as a virtual row
    of multiplicity ``forged_mult``.  Exactly equivalent to
    :func:`fused_finish` on the full ``(nb + forged_mult, d)`` matrix
    with the malicious rows scattered (tests/test_pallas_round.py), at
    75% of its per-row work and HBM footprint for the benchmark's
    quarter-byzantine scale.

    Returns ``(agg_vec (d,), sq_norms (nb,), bad (nb,), forged (d,))`` —
    the caller reconstructs malicious-row norms as ``||forged||^2``.

    ``updates`` is ``(nb, d)``, or ``(nb, d // 128, 128)`` where the
    matrix keeps a row a plane (the module text; ``d`` is then the
    allocated width, and ``forge_noise`` is as wide).  On planes the grid
    walks blocks ``(nb, s, 128)``, the two d-sized outputs are ``(1, d //
    128, 128)`` inside (flat again on return: the same bytes), the rows
    need no sublane padding (``num_real`` is accepted, and rows past it
    are masked, whatever they hold), the MXU variants are off (there is
    no sublane row to contract over), and the forge's uniforms are an
    operand only where the forge is adaptive: the two-dimensional call
    keeps its zero ``(1, d)`` buffer for the other forges, and with it
    the program it always built.

    ``num_real``: benign row count when the CALLER pre-padded the matrix
    to a sublane multiple with +inf rows (row padding here would
    concat-copy the giant matrix; the streamed round allocates padded
    and writes the +inf rows once).  Default: every row is real.

    ``radix_mxu``: run each radix step's row count as an MXU
    ``ones @ indicator`` contraction instead of a VPU reduction —
    BIT-EXACT (counts are small integers, exact in f32).  ``stats_mxu``:
    also run the forged-row mean/var and row-norm reductions on the MXU
    — at its default bf16-pass precision (see :func:`parse_mxu_mode`),
    NOT ulp-level.  Here both are concrete
    static booleans; the public wrapper resolves the
    ``BLADES_TPU_MXU_FINISH`` env default per call.

    ``cols``: the columns of one grid step, for the sake of the tests and
    of ``tools/chip_kernels.py --sweep`` only (they force 512, or sweep
    it, to compare widths); the public wrapper passes none and the width
    is :func:`~blades_tpu.ops.pallas_select.stripe_cols` of the matrix's
    height, or :func:`~blades_tpu.ops.pallas_select.plane_cols` of it on
    planes (``s = cols // 128`` sublanes of a plane).
    """
    nb, *tail = updates.shape
    plane = len(tail) == 2
    if plane and tail[1] != 128:
        raise ValueError(f"a row-plane matrix is (rows, d // 128, 128), "
                         f"got {updates.shape}")
    d = math.prod(tail)
    one = (1,) * len(tail)
    if num_real is not None:
        if not (0 < num_real <= nb):
            raise ValueError(f"num_real={num_real} out of range for {nb} rows")
        nb = num_real
    if forge is None:
        raise ValueError("compact finish requires a forge (elision is "
                         "only sound when forged rows replace training)")
    if forged_mult <= 0:
        raise ValueError(f"forged_mult must be positive, got {forged_mult}")
    n_tot = nb + forged_mult
    if agg[0] == "trimmed" and n_tot <= 2 * agg[1]:
        raise ValueError(f"trimmed mean needs > {2 * agg[1]} rows, "
                         f"got {n_tot}")
    if forge[0] == "adaptive":
        if forge_noise is None:
            raise ValueError("('adaptive', b) forging needs forge_noise")
        if forge_noise.shape != (d,):
            raise ValueError(
                f"forge_noise must be ({d},), got {forge_noise.shape}"
            )
        rbuf = forge_noise.astype(jnp.float32).reshape((1, *tail))
    elif plane:
        # No forge reads it: at d = 4.1e8 a zero row is 1.66 GB.
        rbuf = None
    else:
        rbuf = jnp.zeros((1, d), jnp.float32)
    if plane:
        # The rows lie on the major axis: any number of them is whole
        # vregs, and the MXU variants (contractions over sublane rows)
        # have nothing to contract.
        npad = updates.shape[0]
        wb = (jnp.arange(npad) < nb).astype(jnp.float32).reshape(npad, *one)
        radix_mxu = stats_mxu = False
    elif num_real is not None:
        # Caller pre-padded to a sublane multiple with +inf rows.
        npad = updates.shape[0]
        if npad % 8:
            raise ValueError(
                f"pre-padded matrix height {npad} is not a sublane multiple")
        wb = (jnp.arange(npad) < nb).astype(jnp.float32)[:, None]
    else:
        wb = jnp.ones((nb, 1), jnp.float32)
        npad = -(-nb // 8) * 8
        if npad != nb:
            pad = jnp.full((npad - nb, d), jnp.inf, updates.dtype)
            updates = jnp.concatenate([updates, pad], axis=0)
            wb = jnp.concatenate(
                [wb, jnp.zeros((npad - nb, 1), jnp.float32)], axis=0)
    cols = cols or (plane_cols(npad) if plane else stripe_cols(npad))
    # What the grid walks along axis 1: columns, or a plane's sublanes.
    step = cols // 128 if plane else cols
    updates, rbuf, dpad = _pad_to_stripes(updates, rbuf, step)
    block = (step, 128) if plane else (cols,)

    kernel = functools.partial(
        _compact_kernel, nb_true=nb, mult=forged_mult, forge=forge, agg=agg,
        sanitize=sanitize, keys16=updates.dtype == jnp.bfloat16,
        radix_mxu=radix_mxu, stats_mxu=stats_mxu,
    )
    stripe, rows1, row = _block_specs(npad, *block)
    row_shape = jax.ShapeDtypeStruct((1, dpad, *tail[1:]), jnp.float32)
    rows1_shape = jax.ShapeDtypeStruct((npad, *one), jnp.float32)
    agg_vec, sq, bad, forged = pl.pallas_call(
        kernel,
        grid=(dpad // step,),
        in_specs=[stripe, rows1] + [row] * (rbuf is not None),
        out_specs=[row, rows1, rows1, row],
        out_shape=[row_shape, rows1_shape, rows1_shape, row_shape],
        compiler_params=stripe_compiler_params(npad, cols=cols),
        interpret=interpret,
    )(updates, wb, *(() if rbuf is None else (rbuf,)))
    flat = ((lambda v: v.reshape(-1)[:d]) if plane
            else (lambda v: v[0, :d]))
    per_row = (slice(nb),) + (0,) * len(tail)
    return flat(agg_vec), sq[per_row], bad[per_row] > 0, flat(forged)


def fused_finish(
    updates: jax.Array,
    malicious: jax.Array,
    forge_noise: Optional[jax.Array] = None,
    *,
    forge: Optional[tuple] = None,
    agg: tuple = ("median",),
    sanitize: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forge + aggregate the update matrix in one HBM pass.

    Args:
        updates: ``(n, d)`` stacked client updates, any float dtype
            (bf16 storage reads at half bandwidth; compute is f32).
        malicious: ``(n,)`` bool forge mask.
        forge_noise: ``(d,)`` pre-drawn per-coordinate uniforms, required
            by ``("adaptive", b)`` (drawing outside the kernel keeps it
            RNG-free and lets the caller reproduce the dense round's
            draw exactly).
        forge: ``None`` (no adversary), ``("alie", z_max)``,
            ``("ipm", scale)`` or ``("adaptive", b)``.
        agg: ``("mean",)``, ``("median",)`` or ``("trimmed", k_cut)``
            with ``k_cut`` rows dropped per side.
        sanitize: zero non-finite rows (stripe-local: within the
            ``stripe_cols(n)`` columns of the value's stripe) and report
            them.

    Returns:
        ``(agg_vec, sq_norms, bad)`` — the ``(d,)`` f32 aggregate, the
        ``(n,)`` per-row squared norms of the post-forge matrix, and the
        ``(n,)`` bool row-unhealthy flags (all-False when ``sanitize``
        is off).
    """
    return _fused_finish_jit(updates, malicious, forge_noise, forge=forge,
                             agg=agg, sanitize=sanitize, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("forge", "agg", "sanitize", "interpret", "cols"),
)
def _fused_finish_jit(
    updates: jax.Array,
    malicious: jax.Array,
    forge_noise: Optional[jax.Array] = None,
    *,
    forge: Optional[tuple] = None,
    agg: tuple = ("median",),
    sanitize: bool = False,
    interpret: bool = False,
    cols: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The jitted body of :func:`fused_finish`; ``cols`` as in
    :func:`_fused_finish_compact_jit` (the tests' and the sweep's only)."""
    n, d = updates.shape
    if agg[0] == "trimmed" and n <= 2 * agg[1]:
        raise ValueError(f"trimmed mean needs > {2 * agg[1]} rows, got {n}")
    if forge is not None and forge[0] == "adaptive":
        if forge_noise is None:
            raise ValueError("('adaptive', b) forging needs forge_noise")
        if forge_noise.shape != (d,):
            raise ValueError(
                f"forge_noise must be ({d},), got {forge_noise.shape}"
            )
        rbuf = forge_noise.astype(jnp.float32)[None, :]
    else:
        rbuf = jnp.zeros((1, d), jnp.float32)
    wb = jnp.where(malicious, 0.0, 1.0)[:, None].astype(jnp.float32)
    fm = malicious[:, None].astype(jnp.float32)
    # Row padding: +inf rows with wb = fm = 0 are invisible to the
    # statistics and sort above every real value, so ranks over the true
    # n are unchanged (same trick as pallas_select._pad_rows).
    npad = -(-n // 8) * 8
    if npad != n:
        pad = jnp.full((npad - n, d), jnp.inf, updates.dtype)
        updates = jnp.concatenate([updates, pad], axis=0)
        z = jnp.zeros((npad - n, 1), jnp.float32)
        wb = jnp.concatenate([wb, z], axis=0)
        fm = jnp.concatenate([fm, z], axis=0)
    cols = cols or stripe_cols(npad)
    updates, rbuf, dpad = _pad_to_stripes(updates, rbuf, cols)

    kernel = functools.partial(
        _fused_kernel, n_true=n, forge=forge, agg=agg, sanitize=sanitize,
        keys16=updates.dtype == jnp.bfloat16,
    )
    stripe, rows1, row = _block_specs(npad, cols)
    agg_vec, sq, bad = pl.pallas_call(
        kernel,
        grid=(dpad // cols,),
        in_specs=[stripe, rows1, rows1, row],
        out_specs=[row, rows1, rows1],
        out_shape=[
            jax.ShapeDtypeStruct((1, dpad), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
        ],
        compiler_params=stripe_compiler_params(npad, cols=cols),
        interpret=interpret,
    )(updates, wb, fm, rbuf)
    return agg_vec[0, :d], sq[:n, 0], bad[:n, 0] > 0

"""Pallas TPU kernels for column order statistics (median, trimmed mean).

The coordinate-wise robust aggregators need per-column order statistics of
the ``(n, d)`` update matrix — at the 1000-client scale, ``jnp.sort``
lowers to XLA's bitonic network: ~log²(n) ≈ 55 full HBM round trips over a
matrix that is hundreds of MB per chunk.  That sort is ~60% of the
benchmark round (profiled: Median rounds 3.04 s vs Mean rounds 1.22 s at
n=1000, d=4.9M).

These kernels make aggregation a SINGLE HBM pass: each grid step loads a
full-height ``(n, block_d)`` column stripe into VMEM and computes exact
order statistics in-core via binary bit-search over monotone uint32 keys
(the classic radix-select): for each of 32 bits, count how many keys fall
below the candidate prefix — O(32·n) VPU compares per column, no data
movement.  Exactness matches ``jnp.sort``-based selection bit-for-bit on
non-NaN data; NaNs of either sign are mapped to the maximum key, matching
jnp.sort's NaN-last ORDER exactly (a selected NaN comes back canonical
rather than payload-preserving).

Used by :class:`blades_tpu.ops.aggregators.Median` / ``Trimmedmean`` when
running on a TPU backend with a large matrix, and directly by the
single-chip streamed round (:mod:`blades_tpu.parallel.streamed`).

The stripe's width.  The kernels here and the row statistics
(:mod:`blades_tpu.ops.pallas_rowstats`) walk the matrix in stripes of
``_BLOCK_D`` = 512 columns at any height.  The fused finishes
(:mod:`blades_tpu.ops.pallas_round`) take theirs from the matrix's height,
:func:`stripe_cols`: 512 from 64 rows up, wider below (3072 at 8 rows),
because a grid step has a fixed cost that four vregs of data do not pay
for.  Whoever allocates a matrix for them pads its columns to
:func:`stripe_padded`, a multiple of 512, so every kernel here is served
by the same allocation.  ``sanitize`` in the fused finishes is
stripe-local, so its granularity is that width too: a non-finite value
blanks its row over the columns of its stripe.  A matrix whose rows are
planes (``(rows, d // 128, 128)``: blocks under a storage tile,
parallel/streamed.py::compact_matrix) is walked by the compact fused
finish alone, in blocks of :func:`plane_cols` columns.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Narrowest column stripe of a grid step: the width of every stripe kernel
# that keeps one width at any height (the rank-select kernels here,
# ops/pallas_rowstats.py), and of the fused finishes from 64 rows up.
_BLOCK_D = 512

# A ``(1, cols)`` float32 value fills 8 sublanes: as many vregs, and as
# much VMEM, as a whole 8-row stripe.
_ONE_ROW = 8

# What one grid step of a fused finish (ops/pallas_round.py) works on, in
# float32 bytes: its stripe and the two one-row values a radix step keeps
# beside it (``res`` and ``cnt``), ``(rows + 16) x stripe_cols(rows) x 4``.  A grid step costs ~0.33 us
# before it has done anything, and at 8 rows x 512 columns (4 vregs of
# data) that was most of a step's 0.6 us; but the radix search goes over
# the stripe 16 times, and past 72 vregs each pass goes through VMEM
# again.  Chosen by sweeping the width at 8, 16, 24, 32 and 64 rows on a
# v5e (``tools/chip_kernels.py --sweep``; the readings are in PERF.md §6,
# PR 30): 3072 columns at 8 rows, 2048 at 16, 1536 at 24 and 32, 512 from
# 64 rows up.
_STRIPE_BUDGET = 288 << 10


def stripe_cols(rows: int) -> int:
    """Stripe width of the fused finishes at a matrix ``rows`` high: the
    multiple of ``_BLOCK_D`` at which the stripe and the radix search's
    one-row values fill ``_STRIPE_BUDGET``, never under ``_BLOCK_D``.  A
    function of the height alone, so whoever allocates the matrix
    (parallel/streamed.py::step, the tools) pads its columns to the
    width the kernel will use (:func:`stripe_padded`).  512 from 64 rows
    up: the federations of hundreds of clients compile the programs they
    always did.
    """
    rows = -(-rows // 8) * 8 + 2 * _ONE_ROW
    return max(_BLOCK_D, _STRIPE_BUDGET // (rows * 4) // _BLOCK_D * _BLOCK_D)


def stripe_padded(d: int, rows: int) -> int:
    """``d`` columns rounded up to a whole number of the fused finishes'
    stripes at a matrix ``rows`` high: the width to ALLOCATE the matrix
    at, so that no pad inside the call copies it."""
    cols = stripe_cols(rows)
    return -(-d // cols) * cols


# The same for a matrix whose rows are planes, ``(rows, d // 128, 128)``
# (the layout of a matrix whose blocks lie under a storage tile:
# parallel/streamed.py::compact_matrix): a block of the fused compact
# finish there is ``(rows, s, 128)``, and a one-row value is ``(1, s,
# 128)``, an eighth of what it fills on the sublanes, so the search's two
# are reckoned as two rows.  Chosen by the same sweep over 6.6 GB of bf16
# on a v5e (``tools/chip_kernels.py --sweep planes``; PERF.md §6, PR 32):
# the time is flat within 2% over 6144-10240 columns at 8 rows (79-81 ms
# the whole call, 88 at 4096, 86 at 12288), over 4096-6144 at 16 and at 24
# rows (68 and 64 ms; 90 and 75 at 2048), and this budget gives 10240,
# 6144 and 4096.
_PLANE_BUDGET = 448 << 10
_PLANE_STEP = 16 * 128  # whole bf16 vregs: 16 sublanes of 128 lanes


def plane_cols(rows: int) -> int:
    """Columns (``s x 128``) of one grid step of the compact finish over
    a row-plane matrix ``rows`` high: the multiple of 2048 (16 sublanes,
    whole vregs of either storage width) at which the block and the
    search's two one-row values fill ``_PLANE_BUDGET``; under the 8 rows
    at which the kernels' gate opens, the width at 8."""
    return max(_PLANE_STEP, _PLANE_BUDGET // ((max(rows, 8) + 2) * 4)
               // _PLANE_STEP * _PLANE_STEP)


def stripe_compiler_params(rows: int, extra_bytes: int = 0,
                           cols: int = _BLOCK_D):
    """Mosaic parameters for a kernel that holds a full-height
    ``(rows, cols)`` stripe in VMEM (``cols``: ``_BLOCK_D``, or the
    fused finishes' :func:`stripe_cols`).

    libtpu's default scoped-VMEM limit is 16 MiB, and a stripe kernel
    needs several stripe-sized buffers at once: the double-buffered
    input, the uint32 keys and the compare temporaries of the radix
    search.  Measured on a v5e (libtpu 0.0.34): 22.1 MiB for the f32
    trimmed finish at ``rows=2048`` — 5.5 f32 stripes — which the
    default refuses.  Eight f32 stripes (plus ``extra_bytes`` for
    non-stripe residents such as a Gram block) clears every kernel at
    the gate's height bound; below 16 MiB the default stands.

    A short, wide stripe also pays for its ONE-ROW residents, which a
    tall one can forget: a ``(1, cols)`` float32 block (the forge's
    uniforms, the aggregate, the forged row: each double-buffered) and
    every ``(1, cols)`` temporary of the radix search (``res``,
    ``cand``, ``cnt``) fills 8 sublanes, so each costs as much VMEM as
    a whole 8-row stripe.  Sixteen of them are reckoned (at the widths
    :func:`stripe_cols` gives, 1.5 MiB at most).
    """
    need = (8 * rows + 16 * _ONE_ROW) * cols * 4 + extra_bytes
    return pltpu.CompilerParams(vmem_limit_bytes=max(16 << 20, need))


_trace_scope = threading.local()


@contextlib.contextmanager
def auto_partitioned():
    """Scope for TRACING a program that GSPMD will partition (``jit``
    with shardings over several devices, :mod:`blades_tpu.parallel.
    sharded`).  A Mosaic kernel cannot be partitioned automatically —
    its lowering raises ``NotImplementedError`` unless the call sits
    inside a ``shard_map`` — so inside this scope every kernel gate says
    no and the ``jnp`` paths trace instead.  Thread-local: a prefetcher
    thread tracing its own program is not affected."""
    prev = getattr(_trace_scope, "auto_partitioned", False)
    _trace_scope.auto_partitioned = True
    try:
        yield
    finally:
        _trace_scope.auto_partitioned = prev


def kernel_applicable(n: int, d: int) -> bool:
    """Shared gate for the rank-select kernels here and the fused round
    kernel (:mod:`blades_tpu.ops.pallas_round`): TPU backend, not under
    :func:`auto_partitioned`, tall enough to select from, short enough
    that full-height ``(n, _BLOCK_D)`` stripes fit VMEM under
    :func:`stripe_compiler_params`' budget (every kernel behind this
    gate compiles at n = 2048 on a v5e: tools/chip_kernels.py), and big
    enough that a single-pass kernel beats the fused-but-multi-pass XLA
    sort.  ``BLADES_TPU_NO_PALLAS=1`` (read per call) is the escape
    hatch forcing the jnp paths."""
    if bool(int(os.environ.get("BLADES_TPU_NO_PALLAS", "0"))):  # blades-lint: disable=jit-purity — documented fresh-process escape hatch, resolved at trace time by contract (docstring)
        return False
    if getattr(_trace_scope, "auto_partitioned", False):
        return False
    return (jax.default_backend() == "tpu" and 8 <= n <= 2048
            and n * d >= (1 << 22))


def should_use(x: jax.Array) -> bool:
    """Use the rank-select kernels for this matrix?"""
    return (
        x.dtype == jnp.float32
        and x.ndim == 2
        and kernel_applicable(x.shape[0], x.shape[1])
    )


def _keys_of(x):
    """Monotone f32 -> uint32 map: order of keys == IEEE order of floats
    (negatives flipped entirely, positives offset past them).  ALL NaNs —
    either sign — map to the maximum key, matching ``jnp.sort``'s
    NaN-last semantics (a raw sign-bit NaN would otherwise sort first and
    shift every selected rank)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    neg = (b >> 31) == 1
    key = jnp.where(neg, ~b, b | jnp.uint32(0x80000000))
    return jnp.where(jnp.isnan(x), jnp.uint32(0xFFFFFFFF), key)


def _vals_of(k):
    """Inverse of :func:`_keys_of`."""
    pos = (k >> 31) == 1
    b = jnp.where(pos, k & jnp.uint32(0x7FFFFFFF), ~k)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _kth_key(keys, k: int):
    """Key value of the k-th smallest (0-indexed) element per column.

    ``keys``: (n, c) uint32.  Returns (1, c) uint32.  Classic 32-step
    binary search on the bit prefix: keep a bit iff at most ``k`` keys are
    strictly below the candidate prefix.  Unrolled so every bit mask is a
    compile-time constant.
    """
    c = keys.shape[1]
    res = jnp.zeros((1, c), jnp.uint32)
    for bit in range(31, -1, -1):
        cand = res | jnp.uint32(1 << bit)
        cnt = jnp.sum((keys < cand).astype(jnp.int32), axis=0, keepdims=True)
        res = jnp.where(cnt <= k, cand, res)
    return res


def _next_key_above(keys, v):
    """Smallest key strictly greater than ``v`` per column (one pass).

    Mosaic has no unsigned reductions, so the min runs in int32 space via
    the order-preserving ``u ^ 0x8000_0000`` bias."""
    big = jnp.uint32(0xFFFFFFFF)
    masked_keys = jnp.where(keys > v, keys, big)
    bias = jnp.uint32(0x80000000)
    as_i32 = jax.lax.bitcast_convert_type(masked_keys ^ bias, jnp.int32)
    m = jnp.min(as_i32, axis=0, keepdims=True)
    return jax.lax.bitcast_convert_type(m, jnp.uint32) ^ bias


def _median_kernel(x_ref, o_ref, *, n_true: int):
    keys = _keys_of(x_ref[...])
    k1, k2 = (n_true - 1) // 2, n_true // 2
    v1 = _kth_key(keys, k1)
    if k2 == k1:
        o_ref[...] = _vals_of(v1)
    else:
        # Even n: the (k1+1)-th order stat is the next distinct key above
        # v1 — unless v1 is duplicated across the boundary, in which case
        # it IS v1.  cnt_le counts members <= v1; if more than k1+1, the
        # duplicate run covers rank k2.
        cnt_le = jnp.sum((keys <= v1).astype(jnp.int32), axis=0, keepdims=True)
        v2 = jnp.where(cnt_le >= k2 + 1, v1, _next_key_above(keys, v1))
        o_ref[...] = (_vals_of(v1) + _vals_of(v2)) * 0.5


def _trimmed_mean_kernel(x_ref, o_ref, *, n_true: int, k_cut: int):
    x = x_ref[...]
    keys = _keys_of(x)
    lo_rank, hi_rank = k_cut, n_true - 1 - k_cut
    vlo = _kth_key(keys, lo_rank)
    vhi = _kth_key(keys, hi_rank)
    flo, fhi = _vals_of(vlo), _vals_of(vhi)

    strictly_between = (keys > vlo) & (keys < vhi)
    sum_mid = jnp.sum(jnp.where(strictly_between, x, 0.0), axis=0,
                      keepdims=True)
    # Tie corrections: sorted positions of the vlo duplicate run are
    # [cnt_lt_lo, cnt_lt_lo + eq_lo); we keep its overlap with the
    # retained rank window [k_cut, n - k_cut).  Same for vhi.
    cnt_lt_lo = jnp.sum((keys < vlo).astype(jnp.int32), axis=0, keepdims=True)
    eq_lo = jnp.sum((keys == vlo).astype(jnp.int32), axis=0, keepdims=True)
    cnt_lt_hi = jnp.sum((keys < vhi).astype(jnp.int32), axis=0, keepdims=True)
    eq_hi = jnp.sum((keys == vhi).astype(jnp.int32), axis=0, keepdims=True)
    lo_keep = jnp.clip(
        jnp.minimum(cnt_lt_lo + eq_lo, n_true - k_cut)
        - jnp.maximum(cnt_lt_lo, k_cut),
        0, None,
    )
    hi_keep = jnp.clip(
        jnp.minimum(cnt_lt_hi + eq_hi, n_true - k_cut)
        - jnp.maximum(cnt_lt_hi, k_cut),
        0, None,
    )
    kept = n_true - 2 * k_cut
    total = sum_mid + lo_keep.astype(jnp.float32) * flo \
        + hi_keep.astype(jnp.float32) * fhi
    # Identical lo/hi value (the whole retained window is one duplicate
    # run): the generic formula would count the run twice.
    total = jnp.where(vlo == vhi, flo * kept, total)
    o_ref[...] = total / kept


def _pad_cols(x, block_d):
    d = x.shape[1]
    dpad = -(-d // block_d) * block_d
    if dpad != d:
        x = jnp.pad(x, ((0, 0), (0, dpad - d)))
    return x, d


def _pad_rows(x):
    """Pad the client axis to a sublane multiple with +inf (sorts above
    every finite value and above no NaN, so true ranks are unchanged)."""
    n = x.shape[0]
    npad = -(-n // 8) * 8
    if npad != n:
        x = jnp.concatenate(
            [x, jnp.full((npad - n, x.shape[1]), jnp.inf, x.dtype)], axis=0
        )
    return x, n


def _run_columnwise(kernel, x, interpret):
    x, d = _pad_cols(x, _BLOCK_D)
    dpad = x.shape[1]
    out = pl.pallas_call(
        kernel,
        grid=(dpad // _BLOCK_D,),
        in_specs=[
            pl.BlockSpec((x.shape[0], _BLOCK_D), lambda i: (0, i),
                         memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((1, _BLOCK_D), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, dpad), jnp.float32),
        compiler_params=stripe_compiler_params(x.shape[0]),
        interpret=interpret,
    )(x)
    return out[0, :d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def column_median(x: jax.Array, interpret: bool = False) -> jax.Array:
    """Exact coordinate-wise median over rows of ``x`` (n, d) -> (d,).

    Bit-for-bit equal to ``(lo + hi) / 2`` of the two central order
    statistics, i.e. :func:`blades_tpu.ops.masked.median` with a full
    mask.  One HBM pass instead of a bitonic sort.
    """
    x, n = _pad_rows(x.astype(jnp.float32))
    return _run_columnwise(
        functools.partial(_median_kernel, n_true=n), x, interpret
    )


@functools.partial(jax.jit, static_argnames=("k_cut", "interpret"))
def column_trimmed_mean(
    x: jax.Array, k_cut: int, interpret: bool = False
) -> jax.Array:
    """Mean of each column with the ``k_cut`` smallest and largest values
    removed (exact duplicate handling) — ``sort(x)[k:n-k].mean(0)``
    without the sort.  ``x`` (n, d) -> (d,)."""
    if k_cut == 0:
        return x.astype(jnp.float32).mean(axis=0)
    if x.shape[0] <= 2 * k_cut:
        raise ValueError(f"need > {2 * k_cut} rows, got {x.shape[0]}")
    x, n = _pad_rows(x.astype(jnp.float32))
    return _run_columnwise(
        functools.partial(_trimmed_mean_kernel, n_true=n, k_cut=k_cut),
        x, interpret,
    )

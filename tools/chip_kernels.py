#!/usr/bin/env python
"""Compile every Pallas kernel on the chip at its gate's edge shapes and
compare it with a float64 reference there.

The gates (``ops/pallas_select.kernel_applicable``,
``ops/pallas_round.should_use``, ``ops/pallas_rowstats.kernel_applicable``,
``ops/pallas_store.store_applicable``)
promise that a kernel applies; only libtpu's Mosaic compile on a real
chip can say whether that promise holds (scoped-VMEM budget, vector
layouts).  ``CASES`` is the table of (kernel, edge shape) pairs the gates
admit.  Two consumers:

- this script, on a TPU: runs each case compiled (never interpreted),
  compares against the numpy float64 reference, prints one JSON line per
  case and writes ``chiprun_out/chip_kernels.json``; any failure makes
  the exit code non-zero.  Run it through the chip tool::

      chiprun -- python tools/chip_kernels.py [name-substring ...]

  ``--sweep [rows [d [dtype [width ...]]]]`` instead TIMES the compact finish
  alone at a short matrix over stripe widths (:func:`sweep`): the
  readings ``pallas_select._STRIPE_BUDGET`` was chosen from.  ``--sweep
  planes [rows ...]`` does so over a matrix whose rows are planes
  (``s x 128`` columns a grid step: ``pallas_select._PLANE_BUDGET``'s
  readings), and times the one-row store into it.  ``--grouped [tile ...]``
  checks and times the grouped product over routed pairs
  (:func:`grouped_product`: ``ops/grouped.py`` at the code-model cell's
  shapes against the dense form, ``lax.ragged_dot`` and the dense masked
  layer beside it: what decided which implementation ships, PR 33).
  ``--attention [block ...]`` times the attention alone at the two
  language-model cells' shapes, the XLA query blocks beside the fused
  kernel at each tiling (:func:`attention_forms`: what
  ``ops/attention.py::BLOCKS`` was chosen from, PR 34).

- ``tests/test_chip_contract.py``, on the CPU: lowers each case for
  ``platforms=["tpu"]`` via ``jax.export`` — catches Pallas API drift in
  seconds without a chip.

A kernel that cannot compile at an edge must not sit behind a gate that
says it applies: fix the kernel or tighten the gate, then re-run this.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from blades_tpu.ops import (  # noqa: E402
    pallas_round,
    pallas_rowstats,
    pallas_select,
    pallas_store,
)

STRIPE = pallas_select._BLOCK_D
D = 4 * STRIPE  # four grid steps: accumulators cross stripe boundaries
ALIE_Z = 0.67   # ALIE's z_max at n=1000, f=250


@dataclasses.dataclass(frozen=True)
class Case:
    name: str
    run: Callable[..., Dict[str, Any]]        # device arrays -> named outputs
    inputs: Callable[[], Tuple[np.ndarray, ...]]  # seeded host inputs
    reference: Callable[..., Dict[str, np.ndarray]]  # float64 numpy twin
    rtol: float                               # relative to max |reference|
    mosaic: bool = True                       # holds a Pallas kernel


def _rng(name: str) -> np.random.Generator:
    return np.random.default_rng(sum(name.encode()))


def _store(x: np.ndarray, dtype) -> np.ndarray:
    """Round to the storage dtype (host side, so the reference sees the
    exact stored values)."""
    return np.array(jnp.asarray(x, jnp.float32).astype(dtype))


def _f64(x: np.ndarray) -> np.ndarray:
    return np.asarray(x).astype(np.float32).astype(np.float64)


def _round_bf16(x: np.ndarray) -> np.ndarray:
    return _f64(_store(x, jnp.bfloat16))


# -- references -------------------------------------------------------------


def _ref_sanitize(x: np.ndarray, real: np.ndarray, stripe: int = STRIPE):
    """Stripe-local: a row with a non-finite value is zeroed within that
    stripe only, and reported."""
    x = x.copy()
    bad = np.zeros(x.shape[0], bool)
    for s in range(0, x.shape[1], stripe):
        row_bad = real & ~np.isfinite(x[:, s:s + stripe]).all(axis=1)
        x[row_bad, s:s + stripe] = 0.0
        bad |= row_bad
    return x, bad


def _ref_forged(benign: np.ndarray, bf16: bool) -> np.ndarray:
    forged = benign.mean(axis=0) + ALIE_Z * benign.std(axis=0, ddof=1)
    return _round_bf16(forged) if bf16 else forged


def _ref_agg(full: np.ndarray, agg: tuple) -> np.ndarray:
    n = full.shape[0]
    if agg[0] == "mean":
        return full.mean(axis=0)
    s = np.sort(full, axis=0)
    if agg[0] == "median":
        return (s[(n - 1) // 2] + s[n // 2]) / 2
    k = agg[1]
    return s[k:n - k].mean(axis=0)


# -- case builders ----------------------------------------------------------


def _matrix(name: str, n: int, dtype, nan_at=None, d: int = D) -> np.ndarray:
    x = _rng(name).normal(size=(n, d)).astype(np.float32)
    if nan_at is not None:
        x[nan_at] = np.nan
    return _store(x, dtype)


def _stripe_and_d(rows: int) -> Tuple[int, int]:
    """A fused finish's stripe at this height, and the cases' width there:
    ``D`` where the stripe is the 512 of the tall matrices, else two
    stripes and a ragged third, so that the accumulators still cross
    stripe boundaries and the last stripe is padded inside the call."""
    stripe = pallas_select.stripe_cols(rows)
    return stripe, D if stripe == STRIPE else 2 * stripe + 74


def _compact_case(nb: int, mult: int, dtype, agg: tuple, mxu: str = "") -> Case:
    """``fused_finish_compact`` as the streamed round calls it: benign
    rows only, pre-padded to a sublane multiple with +inf rows, the
    forged row a virtual row of multiplicity ``mult``."""
    bf16 = dtype == jnp.bfloat16
    rows = -(-nb // 8) * 8
    name = (f"compact_{agg[0]}_{jnp.dtype(dtype).name}_nb{nb}_mult{mult}"
            + (f"_mxu-{mxu}" if mxu else ""))
    radix_mxu, stats_mxu = pallas_round.parse_mxu_mode(mxu)
    stripe, d = _stripe_and_d(rows)

    def inputs():
        x = _matrix(name, rows, dtype, nan_at=(3, stripe + 7), d=d)
        x[nb:] = np.inf
        return (x,)

    def run(x):
        agg_vec, sq, bad, forged = pallas_round.fused_finish_compact(
            x, None, forged_mult=mult, forge=("alie", ALIE_Z), agg=agg,
            sanitize=True, num_real=nb, radix_mxu=radix_mxu,
            stats_mxu=stats_mxu)
        return {"agg": agg_vec, "sq": sq, "bad": bad, "forged": forged}

    def reference(x):
        benign, bad = _ref_sanitize(_f64(x)[:nb], np.ones(nb, bool), stripe)
        forged = _ref_forged(benign, bf16)
        full = np.concatenate([np.tile(forged, (mult, 1)), benign])
        return {"agg": _ref_agg(full, agg), "sq": (benign ** 2).sum(axis=1),
                "bad": bad, "forged": forged}

    # bf16 storage: a last-ulp difference in the f32 statistics can move
    # the forged row by one bf16 ulp (2**-8 relative), and ALIE makes the
    # forged value the median in many columns.  The stats-MXU variant
    # multiplies at bf16-pass precision whatever the storage.
    return Case(name, run, inputs, reference,
                2.0 ** -6 if bf16 or stats_mxu else 1e-4)


def _planes(x: np.ndarray) -> np.ndarray:
    """``(rows, d)`` as ``(rows, d // 128, 128)``."""
    return x.reshape(x.shape[0], -1, 128)


def _plane_case(nb: int, mult: int, dtype, agg: tuple) -> Case:
    """``fused_finish_compact`` over a matrix whose rows are planes, as
    the streamed round calls it where a block lies under a storage tile
    (parallel/streamed.py::compact_matrix): ``nb`` rows and no padding
    row, two grid steps and a third padded inside the call.  Against
    float64, and against the two-dimensional kernel on the same values
    (``same_bits``: the aggregate's and the forged row's, which on bf16
    storage must be equal to the bit; without the poisoned value, whose
    blanking follows each layout's own block)."""
    bf16 = dtype == jnp.bfloat16
    name = f"plane_{agg[0]}_{jnp.dtype(dtype).name}_nb{nb}_mult{mult}"
    block = pallas_select.plane_cols(nb)
    d = 2 * block + 128 * 5

    def inputs():
        return (_planes(_matrix(name, nb, dtype, nan_at=(3, block + 7),
                                d=d)),)

    def finish(x):
        return pallas_round.fused_finish_compact(
            x, None, forged_mult=mult, forge=("alie", ALIE_Z), agg=agg,
            sanitize=True)

    def run(x):
        agg_vec, sq, bad, forged = finish(x)
        sound = jnp.where(jnp.isnan(x.astype(jnp.float32)), 0, x)
        plane, flat = finish(sound), finish(sound.reshape(nb, d))
        same = jnp.stack([
            (jax.lax.bitcast_convert_type(plane[i], jnp.uint32)
             == jax.lax.bitcast_convert_type(flat[i], jnp.uint32)).all()
            for i in (0, 3)])
        return {"agg": agg_vec, "sq": sq, "bad": bad, "forged": forged,
                "same_bits": same}

    def reference(x):
        benign, bad = _ref_sanitize(_f64(x).reshape(nb, d),
                                    np.ones(nb, bool), block)
        forged = _ref_forged(benign, bf16)
        full = np.concatenate([np.tile(forged, (mult, 1)), benign])
        return {"agg": _ref_agg(full, agg), "sq": (benign ** 2).sum(axis=1),
                "bad": bad, "forged": forged,
                "same_bits": np.ones(2, bool) if bf16 else None}

    return Case(name, run, inputs, reference, 2.0 ** -6 if bf16 else 1e-4)


def _plane_store_case(rows: int, lanes: int, dtype,
                      leaves: bool = False) -> Case:
    """The store of a block under a storage tile: ``lanes`` rows, padded
    to the matrix's width, into row planes at a runtime row (XLA's own
    copy: ``pallas_store.row_planes`` + ``lax.dynamic_update_slice``, as
    in ``_train_block``); every other row must stay.  ``leaves``: the one
    lane comes as a pytree of its leaves, as the one-lane block hands it
    over, and is laid out in one dimension."""
    name = (f"plane_store_{jnp.dtype(dtype).name}_r{rows}_b{lanes}"
            + "_leaves" * leaves)
    tail = (48, 128)
    d = tail[0] * tail[1] - 74

    def inputs():
        rng = _rng(name)
        return (_store(rng.normal(size=(rows,) + tail), dtype),
                _store(rng.normal(size=(lanes, d)), dtype),
                np.uint32(rows - lanes - 1))

    def run(mat, upd, row):
        if leaves:   # three leaves, none a whole number of lanes
            upd = {"a": upd[:, :1000].reshape(1, 8, 125),
                   "b": upd[:, 1000:1003], "c": upd[:, 1003:]}
        return {"rows": jax.lax.dynamic_update_slice(
            mat, pallas_store.row_planes(upd, tail),
            (row, jnp.uint32(0), jnp.uint32(0)))}

    def reference(mat, upd, row):
        want = _f64(mat).reshape(rows, -1)
        want[row:row + lanes] = 0.0
        want[row:row + lanes, :d] = _f64(upd)
        return {"rows": want.reshape((rows,) + tail)}

    return Case(name, run, inputs, reference, 0.0, mosaic=False)  # a copy


def _movers_case(held_a_token: int, tokens: int = 512, h: int = 2304
                 ) -> Case:
    """The routed layer's row movers (``ops/grouped.py``) at the code
    model's width, bf16, top-8 over 8 held experts, every token selecting
    ``held_a_token`` of them (1: one row a token, as the cell's routers
    send; 8: the whole buffer, the worst case): the dispatch's rows, the
    combine's weighted sums and the combine's cotangent rows, each
    against float64 over the rows in use."""
    from blades_tpu.ops import grouped

    name = f"row_movers_t{tokens}_held{held_a_token}"
    top_k = held = 8
    pairs = tokens * top_k
    cfg = grouped._Tiles(grouped.ROW_TILE, grouped._CHUNK,
                         grouped._TOKEN_TILE, False)

    def inputs():
        rng = _rng(name)
        expert = np.full((tokens, top_k), -1, np.int32)
        for k in range(held_a_token):
            expert[:, k] = (np.arange(tokens) + k) % held
        return (expert, _store(rng.normal(size=(tokens, h)), jnp.bfloat16),
                _store(rng.normal(size=(pairs, h)), jnp.bfloat16),
                rng.uniform(size=(tokens, top_k)).astype(np.float32))

    def run(expert, x, ys, w):
        here = expert >= 0
        order, pos, sizes = grouped.sort_pairs(expert.reshape(pairs),
                                               here.reshape(pairs), held)
        mine = here[..., None] & (expert[..., None] == jnp.arange(held))
        route = grouped._route(mine, order, pos, sizes, cfg)
        wg = jnp.where(mine, w.astype(jnp.bfloat16)[..., None].astype(
            jnp.float32), 0.0).sum(1).T
        used = (jnp.arange(pairs) < sizes.sum())[:, None]
        w_row = jnp.where(route.tok >= 0, w.reshape(-1)[order], 0.0)
        back, dots = grouped._spread(x, route, cfg, w_row.astype(
            jnp.bfloat16).astype(jnp.float32), ys)
        return {"dispatch": jnp.where(used, grouped._spread(x, route, cfg),
                                      0),
                "combine": grouped._collect([ys], route, cfg, wg),
                "combine_bwd": jnp.where(used, back, 0),
                "dots": jnp.where(used[:, 0], dots.reshape(-1), 0)}

    def reference(expert, x, ys, w):
        here = expert >= 0
        key = np.where(here, expert, held).reshape(-1)
        order = np.argsort(key, kind="stable")
        n = int(here.sum())
        tok = order[:n] // top_k
        wb = _round_bf16(w).reshape(-1)
        disp = np.zeros((pairs, h))
        disp[:n] = _f64(x)[tok]
        back = np.zeros((pairs, h))
        back[:n] = _round_bf16(wb[order[:n], None] * _f64(x)[tok])
        dots = np.zeros(pairs)
        dots[:n] = (_f64(x)[tok] * _f64(ys)[:n]).sum(1)
        pos = np.argsort(order).reshape(tokens, top_k)
        prod = _round_bf16(wb.reshape(tokens, top_k)[..., None]
                           * _f64(ys)[pos])
        comb = np.where(here[..., None], prod, 0.0).sum(1)
        return {"dispatch": disp, "combine": comb, "combine_bwd": back,
                "dots": dots}

    # Every row and product is exact; the one rounding of a sum of several
    # rows to bf16, and the dots' float32 sums, are not.
    return Case(name, run, inputs, reference, 2.0 ** -7)


def _full_case(n: int, f: int, dtype, agg: tuple) -> Case:
    """``fused_finish`` over the full matrix with a malicious prefix."""
    bf16 = dtype == jnp.bfloat16
    name = f"fused_{agg[0]}_{jnp.dtype(dtype).name}_n{n}_f{f}"
    stripe, d = _stripe_and_d(n)

    def inputs():
        return (_matrix(name, n, dtype, nan_at=(f + 3, stripe + 7), d=d),
                np.arange(n) < f)

    def run(x, mal):
        agg_vec, sq, bad = pallas_round.fused_finish(
            x, mal, forge=("alie", ALIE_Z), agg=agg, sanitize=True)
        return {"agg": agg_vec, "sq": sq, "bad": bad}

    def reference(x, mal):
        xs, bad = _ref_sanitize(_f64(x), np.ones(n, bool), stripe)
        forged = _ref_forged(xs[~mal], bf16)
        full = np.where(mal[:, None], forged, xs)
        return {"agg": _ref_agg(full, agg), "sq": (full ** 2).sum(axis=1),
                "bad": bad}

    return Case(name, run, inputs, reference, 2.0 ** -6 if bf16 else 1e-4)


def _select_case(n: int, k_cut: int = 0) -> Case:
    """``column_median`` (``k_cut == 0``) / ``column_trimmed_mean``."""
    name = f"column_trimmed{k_cut}_n{n}" if k_cut else f"column_median_n{n}"
    agg = ("trimmed", k_cut) if k_cut else ("median",)

    def run(x):
        if k_cut:
            return {"agg": pallas_select.column_trimmed_mean(x, k_cut)}
        return {"agg": pallas_select.column_median(x)}

    return Case(name, run, lambda: (_matrix(name, n, jnp.float32),),
                lambda x: {"agg": _ref_agg(_f64(x), agg)}, 1e-5)


def _rowstats_case(n: int, dtype, gram: bool) -> Case:
    """``row_stats_bundle`` with every accumulator the planner can ask
    for in one bundle."""
    integer = jnp.issubdtype(dtype, jnp.integer)
    name = (f"rowstats_{jnp.dtype(dtype).name}_n{n}"
            + ("_gram" if gram else ""))

    def inputs():
        rng = _rng(name)
        if integer:
            x = rng.integers(-127, 128, size=(n, D)).astype(np.int8)
        else:
            x = _matrix(name, n, dtype)
        return (x, rng.normal(size=(2, D)).astype(np.float32),
                rng.normal(size=(2, n)).astype(np.float32),
                rng.normal(size=(1, n)).astype(np.float32))

    def run(x, dots, weights, gram_dot):
        return pallas_rowstats.row_stats_bundle(
            x, sq=True, gram=gram, signs=True, dots=dots, weights=weights,
            gram_dot=gram_dot)

    def reference(x, dots, weights, gram_dot):
        x = np.asarray(x).astype(np.float64) if integer else _f64(x)
        pos, neg = (x > 0).sum(axis=1), (x < 0).sum(axis=1)
        out = {
            "sq": (x ** 2).sum(axis=1),
            "signs": np.stack([pos, neg, D - pos - neg], axis=1),
            "dots": x @ dots.astype(np.float64).T,
            "wsum": weights.astype(np.float64) @ x,
            "gram_dot": x @ (gram_dot.astype(np.float64) @ x).T,
        }
        if gram:
            out["gram"] = x @ x.T
        return out

    # The f32 MXU contractions run at the backend's default matmul
    # precision; the int8 self-contractions are exact.
    return Case(name, run, inputs, reference, 2e-2)


def _store_case(lanes: int, dtype, surplus: int = 0) -> Case:
    """``store_row_block``: the middle one of three row blocks, an update
    two column blocks and 74 columns wide (the last block masked) into a
    matrix padded to the next stripe, whose +inf rows must stay.  With
    ``surplus`` it is a padded last block: rows from ``surplus`` on, then
    ``surplus`` rows of +inf."""
    name = f"store_{jnp.dtype(dtype).name}_b{lanes}_s{surplus}"
    d = 2 * pallas_store._block_cols(lanes, dtype) + 74
    width = -(-d // STRIPE) * STRIPE

    def inputs():
        mat = np.zeros((3 * lanes, width), np.float32)
        mat[-1] = np.inf
        upd = _rng(name).normal(size=(lanes, d)).astype(np.float32)
        return _store(mat, dtype), _store(upd, dtype)

    def run(mat, upd):
        out = pallas_store.store_row_block(
            mat, upd, jnp.uint32(1), jnp.uint32(surplus), surplus=surplus)
        # +inf rows as a flag, the rest finite for the comparison.
        inf = jnp.isposinf(out.astype(jnp.float32))
        return {"inf": inf, "rows": jnp.where(inf, 0, out)}

    def reference(mat, upd):
        want = _f64(mat)
        want[lanes:2 * lanes] = 0.0
        want[lanes:2 * lanes - surplus, :d] = _f64(upd)[surplus:]
        want[2 * lanes - surplus:2 * lanes] = np.inf
        inf = np.isposinf(want)
        return {"inf": inf, "rows": np.where(inf, 0.0, want)}

    return Case(name, run, inputs, reference, 0.0)  # a copy: exact


def _cases() -> Tuple[Case, ...]:
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    gram_n = pallas_rowstats._GRAM_MAX_N
    return (
        # The smoke's own finish: ResNet-10 x 1000 clients, 250 ALIE.
        _compact_case(750, 250, bf16, ("median",)),
        _compact_case(750, 250, bf16, ("trimmed", 250)),
        _compact_case(750, 250, bf16, ("mean",)),
        # ResNet-18 x 768 clients, 192 ALIE.
        _compact_case(576, 192, bf16, ("median",)),
        # The height bound of the shared gate, both storage widths.
        _compact_case(2048, 680, bf16, ("median",)),
        _compact_case(2048, 680, f32, ("median",)),
        _compact_case(2048, 680, f32, ("trimmed", 680)),
        # MXU finish variants (M=1 dots).
        _compact_case(750, 250, bf16, ("median",), mxu="counts"),
        _compact_case(750, 250, bf16, ("median",), mxu="all"),
        _compact_case(2048, 680, f32, ("median",), mxu="all"),
        # Short matrices, whose stripe is wide (pallas_select.stripe_cols):
        # the language-model cell's 8 benign rows + 2 forged, and heights
        # whose rows are padded inside the call.
        _compact_case(8, 2, bf16, ("median",)),
        _compact_case(8, 2, f32, ("trimmed", 2)),
        _compact_case(13, 3, bf16, ("trimmed", 3)),
        _compact_case(24, 8, f32, ("median",)),
        # The same over row planes (blocks under a storage tile): the
        # language-model cell's call, and heights that are no sublane
        # multiple, which need no padding row there.
        _plane_case(8, 2, bf16, ("median",)),
        _plane_case(8, 2, f32, ("trimmed", 2)),
        _plane_case(10, 3, bf16, ("trimmed", 3)),
        _plane_case(13, 3, bf16, ("mean",)),
        _plane_case(24, 8, bf16, ("median",)),
        _plane_case(24, 8, f32, ("median",)),
        # Full-matrix finish (no elision).
        _full_case(1000, 250, bf16, ("median",)),
        _full_case(2048, 512, bf16, ("median",)),
        _full_case(2048, 512, f32, ("median",)),
        _full_case(2048, 512, f32, ("trimmed", 512)),
        _full_case(16, 4, bf16, ("median",)),
        # Rank-select kernels behind Median/Trimmedmean (f32 only).
        _select_case(1000),
        _select_case(2048),
        _select_case(2047),          # odd n: +inf row padding inside
        _select_case(2048, k_cut=512),
        # Row statistics: Gram height bound, then the shared bound.
        _rowstats_case(gram_n, f32, gram=True),
        _rowstats_case(gram_n, bf16, gram=True),
        _rowstats_case(gram_n, i8, gram=True),
        _rowstats_case(2048, f32, gram=False),
        _rowstats_case(2048, i8, gram=False),
        # The streamed block's tile store: the cells' 16 bf16 lanes plain
        # and as r10_median's padded last block, an f32 tile with an odd
        # surplus, and three tiles a block (client_block 50 -> 48).
        _store_case(16, bf16),
        _store_case(16, bf16, surplus=2),
        _store_case(8, f32, surplus=5),
        _store_case(48, bf16, surplus=20),
        # Blocks under a tile, into row planes: one row of 8 (the
        # language-model cell's), three of 10, five of 24.
        _plane_store_case(8, 1, bf16),
        _plane_store_case(8, 1, bf16, leaves=True),
        _plane_store_case(10, 3, f32),
        _plane_store_case(24, 5, bf16),
        # The routed layer's row movers at the code model's width: a row
        # a token, and every token selecting every held expert.
        _movers_case(1),
        _movers_case(8),
    )


CASES = _cases()


# -- chip runner ------------------------------------------------------------


def check(case: Case) -> Dict[str, Any]:
    """Compile + run one case on the default backend and compare with
    its reference.  Never raises: a failure is recorded."""
    rec: Dict[str, Any] = {"case": case.name, "ok": False}
    try:
        host = case.inputs()
        args = tuple(jnp.asarray(a) for a in host)
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(case.run)(*args))
        rec["first_call_s"] = round(time.perf_counter() - t0, 2)
        ref = case.reference(*host)
        errs, ok = {}, True
        for key, want in ref.items():
            if want is None:   # not held in this case
                continue
            got = np.asarray(out[key])
            if got.shape != want.shape:
                raise AssertionError(
                    f"{key}: shape {got.shape} != {want.shape}")
            if want.dtype == bool:  # flags must agree exactly
                err, tol = float((got != want).sum()), 0.0
            else:
                if not np.isfinite(got).all():
                    raise AssertionError(f"{key}: non-finite output")
                scale = max(float(np.abs(want).max()), 1e-30)
                err = float(np.abs(got.astype(np.float64) - want).max()
                            / scale)
                tol = case.rtol
            errs[key] = float(f"{err:.3g}")
            ok = ok and err <= tol
        rec["rel_err"] = errs
        rec["ok"] = ok
        if not ok:
            rec["error"] = f"mismatch: rtol {case.rtol}"
    except Exception as e:  # a refused compile is this script's finding
        rec["error"] = f"{type(e).__name__}: {e}"[-1500:]
    return rec


SWEEP_ROWS, SWEEP_D = 8, 413_959_168  # joyai_n10_median's stored matrix
SWEEP_WIDTHS = (512, 1024, 2048, 3072, 4096, 8192, 32768)
SWEEP_PLANE_WIDTHS = (2048, 4096, 8192, 16384, 32768, 65536)


def _bits_sums(v):
    """Two wrapping uint32 sums over a float32 vector's bits (plain, and
    weighted by an odd multiple of the index): equal vectors give equal
    pairs, and the widths' 1.7 GB outputs need not be held side by side."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    index = jax.lax.iota(jnp.uint32, v.shape[0])
    return jnp.stack([jnp.sum(bits), jnp.sum(bits * (2 * index + 1))])


def sweep(argv) -> int:
    """Time the compact finish ALONE (2 ALIE rows, Median, sanitize on,
    bfloat16 unless told: the language-model cell's call) at a ``rows`` x
    ``d`` matrix for each stripe width, the rule's own (``stripe_cols``)
    included.  One JSON line a width: the first call (the compile), the
    steady calls' milliseconds by the host's clock around
    ``block_until_ready`` (the kernel and the ~20 ms of slices and sums
    around it), the grid, whether the aggregate and the forged row have
    the bits the 512-column kernel gave (by :func:`_bits_sums`), and the
    row norms' largest relative gap to that kernel's (their float32 sums
    run across stripes).  A refused compile is a line, not a failure.

    With ``planes`` as the first argument the matrix keeps a row a plane
    (``(rows, d // 128, 128)``, the same values): the widths are the
    columns ``s x 128`` of a grid step (``plane_cols`` the rule's), the
    bits are compared with the TWO-DIMENSIONAL kernel's at its own rule's
    width, which runs first, and two last lines time the store of one row
    at a runtime row index into either layout (``lax.dynamic_update_slice``
    into the donated matrix, as ``_train_block`` stores it)."""
    planes = bool(argv) and argv[0] == "planes"
    argv = argv[1:] if planes else argv
    rows = int(argv[0]) if argv else SWEEP_ROWS
    d = int(argv[1]) if len(argv) > 1 else SWEEP_D
    dtype = jnp.dtype(argv[2] if len(argv) > 2 else "bfloat16")
    widths = tuple(int(w) for w in argv[3:]) or (
        SWEEP_PLANE_WIDTHS if planes else SWEEP_WIDTHS)
    ruled = (pallas_select.plane_cols if planes
             else pallas_select.stripe_cols)(rows)

    @functools.partial(jax.jit, static_argnames=("dpad", "planes"))
    def matrix(dpad, planes):
        """The matrix as the round allocates it, padded to the stripe
        with zero columns: values in [-1, 1) hashed from (row, column),
        one elementwise program with no temporary beside the 6.6 GB."""
        shape = (rows, dpad // 128, 128) if planes else (rows, dpad)
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.uint32, shape)
        row, col = iota(0), iota(1)
        if planes:
            col = col * jnp.uint32(128) + iota(2)
        h = col * jnp.uint32(2654435761) + row * jnp.uint32(40503)
        h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
        h = h ^ (h >> 13)
        v = (h >> 8).astype(jnp.float32) * 2.0 ** -23 - 1.0
        return jnp.where(col < d, v, 0).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("cols",))
    def finish(x, cols):
        agg, sq, _, forged = pallas_round._fused_finish_compact_jit(
            x, None, forged_mult=2, forge=("alie", ALIE_Z), agg=("median",),
            sanitize=True, num_real=rows, cols=cols)
        return _bits_sums(agg[:d]), _bits_sums(forged[:d]), sq

    @functools.partial(jax.jit, donate_argnums=(0,))
    def store(x, upd, row):
        if x.ndim == 3:
            upd = pallas_store.row_planes(upd.astype(x.dtype), x.shape[1:])
        zero = jnp.uint32(0)
        return jax.lax.dynamic_update_slice(
            x, upd, (row,) + (zero,) * (x.ndim - 1))

    def timed(call, times=4):
        ms = []
        for _ in range(times):
            t0 = time.perf_counter()
            jax.block_until_ready(call())
            ms.append(round((time.perf_counter() - t0) * 1e3, 2))
        return ms

    records, want = [], None
    runs = [(False, cols) for cols in sorted({STRIPE, ruled, *widths})]
    if planes:
        runs = ([(False, pallas_select.stripe_cols(rows))]
                + [(True, cols) for cols in sorted({ruled, *widths})])
    for plane, cols in runs:
        dpad = -(-d // cols) * cols
        rec = {"rows": rows, "d": d, "dtype": dtype.name, "cols": cols,
               "planes": plane, "grid": dpad // cols,
               "ruled": cols == ruled and plane == planes}
        try:
            x = jax.block_until_ready(matrix(dpad, plane))
            t0 = time.perf_counter()
            got = jax.block_until_ready(finish(x, cols))
            rec["first_call_s"] = round(time.perf_counter() - t0, 2)
            rec["ms"] = timed(lambda: finish(x, cols))
            got = [np.asarray(g) for g in got]
            want = want or got   # the narrowest width comes first
            rec["agg_bits_equal"] = bool((got[0] == want[0]).all())
            rec["forged_bits_equal"] = bool((got[1] == want[1]).all())
            rec["sq_rel_err"] = float(
                np.abs(got[2] / want[2] - 1.0).max())
            del x, got
        except Exception as e:  # a refused width is this sweep's finding
            rec["error"] = f"{type(e).__name__}: {e}"[-600:]
        records.append(rec)
        print(json.dumps(rec), flush=True)
    # The store of ONE row, as ``_train_block`` hands it over: in the
    # storage type, where a ``(1, d)`` row of a 2-byte type is laid out
    # with a second, empty row in every word (T(2,128)(2,1)), or in
    # float32, converted on the way.
    stores = [(False, dtype), (True, dtype), (True, jnp.dtype("float32"))]
    for plane, row_dtype in stores if planes else ():
        cols = ruled if plane else pallas_select.stripe_cols(rows)
        rec = {"rows": rows, "d": d, "dtype": dtype.name, "planes": plane,
               "store_of_rows": 1, "row_dtype": row_dtype.name}
        try:
            box = [jax.block_until_ready(
                matrix(-(-d // cols) * cols, plane))]
            upd = jax.block_until_ready(jnp.full((1, d), 0.5, row_dtype))
            index = iter(range(1, 1000))

            def one_store():
                box[0] = store(box[0], upd,
                               np.uint32(next(index) % rows))
                return box[0]

            t0 = time.perf_counter()
            jax.block_until_ready(one_store())
            rec["first_call_s"] = round(time.perf_counter() - t0, 2)
            rec["ms"] = timed(one_store, times=8)
            del box, upd
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"[-600:]
        records.append(rec)
        print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join(
            "chiprun_out", "chip_kernels_sweep_"
            f"{'planes_' * planes}{rows}x{d}_{dtype.name}.json"), "w") as f:
        json.dump(records, f, indent=1)
    return 0


# (tm, tk, tn) the package's own ``gmm`` is timed at: what
# ``ops/grouped.py::_TILE_KN`` was chosen from.  The projections' widths,
# 2304 and 896, share no lane-tile divisor but 128; whole widths (256, 2304,
# 896) are refused (18.88 MB of the 16 MiB scoped VMEM in ``tgmm``).
PUBLIC_TILINGS = ((256, 128, 128), (256, 896, 896), (256, 1024, 1024),
                  (512, 1024, 1024))

def _median_ms(fn, *args, times=5):
    """ms of ``fn(*args)`` to completion: the median of ``times`` calls
    after one that compiles."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(times):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return 1e3 * float(np.median(out))


GROUPED_LOADS = {
    # pairs a held expert receives: 8 lanes x S 8192 x top-8 x 8/64 held
    "even": (1024,) * 8,
    "skewed": (3900, 2100, 900, 600, 300, 200, 100, 92),
    "one_expert_takes_every_pair": (0, 0, 0, 8192, 0, 0, 0, 0),
    "an_expert_without_a_pair": (2048, 0, 2048, 1024, 1024, 1024, 512, 512),
    "every_token_selects_every_held_expert": (8192,) * 8,
}


def row_movers(expert, here, x, weight, tile=256):
    """The four row moves of one routed layer (``ops/grouped.py``) under
    one routing: XLA's gathers over the whole buffer (the ``jnp`` form,
    what the chip ran before the movers) beside the Pallas movers, each
    one's time (the median of 5 calls) and the largest difference between
    the two on what is read of it (the buffer's rows in use, every token,
    every weight), relative to the largest value.  ``dispatch``: the rows
    into the buffer; ``combine``: the weighted sum back; ``*_bwd``: their
    cotangents (the dispatch's from the two input projections' cotangents,
    which the jnp form adds over the whole buffer first)."""
    from blades_tpu.ops import grouped

    bf16, f32 = jnp.bfloat16, jnp.float32
    tokens, top_k = expert.shape
    pairs, h, held = tokens * top_k, x.shape[1], 8
    order, pos, sizes = grouped.sort_pairs(expert.reshape(pairs),
                                           here.reshape(pairs), held)
    # In Pallas's interpreter where there is no TPU (the tests' small size).
    cfg = grouped._Tiles(tile, math.gcd(tokens, grouped._CHUNK),
                         math.gcd(tokens, grouped._TOKEN_TILE),
                         jax.default_backend() != "tpu")
    mine = here[..., None] & (expert[..., None] == jnp.arange(held))
    route = grouped._route(mine, order, pos, sizes, cfg)
    pos2 = pos.reshape(tokens, top_k)
    wg = jnp.where(mine, weight.astype(bf16)[..., None].astype(f32),
                   0.0).sum(1).T
    key = jax.random.split(jax.random.PRNGKey(39), 4)
    ys, a, b = (jax.random.normal(k, (pairs, h), bf16) for k in key[:3])
    g = jax.random.normal(key[3], (tokens, h), bf16)

    def combine_xla(ys, w, pos2, here):
        got = jnp.where(here[..., None], ys[pos2], 0)
        return (w.astype(bf16)[..., None] * got).sum(1, dtype=f32).astype(
            bf16)

    def g_w(g_wg, expert, here):     # (G, T) -> (T, K), the jnp form's shape
        return jnp.where(here, g_wg[jnp.clip(expert, 0, held - 1),
                                    jnp.arange(tokens)[:, None]], 0.0)

    # Every array is an argument: one captured by a closure would be a
    # constant of the compiled program (GBs here), slow to compile.
    v = dict(x=x, a=a, b=b, ys=ys, g=g, weight=weight, wg=wg, order=order,
             pos2=pos2, here=here, expert=expert)
    forms = {
        "dispatch": (
            lambda r, v: grouped.gather_rows(
                v["x"], v["order"] // top_k, v["pos2"], v["here"]),
            lambda r, v: grouped._spread(v["x"], r, cfg)),
        "dispatch_bwd": (
            lambda r, v: grouped._gather_bwd((v["pos2"], v["here"]),
                                             v["a"] + v["b"])[0],
            lambda r, v: grouped._collect([v["a"], v["b"]], r, cfg)),
        "combine": (
            lambda r, v: combine_xla(v["ys"], v["weight"], v["pos2"],
                                     v["here"]),
            lambda r, v: grouped._collect([v["ys"]], r, cfg, v["wg"])),
        "combine_bwd": (
            lambda r, v: jax.vjp(
                lambda ys, w: combine_xla(ys, w, v["pos2"], v["here"]),
                v["ys"], v["weight"])[1](v["g"]),
            lambda r, v: (lambda o: (o[0], g_w(o[1], v["expert"],
                                               v["here"])))(
                grouped._combine_bwd(cfg, (v["ys"], v["wg"], r), v["g"]))),
    }
    n = int(sizes.sum())
    used = np.arange(pairs) < n
    out = {"pairs": n, "rows_moved": int(
        grouped.rows_computed(sizes.sum(keepdims=True), tile))}
    for name, (xla, pallas) in forms.items():
        xla, pallas = jax.jit(xla), jax.jit(pallas)
        rec = {}
        try:
            want, got = (jax.tree.leaves(jax.device_get(f(route, v)))
                         for f in (xla, pallas))
            errs = []
            for w, o in zip(want, got):
                w, o = np.asarray(w, np.float64), np.asarray(o, np.float64)
                if w.shape[0] == pairs:
                    w, o = w[used], o[used]
                errs.append(float(np.abs(w - o).max()
                                  / max(np.abs(w).max(), 1e-30))
                            if w.size else 0.0)
            rec["rel_err"] = [float(f"{e:.3g}") for e in errs]
            rec["xla_ms"] = round(_median_ms(xla, route, v), 3)
            rec["pallas_ms"] = round(_median_ms(pallas, route, v), 3)
            rec["speedup"] = round(rec["xla_ms"] / rec["pallas_ms"], 2)
            # The jnp form rounds the weight's cotangent to bf16 (its
            # products and sum are bf16): up to 2**-7 of the largest.
            rec["ok"] = bool(max(errs) < 2e-2)
        except Exception as e:   # a refused kernel is a finding
            rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[-600:]}
        out[name] = rec
    return out


def grouped_product(argv, tokens=8192, top_k=8, h=2304, f=896,
                    impl="kernel") -> int:
    """The grouped product of ``ops/grouped.py`` at the code-model cell's
    shapes (a buffer of ``tokens x top_k`` pair rows, 8 held experts of
    ``h x f``, bf16), for each load of ``GROUPED_LOADS``:

    - one product ``(rows, h) x (8, h, f)``, forward and both cotangents,
      against the dense form (every group's float32 product on every row,
      kept where the row is the group's), over the rows in use;
    - its time (forward + backward, the median of 5 calls) at each row tile
      of ``argv`` (default 128 256 512) and its share of the MXU roofline
      for the pairs in use; ``lax.ragged_dot``'s beside it;
    - the row movers beside XLA's gathers (:func:`row_movers`);
    - the whole routed layer (``routed_ffn``: sort, move, three grouped
      products, combine; forward + backward) under a drawn routing with
      that load, with the movers (``routed_ms``) and with XLA's gathers
      (``routed_xla_gathers_ms``), beside the dense masked form (every
      held expert on every token under a 0/1 weight: how
      ``models/mla_moe.py`` computes its share).

    ``argv`` ``movers`` runs the last two alone."""
    from blades_tpu.ops import grouped

    movers_only = argv[:1] == ["movers"]
    tiles = () if movers_only else (tuple(int(a) for a in argv)
                                    or (128, 256, 512))
    rows, held, bf16 = tokens * top_k, 8, jnp.bfloat16
    key = jax.random.split(jax.random.PRNGKey(33), 8)
    lhs = jax.random.normal(key[0], (rows, h), bf16)
    rhs = 0.02 * jax.random.normal(key[1], (held, h, f), jnp.float32)
    ct = jax.random.normal(key[2], (rows, f), bf16)
    x = jax.random.normal(key[3], (tokens, h), bf16)
    gate, up = (0.02 * jax.random.normal(k, (held, h, f)).astype(bf16)
                for k in key[4:6])
    down = 0.02 * jax.random.normal(key[6], (held, f, h)).astype(bf16)

    timed = _median_ms

    def vjp_of(product):
        def run(lhs, rhs, gs, ct):
            out, vjp = jax.vjp(lambda a, b: product(a, b, gs), lhs, rhs)
            return (out,) + vjp(ct)
        return jax.jit(run)

    @jax.jit
    def dense_form(lhs, rhs, gs, ct):
        ends = jnp.cumsum(gs)
        row = jnp.arange(rows)[:, None]

        def one(lhs, rhs):
            out = 0.0
            for g in range(held):
                mine = (row >= ends[g] - gs[g]) & (row < ends[g])
                out = out + jnp.where(mine, jnp.dot(
                    lhs.astype(jnp.float32), rhs[g],
                    precision=jax.lax.Precision.HIGHEST), 0)
            return out

        out, vjp = jax.vjp(one, lhs, rhs)
        return (out,) + vjp(ct.astype(jnp.float32))

    def routing(sizes, seed):
        """``(expert, here)`` ``(tokens, top_k)``: a routing under which
        held expert ``e`` is selected by ``sizes[e]`` tokens."""
        rng = np.random.default_rng(seed)
        expert = np.full((tokens, top_k), -1, np.int32)
        for e, n in enumerate(sizes):
            expert[rng.choice(tokens, n, replace=False), e] = e
        return jnp.asarray(expert), jnp.asarray(expert >= 0)

    def layer(fn):
        def run(x, weight, gate, up, down):
            y, vjp = jax.vjp(fn, x, weight, gate, up, down)
            return (y,) + vjp(y)
        return jax.jit(run)

    def both_projections(gs):
        """ms of one product forward + backward at the up projection's
        shapes plus one at the down projection's: ``ops/grouped.py`` beside
        the package's own ``megablox.gmm`` (its custom VJP, ONE tiling for
        its three products) at each of ``PUBLIC_TILINGS``.  (Until the
        review round of PR 33 ``ops/grouped.py`` called the kernels under
        a VJP of its own with a tiling searched for each product: 4.02 ms
        at 8192 pairs even, 14.5 at 65 536.)"""
        from jax.experimental.pallas.ops.tpu import megablox

        shapes = ((lhs, rhs, ct), (ct, rhs.swapaxes(1, 2), lhs))
        products = {"ops_grouped": lambda a, b, g: grouped.grouped_matmul(
            a, b.astype(bf16), g, impl=impl)}
        for t in PUBLIC_TILINGS:
            products["megablox_gmm_%dx%dx%d" % t] = (
                lambda a, b, g, t=t: megablox.gmm(a, b.astype(bf16), g,
                                                  bf16, t))
        out = {}
        for name, product in products.items():
            run = vjp_of(product)
            try:
                out[name] = round(sum(timed(run, a, b, gs, c)
                                      for a, b, c in shapes), 3)
            except Exception as e:   # a refused tiling is a finding
                out[name] = f"{type(e).__name__}: {e}"[-200:]
        return out

    records = []
    for name, sizes in GROUPED_LOADS.items():
        gs = jnp.asarray(sizes, jnp.int32)
        pairs = int(sum(sizes))
        used = np.arange(rows) < pairs
        rec = {"load": name, "pairs": pairs, "sizes": list(sizes)}
        want = [] if movers_only else [np.asarray(a, np.float64) for a in
                                       dense_form(lhs, rhs, gs, ct)]
        floor_ms = 1e3 * 3 * 2 * pairs * h * f / 197e12
        for tile in tiles:
            run = vjp_of(lambda a, b, g, t=tile: grouped.grouped_matmul(
                a, b.astype(bf16), g, tile=t, impl=impl))
            try:
                got = run(lhs, rhs, gs, ct)
                errs = []
                for a, b in zip(got, want):
                    a = np.asarray(a, np.float64)
                    if a.shape[0] == rows:
                        a, b = a[used], b[used]
                    errs.append(float(np.abs(a - b).max()
                                      / max(np.abs(b).max(), 1e-30))
                                if b.size else 0.0)
                ms = timed(run, lhs, rhs, gs, ct)
                rec[f"tile{tile}"] = {
                    "rel_err": [float(f"{e:.3g}") for e in errs],
                    "ok": bool(max(errs) < 2e-2), "ms": round(ms, 3),
                    "roofline_pct": round(100 * floor_ms / ms, 1),
                    "rows_computed": int(grouped.rows_computed(gs, tile))}
            except Exception as e:   # a refused tiling is a finding
                rec[f"tile{tile}"] = {"ok": False, "error":
                                      f"{type(e).__name__}: {e}"[-600:]}
        if impl == "kernel" and not movers_only:
            rag = vjp_of(lambda a, b, g: jax.lax.ragged_dot(
                a, b.astype(bf16), g))
            try:
                rec["ragged_dot_ms"] = round(timed(rag, lhs, rhs, gs, ct), 3)
            except Exception as e:
                rec["ragged_dot_error"] = f"{type(e).__name__}: {e}"[-300:]
            rec["both_projections_ms"] = both_projections(gs)
        expert, here = routing(sizes, 7)
        weight = jax.random.uniform(key[7], (tokens, top_k), jnp.float32)
        if impl == "kernel":
            rec["movers"] = row_movers(expert, here, x, weight)
        routed = layer(lambda x, w, g, u, d: grouped.routed_ffn(
            x, expert, here, w, g, u, d, impl=impl)[0])
        gathered = layer(lambda x, w, g, u, d: grouped.routed_ffn(
            x, expert, here, w, g, u, d,
            impl="jnp" if impl == "jnp" else None)[0])
        # Traced (at its first call, here) with the movers' gate shut: the
        # grouped product's kernels and XLA's gathers, the layer as it ran
        # before the movers.
        gate_open = grouped.movers_applicable
        grouped.movers_applicable = lambda *a: False
        try:
            jax.block_until_ready(gathered(x, weight, gate, up, down))
        finally:
            grouped.movers_applicable = gate_open

        def masked(x, w, g, u, d):
            w_held = (w[..., None] * (expert[..., None] == jnp.arange(held))
                      ).sum(1)
            a = jax.nn.silu(jnp.einsum("th,ehf->tef", x, g)) \
                * jnp.einsum("th,ehf->tef", x, u)
            return jnp.einsum("tef,efh->th", a * w_held.astype(
                x.dtype)[..., None], d)

        args = (x, weight, gate, up, down)
        y_r, y_g, y_m = (np.asarray(fn(*args)[0], np.float64)
                         for fn in (routed, gathered, layer(masked)))
        rel = lambda a, b: float(
            f"{np.abs(a - b).max() / max(np.abs(b).max(), 1e-30):.3g}")
        rec["layer"] = {
            "rel_err": rel(y_r, y_m), "rel_err_xla_gathers": rel(y_g, y_m),
            "routed_ms": round(timed(routed, *args), 3),
            "routed_xla_gathers_ms": round(timed(gathered, *args), 3),
            "dense_masked_ms": round(timed(layer(masked), *args), 3),
            "floor_ms": round(3 * floor_ms, 3)}
        records.append(rec)
        print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_grouped.json"), "w") as fh:
        json.dump(records, fh, indent=1)
    bad = [r["load"] for r in records
           if not all(v.get("ok", True) for v in r.values()
                      if isinstance(v, dict))
           or not all(v.get("ok", True)
                      for v in r.get("movers", {}).values()
                      if isinstance(v, dict))]
    print(json.dumps({"loads": len(records), "failed": bad}), flush=True)
    return 1 if bad else 0


# (S, query heads, key heads, key width, value width, window) of one row of
# one lane, as the two language-model cells call the attention.
ATTENTION_SHAPES = {
    "mellum2_window": (8192, 32, 4, 128, 128, 1024),
    "mellum2_full": (8192, 32, 4, 128, 128, None),
    "joyai": (4096, 32, 32, 192, 128, None),
}
# Tilings timed by default: the six ``BlockSizes`` numbers (one number
# stands for all six, three for the forward and the dk/dv kernel alike),
# ``/split`` for a dq kernel of its own, ``/pad`` with the keys' features
# padded with zeros to whole lane tiles (only where they are not).
ATTENTION_BLOCKS = ("512,512,512,1024,1024,512",        # what ships
                    "512,512,512,1024,1024,512/split",
                    "512,512,512,1024,1024,512/pad", "512", "512/split",
                    "256", "1024,1024,512", "1024,1024,512/split",
                    "512,512,512,1024,1024,1024",
                    "512,512,512,1024,2048,512",
                    "512,512,512,512,1024,512")


def attention_forms(argv, impl="kernel", shapes=None, times=5) -> int:
    """The attention ALONE, bf16, one row of one lane under the models' two
    ``vmap``s, at each shape of ``ATTENTION_SHAPES`` with documents of
    2048 tokens on average:

    - the XLA query blocks (``models/layers.py::packed_causal_attention``
      with ``impl="jnp"``, blocks of 512 rematerialised as the models run
      them): forward, and forward + backward;
    - the fused kernel (``ops/attention.py``) at each tiling of ``argv``
      (default ``ATTENTION_BLOCKS``; ``bq,bkv,bkv_compute,bq_dkv,bkv_dkv,
      bkv_dkv_compute``, the first three for both kernels or one number
      for all six; ``/split`` for a dq kernel of its own instead of the
      fused backward kernel, ``/pad`` with q's and k's features padded
      with zeros to whole lane tiles): the same two
      times, its largest difference from the XLA form (output and the
      three cotangents, relative to the form's largest value) and the
      positions it scores over those required;
    - ``err_f32``: each form's largest difference from the XLA blocks in
      float32 at ``HIGHEST`` on the same bf16 operands, the same four
      arrays: what either form's roundings cost;
    - each time's share of the MXU roofline for the REQUIRED positions
      (``sum_i min(i + 1, window)`` a head, documents not counted; 2
      operations a multiply-add over both contractions, backward twice
      the forward)."""
    from blades_tpu.models import layers
    from blades_tpu.ops import attention

    bf16 = jnp.bfloat16
    specs = tuple(argv) or ATTENTION_BLOCKS
    interpret = impl == "interpret"

    def timed(fn, *args):
        return _median_ms(fn, *args, times=times)

    def rel(got, ref):
        """Largest difference of each array from its reference, relative
        to the reference's largest value."""
        return [float(f"{np.abs(np.asarray(a, np.float64) - b).max() / np.abs(b).max():.3g}")
                for a, b in zip(got, ref)]

    def both(form):
        """``(forward, forward + backward)`` of ``form(q, k, v, seg)``
        under the lanes' ``vmap``, jitted."""
        def fwd(q, k, v, seg, ct):
            return jax.vmap(form)(q, k, v, seg)

        def fwd_bwd(q, k, v, seg, ct):
            out, vjp = jax.vjp(
                lambda q, k, v: jax.vmap(form)(q, k, v, seg), q, k, v)
            return (out,) + vjp(ct)
        return jax.jit(fwd), jax.jit(fwd_bwd)

    records = []
    for name, (s, heads, kv_heads, dk, dv, window) in (
            shapes or ATTENTION_SHAPES).items():
        key = jax.random.split(jax.random.PRNGKey(34), 5)
        q = jax.random.normal(key[0], (1, 1, s, heads, dk), bf16)
        k = jax.random.normal(key[1], (1, 1, s, kv_heads, dk), bf16)
        v = jax.random.normal(key[2], (1, 1, s, kv_heads, dv), bf16)
        ct = jax.random.normal(key[3], (1, 1, s, heads, dv), bf16)
        seg = jnp.cumsum(jax.random.bernoulli(
            key[4], max(1 / 2048, 4 / s), (1, 1, s)).astype(jnp.int32), -1)
        args = (q, k, v, seg, ct)
        required = sum(min(i + 1, window or s) for i in range(s))
        fwd_floor = 1e3 * heads * 2 * required * (dk + dv) / 197e12
        scale = dk ** -0.5

        def line(fwd_ms, both_ms):
            return {"fwd_ms": round(fwd_ms, 3),
                    "fwd_bwd_ms": round(both_ms, 3),
                    "fwd_roofline_pct": round(100 * fwd_floor / fwd_ms, 1),
                    "fwd_bwd_roofline_pct": round(
                        300 * fwd_floor / both_ms, 1)}

        rec = {"shape": name, "s": s, "heads": heads, "kv_heads": kv_heads,
               "dk": dk, "dv": dv, "window": window,
               "documents": int(seg.max()) + 1,
               "required_positions_a_head": required,
               "fwd_floor_ms": round(fwd_floor, 3)}
        def xla_blocks(q, k, v, seg):
            return layers.packed_causal_attention(
                q, k, v, seg, scale, 512, window, impl="jnp")

        xla_fwd, xla_both = both(xla_blocks)
        want = [np.asarray(a, np.float64) for a in xla_both(*args)]
        with jax.default_matmul_precision("highest"):
            exact = [np.asarray(a, np.float64) for a in both(xla_blocks)[1](
                *(a.astype(jnp.float32) if a.dtype == bf16 else a
                  for a in args))]
        rec["xla_blocks"] = dict(
            line(timed(xla_fwd, *args), timed(xla_both, *args)),
            err_f32=rel(want, exact),
            scored_over_required=round(layers.attention_scores_computed(
                s, 512, window) / required, 4))
        for spec in specs:
            sizes, _, how = spec.partition("/")
            blocks = tuple(int(b) for b in sizes.split(","))
            blocks = blocks * (6 // len(blocks))
            pad = -dk % 128 if how == "pad" else 0
            if how == "pad" and not pad:
                continue
            wider = ((0, 0),) * 3 + ((0, pad),)
            try:
                k_fwd, k_both = both(lambda q, k, v, seg, b=blocks: (
                    attention.fused_causal_attention(
                        jnp.pad(q, wider), jnp.pad(k, wider), v, seg, scale,
                        window, blocks=b, fused_bwd=how != "split",
                        interpret=interpret)))
                got = k_both(*args)
                diff = rel(got, want)
                rec[f"kernel_{spec}"] = dict(
                    line(timed(k_fwd, *args), timed(k_both, *args)),
                    rel_diff=diff, err_f32=rel(got, exact),
                    ok=bool(max(diff) < 3e-2),
                    scored_over_required=round(attention.scores_computed(
                        s, window, blocks) / required, 4))
            except Exception as e:   # a refused tiling is a finding
                rec[f"kernel_{spec}"] = {
                    "ok": False, "error": f"{type(e).__name__}: {e}"[-400:]}
        records.append(rec)
        print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_attention.json"), "w") as fh:
        json.dump(records, fh, indent=1)
    bad = [f"{r['shape']}:{k}" for r in records for k, v in r.items()
           if isinstance(v, dict) and not v.get("ok", True)]
    print(json.dumps({"shapes": len(records), "failed": bad}), flush=True)
    return 1 if bad else 0


def main(argv) -> int:
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_kernels: needs a TPU, JAX found {dev[0].platform}",
              file=sys.stderr)
        return 2
    if argv and argv[0] == "--sweep":
        return sweep(argv[1:])
    if argv and argv[0] == "--grouped":
        return grouped_product(argv[1:])
    if argv and argv[0] == "--attention":
        return attention_forms(argv[1:])
    picked = [c for c in CASES
              if not argv or any(s in c.name for s in argv)]
    records = []
    for case in picked:
        rec = check(case)
        records.append(rec)
        print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_kernels.json"), "w") as f:
        json.dump({"device_kind": dev[0].device_kind, "cases": records}, f,
                  indent=1)
    failed = [r["case"] for r in records if not r["ok"]]
    print(json.dumps({"cases": len(records), "failed": failed}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

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
  readings ``pallas_select._STRIPE_BUDGET`` was chosen from.

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
    run across stripes).  A refused compile is a line, not a failure."""
    rows = int(argv[0]) if argv else SWEEP_ROWS
    d = int(argv[1]) if len(argv) > 1 else SWEEP_D
    dtype = jnp.dtype(argv[2] if len(argv) > 2 else "bfloat16")
    widths = tuple(int(w) for w in argv[3:]) or SWEEP_WIDTHS
    ruled = pallas_select.stripe_cols(rows)

    @functools.partial(jax.jit, static_argnames=("dpad",))
    def matrix(dpad):
        """The matrix as the round allocates it, padded to the stripe
        with zero columns: values in [-1, 1) hashed from (row, column),
        one elementwise program with no temporary beside the 6.6 GB."""
        col = jax.lax.broadcasted_iota(jnp.uint32, (rows, dpad), 1)
        row = jax.lax.broadcasted_iota(jnp.uint32, (rows, dpad), 0)
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
        return _bits_sums(agg), _bits_sums(forged), sq

    records, want = [], None
    for cols in sorted({STRIPE, ruled, *widths}):
        dpad = -(-d // cols) * cols
        rec = {"rows": rows, "d": d, "dtype": dtype.name, "cols": cols,
               "grid": dpad // cols, "ruled": cols == ruled}
        try:
            x = jax.block_until_ready(matrix(dpad))
            t0 = time.perf_counter()
            got = jax.block_until_ready(finish(x, cols))
            rec["first_call_s"] = round(time.perf_counter() - t0, 2)
            ms = []
            for _ in range(4):
                t0 = time.perf_counter()
                jax.block_until_ready(finish(x, cols))
                ms.append(round((time.perf_counter() - t0) * 1e3, 2))
            rec["ms"] = ms
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
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join(
            "chiprun_out", f"chip_kernels_sweep_{rows}x{d}_{dtype.name}.json"),
            "w") as f:
        json.dump(records, f, indent=1)
    return 0


def main(argv) -> int:
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_kernels: needs a TPU, JAX found {dev[0].platform}",
              file=sys.stderr)
        return 2
    if argv and argv[0] == "--sweep":
        return sweep(argv[1:])
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

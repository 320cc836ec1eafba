#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at
the full width of ResNet-10: the single-chip giant-federation round of
``blades_tpu/tuned_examples/fedavg_cifar10_1000clients.yaml`` (Median
arm — 1000 clients, 250 ALIE, bf16 compute and update matrix,
``execution: streamed``), loaded from the YAML and run through
``Fedavg.train()`` for a few rounds plus one ``evaluate()``.  Data is the
seeded synthetic CIFAR stand-in; nothing under ``~/.blades_tpu`` or
``~/.cache/blades_tpu`` is read.

It reports facts, not a benchmark result: one JSON line per phase on
stdout (device, versions, compile vs. steady round seconds, per-round
losses, eval metrics, peak HBM, which finish program ran — read from the
compiled program, not from the gate — and a compiled-kernel-vs-reference
check at the round's own finish shape), then as the LAST line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Exit code 0 only if every phase passed.  With no TPU it exits non-zero
within seconds and prints no result — JAX itself falls back to the CPU
with a warning when libtpu cannot take the chip, so the platform is
checked, not assumed.  Everything runs in this one process: a chip
belongs to one process at a time.

    chiprun -- python chip_smoke.py
"""

from __future__ import annotations

import gc
import importlib.metadata
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
YAML = os.path.join(ROOT, "blades_tpu", "tuned_examples",
                    "fedavg_cifar10_1000clients.yaml")
ROUNDS = 4  # >= 3, so at least one runs on warm executables
# The four-chip leg: the same arm on the width-sharded mesh round.  The
# path holds n=1000 on four v5e chips but then compiles for 325 s; 384
# clients compile in ~30 s (tools/chip_mesh.py, CHANGES.md PR 21).
DSHARDED_LEG = {"execution": "dsharded", "num_devices": 4,
                "num_clients": 384, "num_malicious_clients": 96}

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
)


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


class CompileLog:
    """What JAX compiled in this process, from ``jax.monitoring``: seconds
    spent tracing + lowering + compiling, the programs the backend
    compiled (by name), and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.programs = {}
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _BACKEND_COMPILE:
            name = kw.get("fun_name", "?")
            self.programs[name] = self.programs.get(name, 0) + 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1


def build_trial(yaml_path: str, aggregator: str, overrides=None):
    """The YAML's trial whose aggregator is ``aggregator``, built the way
    ``run_experiments`` builds it."""
    from blades_tpu.algorithms import get_algorithm_class
    from blades_tpu.tune import expand_grid, load_experiments_from_file

    (spec,) = load_experiments_from_file(yaml_path).values()
    (trial,) = [t for t in expand_grid(spec["config"])
                if t["server_config"]["aggregator"]["type"] == aggregator]
    _, config = get_algorithm_class(spec["run"], return_config=True)
    config.update_from_dict(trial)
    config.update_from_dict(overrides or {})
    return config.build()


def train_rounds(algo, rounds: int, compiles: CompileLog) -> dict:
    """``rounds`` calls of ``Fedavg.train()``; every loss must be finite
    and every round healthy."""
    rows, secs, compile_s = [], [], []
    for _ in range(rounds):
        c0, t0 = compiles.seconds, time.perf_counter()
        row = algo.train()  # fetches the round's metrics: a full sync
        secs.append(round(time.perf_counter() - t0, 3))
        compile_s.append(round(compiles.seconds - c0, 3))
        rows.append(row)
    losses = [float(r["train_loss"]) for r in rows]
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite train_loss: {losses}")
    if not all(r.get("round_ok", True) for r in rows):
        raise AssertionError("a round reported round_ok=false")
    if rows[-1]["training_iteration"] != rounds:
        raise AssertionError(f"ran {rows[-1]['training_iteration']} rounds, "
                             f"wanted {rounds}")
    return {
        "rounds": rounds,
        "train_loss": [round(v, 5) for v in losses],
        "round_ok": True,
        "num_unhealthy": [int(r.get("num_unhealthy", 0)) for r in rows],
        "elided_lanes": rows[-1].get("elided_lanes"),
        "finish_stripe_cols": rows[-1].get("finish_stripe_cols"),
        "round_s": secs,
        "compile_s_in_round": compile_s,
        "steady_round_s": min(secs),
    }


def evaluate(algo) -> dict:
    ev = algo.evaluate()
    if not all(math.isfinite(float(v)) for v in ev.values()):
        raise AssertionError(f"non-finite eval metrics: {ev}")
    if not 0.0 <= ev["test_acc"] <= 1.0:
        raise AssertionError(f"test_acc out of range: {ev}")
    return {k: round(float(v), 5) for k, v in ev.items()}


def finish_that_ran(algo, compiles: CompileLog) -> dict:
    """Which finish program the streamed round ran, and what it compiled
    to — read from the compiled program, not from the gate.

    The round runs exactly one of three jitted finishes; the backend's
    own compile log says which were ever compiled.  The fused compact
    finish is then lowered again at the round's shapes: JAX hands back
    the executable it already holds unless the shapes differ, so no new
    backend compile means the inspected HLO is the program the rounds
    ran.  That HLO is searched for the Mosaic custom call.
    """
    import jax
    import jax.numpy as jnp

    from blades_tpu.parallel.streamed import block_plan, compact_matrix

    want = "jit(_finish_fused_compact)"
    ran = sorted({"jit(_finish)", "jit(_finish_fused)", want}
                 & set(compiles.programs))
    if ran != [want]:
        raise AssertionError(f"finish programs compiled: {ran}, "
                             f"expected only {want}")
    cfg = algo.config
    n, f = cfg.num_clients, cfg.num_malicious_clients
    # The matrix by the rule the round builds it by (two-dimensional for
    # blocks of whole storage tiles, a row a plane for blocks under one).
    dtype = jnp.dtype(cfg.update_dtype)
    plan = block_plan(n, f, cfg.client_block, dtype, compact=True)
    matrix, _ = compact_matrix(plan, n - f, algo._num_params)
    buf = jax.ShapeDtypeStruct(matrix, dtype)
    losses = jax.ShapeDtypeStruct((n,), jnp.float32)
    compiled = compiles.programs[want]
    hlo = algo._step.finish_fused_compact.lower(
        algo.state.server, buf, algo.malicious, losses,
        jax.random.PRNGKey(0), nb_real=n - f).compile().as_text()
    if compiles.programs[want] != compiled:
        raise AssertionError(
            f"lowering {want} at {matrix} compiled a new program: "
            "the inspected program is not the one the rounds ran")
    calls = hlo.count("tpu_custom_call")
    if calls < 1:
        raise AssertionError("no tpu_custom_call in the compiled finish")
    return {"finish_program": want, "times_compiled": compiled,
            "matrix": list(matrix), "tpu_custom_calls_in_hlo": calls}


def kernel_vs_reference() -> dict:
    """The compact finish kernel, compiled, at the round's own stripe
    shape against the repo's float64 reference (tools/chip_kernels.py)."""
    from tools.chip_kernels import CASES, check

    (case,) = [c for c in CASES
               if c.name == "compact_median_bfloat16_nb750_mult250"]
    rec = check(case)
    if not rec.pop("ok"):
        raise AssertionError(f"kernel != reference: {rec}")
    return rec


def placement(algo) -> dict:
    """Where the client stack lives: shards on distinct devices and
    per-device HBM roughly equal (everything on device 0 is the expected
    failure of a mesh path)."""
    import jax

    x = algo._train_arrays[0]
    shard_devs = sorted(s.device.id for s in x.addressable_shards)
    used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
    n_dev = algo.config.num_devices
    if len(set(shard_devs)) != n_dev:
        raise AssertionError(f"client stack on devices {shard_devs}, "
                             f"wanted {n_dev} distinct")
    busy = sorted(used, reverse=True)[:n_dev]
    if busy[-1] < 0.5 * busy[0]:
        raise AssertionError(f"per-device bytes_in_use uneven: {used}")
    return {"client_stack_devices": shard_devs, "bytes_in_use": used}


class Phases:
    """Runs each phase, prints the facts it returns (a dict) as one JSON
    line, and remembers the phases that raised."""

    def __init__(self):
        self.failed = []

    def run(self, name, fn, *args):
        t0 = time.perf_counter()
        try:
            result = fn(*args)
        except Exception as e:
            traceback.print_exc()
            say(name, ok=False, error=f"{type(e).__name__}: {e}"[-600:])
            self.failed.append(name)
            return None
        say(name, ok=True, seconds=round(time.perf_counter() - t0, 2),
            **(result if isinstance(result, dict) else {}))
        return result


def describe(algo) -> dict:
    cfg = algo.config
    if not algo.dataset.synthetic:
        raise AssertionError("real data was loaded; the smoke is seeded "
                             "synthetic")
    if algo.plan is not None:
        raise AssertionError("an autotune plan was resolved")
    return {"synthetic": algo.dataset.synthetic,
            "model": cfg.global_model, "params": algo._num_params,
            "clients": cfg.num_clients,
            "malicious": cfg.num_malicious_clients,
            "execution": cfg.execution, "client_block": cfg.client_block,
            "update_dtype": str(cfg.update_dtype)}


def dsharded_leg(compiles: CompileLog) -> dict:
    algo = build_trial(YAML, "Median", DSHARDED_LEG)
    try:
        facts = train_rounds(algo, 3, compiles)
        facts.update(placement(algo))
    finally:
        algo.stop()
    return {"clients": algo.config.num_clients, **facts}


def main() -> int:
    if os.environ.get("BLADES_TPU_NO_PALLAS", "0") != "0":
        print("chip_smoke: BLADES_TPU_NO_PALLAS is set — the smoke proves "
              "the Pallas kernels, unset it", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2

    # Hermetic data: a root that does not exist selects the seeded
    # synthetic stand-in whatever ~/.blades_tpu/data holds.
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(ROOT, ".no_data")
    from blades_tpu.algorithms.fedavg import Fedavg
    from blades_tpu.perf import enable_persistent_compilation_cache

    cache_dir = enable_persistent_compilation_cache()
    compiles = CompileLog()
    limit, limit_source = Fedavg.dense_matrix_hbm_limit_source()
    say("device", **device, jax=jax.__version__,
        jaxlib=importlib.metadata.version("jaxlib"),
        libtpu=importlib.metadata.version("libtpu"),
        compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=(
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0),
        dense_matrix_hbm_limit=limit,
        dense_matrix_hbm_limit_source=limit_source)

    phases = Phases()
    algo = phases.run("build", build_trial, YAML, "Median")
    if algo is not None:
        phases.run("config", describe, algo)
        phases.run("train", train_rounds, algo, ROUNDS, compiles)
        phases.run("eval", evaluate, algo)
        phases.run("finish_kernel", finish_that_ran, algo, compiles)
        stats = dev[0].memory_stats()
        say("memory", peak_bytes_in_use=stats["peak_bytes_in_use"],
            bytes_limit=stats["bytes_limit"])
        algo.stop()
        del algo
        gc.collect()  # hand the streamed leg's HBM back before the next
    phases.run("kernel_vs_reference", kernel_vs_reference)
    if len(dev) >= 4:
        phases.run("dsharded", dsharded_leg, compiles)
    else:
        say("dsharded", skipped=f"needs 4 chips, have {len(dev)}")

    say("compile", seconds=round(compiles.seconds, 2),
        backend_compiles=sum(compiles.programs.values()),
        persistent_cache_requests=compiles.cache_requests,
        persistent_cache_hits=compiles.cache_hits)
    if phases.failed:
        print(json.dumps({"ok": False, "failed": phases.failed,
                          "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, warm-up, window, reference, reduction."""

from __future__ import annotations

import gc
import glob
import json
import math
import os
import shutil
import sys
import time

from . import compare, tracered, window
from .manifest import Manifest, data_kind, family, reader

TRACE_SECONDS = 8.0   # the profiler covers the window's first rounds only
TRACE_MIN_ROUNDS = 3


def say(**facts) -> None:
    """A fact of the run, as one JSON line on standard error."""
    print(json.dumps(facts), file=sys.stderr, flush=True)


def out_dir(checkout: str) -> str:
    d = os.environ.get("PERFBENCH_OUT") or os.path.join(checkout,
                                                        "perfbench_out")
    os.makedirs(d, exist_ok=True)
    return d


def device_facts() -> dict:
    import jax

    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


class Cell:
    """The program built for one cell on one seed, and what the harness
    reads back from it.  Set-up builds this one object; the warm-up rounds
    and the window both go through ``round()``."""

    def __init__(self, manifest: Manifest, workload: str, seed: int,
                 rehearse: bool = False, spoil=None):
        from . import sut

        self.manifest, self.workload, self.seed = manifest, workload, seed
        files = manifest.cell(workload)
        self.cfg, self.traffic = files["config"], files["traffic"]
        self.limits = files["limits"]["limits"]
        # Rounds the reference follows: the configuration's, or fewer where
        # the cell's file says so to keep the reference under the window.
        self.compared_rounds = files["limits"].get(
            "reference_rounds", self.cfg["reference"]["rounds"])
        self.reference_block = self.cfg["reference"]["client_block"]
        if rehearse:
            # The traffic file's tiny sizes (PERFBENCH_REHEARSE: other
            # overrides, as JSON); never a measurement.
            tiny = self.traffic["rehearsal"]
            self.traffic = json.loads(json.dumps(self.traffic))
            self.traffic["overrides"].update(
                json.loads(os.environ.get("PERFBENCH_REHEARSE", "null"))
                or tiny["overrides"])
            self.cfg = dict(self.cfg, **tiny.get("config", {}))
            self.reference_block = tiny["reference_client_block"]
        self.family = family(self.cfg["family"], manifest.root)
        self.kind = data_kind(self.traffic["data"]["kind"], manifest.root)
        t = time.perf_counter()
        found = sut.trial_dict(manifest.checkout, self.traffic)
        config = sut.build_config(found, seed)
        self.fed = sut.federation(config)
        if not rehearse:
            for k in ("num_clients", "num_malicious_clients"):
                if self.fed[k] != self.cfg[k]:
                    raise ValueError(
                        f"{k}: the configuration's file says {self.cfg[k]}, "
                        f"the traffic builds {self.fed[k]}")
        self.data = self.kind.make(self.traffic["data"],
                                   self.fed["num_clients"], self.cfg, seed)
        self.t_data = time.perf_counter() - t
        t = time.perf_counter()
        self.algo = sut.build(config, self.kind, self.data)
        self.params0 = self.family.init_params(self.cfg, seed)
        sut.place_weights(self.algo, self.params0)
        self.params0 = sut.server_params(self.algo)
        if spoil is not None:   # the fault tests break the timed path here
            spoil(self)
        self.t_build = time.perf_counter() - t
        self.describe = sut.describe(self.algo)
        self.rows = []

    def follow(self, **kw) -> dict:
        """The plain reference over this cell's first rounds (``quant``,
        ``fault``: the control and the planted faults)."""
        from . import reference

        return reference.run_rounds(
            self.family, self.kind, self.cfg, self.fed, self.data, self.seed,
            self.compared_rounds, self.reference_block, **kw)

    def round(self) -> dict:
        import jax

        with jax.profiler.TraceAnnotation(tracered.ROUND_SPAN):
            row = self.algo.train()   # fetches the round's metrics: a sync
        self.rows.append(row)
        return row

    def params(self):
        from . import sut

        return sut.server_params(self.algo)

    def evaluate(self) -> dict:
        return self.algo.evaluate()

    def free(self) -> None:
        """Give the program's device memory back before the reference."""
        import jax

        self.algo.stop()
        self.algo = None
        gc.collect()
        jax.clear_caches()
        gc.collect()


def warm_up(cell: Cell, rounds: int, compared: int) -> dict:
    """The program's first rounds through the window's own call, with what
    the comparison needs of the first ``compared``: every loss, the
    parameters after round 1 and after the last of them."""
    losses, secs, params = [], [], []
    for r in range(max(rounds, compared)):
        t = time.perf_counter()
        row = cell.round()
        secs.append(time.perf_counter() - t)
        if r < compared:
            losses.append(float(row["train_loss"]))
        if r == 0 or r == compared - 1:
            params.append(cell.params())
    return {"losses": losses, "params0": cell.params0, "params": params,
            "round_s": secs}


def run_window(cell: Cell, seconds: float, trace_dir=None) -> dict:
    """Rounds until ``seconds`` have passed and the round in flight has
    completed.  With ``trace_dir`` the profiler covers the first rounds."""
    import jax

    starts, ends, traced = [], [], 0
    tracing = False
    collections, began = [], [0.0]

    def on_gc(phase, info):
        # The collector's pauses, so that a long round can be laid beside
        # them: [generation, seconds into the window, seconds it took].
        now = time.perf_counter()
        if phase == "start":
            began[0] = now
        elif now - began[0] >= 1e-3 or info["generation"] == 2:
            collections.append([info["generation"], began[0] - t0,
                                now - began[0]])

    gc.callbacks.append(on_gc)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing = True
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        if t - t0 >= seconds and ends:
            break
        if tracing and t - t0 >= min(TRACE_SECONDS, seconds) \
                and len(ends) >= TRACE_MIN_ROUNDS:
            jax.profiler.stop_trace()
            tracing, traced = False, len(ends)
            continue
        starts.append(t)
        cell.round()
        ends.append(time.perf_counter())
    gc.callbacks.remove(on_gc)
    if tracing:
        jax.profiler.stop_trace()
        traced = len(ends)
    return {"t0": t0, "starts": starts, "ends": ends, "traced": traced,
            "collections": collections[:200]}


def run_cell(checkout: str, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, rehearse: bool = False,
             spoil=None, reference_cache=None) -> tuple:
    """``(exit code, result dict or None)``."""
    from . import sut

    t_import = time.perf_counter() - t_process
    manifest = Manifest(checkout)
    want_chips = manifest.workloads[workload]["chips"] \
        if workload in manifest.workloads else 1
    device = device_facts()
    t_chip = time.perf_counter() - t_process - t_import
    if (device["platform"] != "tpu" or device["count"] < want_chips) \
            and not rehearse:
        print(f"perfbench: {workload} needs {want_chips} TPU chip(s), JAX "
              f"found {device}; a CPU run is a rehearsal "
              "(--rehearse), never a measurement", file=sys.stderr)
        return 2, None
    peaks = manifest.peaks(device["kind"]) if not rehearse else \
        {"bf16_flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
    cache_dir = None if rehearse else sut.place_compile_cache(checkout)
    log = sut.CompileLog()
    mark0 = log.mark()
    cell = Cell(manifest, workload, seed, rehearse, spoil)
    t_dev = time.perf_counter()
    rounds = cell.compared_rounds
    prog = warm_up(cell, cell.traffic["warmup_rounds"], rounds)
    t_warm = time.perf_counter() - t_dev
    gc.collect()
    gc.freeze()
    mark1 = log.mark()
    setup_s = time.perf_counter() - t_process
    say(phase="setup", setup_s=setup_s, imports_s=t_import, chip_init_s=t_chip,
        data_s=cell.t_data, build_s=cell.t_build, warmup_rounds_s=t_warm,
        warmup_round_s=prog["round_s"],
        compile_or_load_s=log.between(mark0, mark1)["seconds"],
        compile_cache_dir=cache_dir, **cell.describe)

    trace_dir = None
    if trace:
        trace_dir = os.path.join(out_dir(checkout), "trace", workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    n_warm = len(cell.rows)
    win = run_window(cell, seconds, trace_dir)
    mark2 = log.mark()
    peak = memory_peak_bytes()
    rows = cell.rows[n_warm:]
    round_s = [e - s for s, e in zip(win["starts"], win["ends"])]
    attempted = len(rows)
    failed = sum(sut.round_failed(r) for r in rows)
    rate = window.rounds_per_s(win["t0"], win["ends"])

    eval_ms = None
    if trace:
        cell.evaluate()                       # compiles or loads
        t = time.perf_counter()
        cell.evaluate()
        eval_ms = 1e3 * (time.perf_counter() - t)
    gc.unfreeze()

    record = {
        "workload": workload, "seed": seed, "trace": int(trace),
        "seconds": seconds, "device": device, "setup_s": setup_s,
        "rounds_per_s": rate, "warmup_round_s": prog["round_s"],
        "window_round_s": round_s,
        "traced_rounds": win["traced"],
        "round_start_s": [s - win["t0"] for s in win["starts"]],
        "collections": win["collections"],
        "window_compiles": log.between(mark1, mark2),
        "passes_per_round": [int(r["hbm_passes"]) for r in rows
                             if "hbm_passes" in r],
        "train_loss": [float(r["train_loss"]) for r in rows],
    }
    write_round_file(checkout, record)

    cell.free()
    t = time.perf_counter()
    key = (workload, seed, rehearse)
    if reference_cache is not None and key in reference_cache:
        ref = reference_cache[key]
    else:
        ref = cell.follow()
        if reference_cache is not None:
            reference_cache[key] = ref
    t_ref = time.perf_counter() - t
    nums = compare.numbers(prog, ref)
    correct, report = compare.decide(nums, cell.limits)
    correct = correct and failed == 0
    say(phase="reference", seconds=t_ref, where=nums["_where"],
        **ref.get("info", {}))

    metrics = {}
    device_out = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if not trace:
        values = {"rounds_per_s": rate, "setup_s": setup_s}
        for m in manifest.metrics_of(workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {
            "traced_rounds": win["traced"],
            "traced_round_s": round_s[:win["traced"]], "round_s": round_s,
            "config": cell.cfg, "family": cell.family,
            "federation": cell.fed, "peaks": peaks,
            "chips": want_chips, "rows": rows, "memory_peak_bytes": peak,
            "eval_ms": eval_ms, "notes": {},
            "compile": {"setup": log.between(mark0, mark1),
                        "window": log.between(mark1, mark2)},
        }
        metrics, traced = per_layer(manifest, workload, trace_dir, ctx)
        if traced is not None:
            device_out.update(busy_s=traced["busy_s"],
                              window_s=traced["window_s"])
            breakdown = traced["breakdown"]
        say(phase="notes", **ctx["notes"])

    for name, value, limit in report:
        print(f"compared {name} = {value!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(failed), "metrics": metrics,
              "device": device_out}
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in report}
    return 0, result


def per_layer(manifest: Manifest, workload: str, trace_dir: str,
              ctx: dict) -> tuple:
    """The traced run's reduction: ``(metrics, {"busy_s", "window_s",
    "breakdown"} or None where no device was traced)``.  Each metric comes
    from the reader its own file names; one that finds nothing is left out."""
    pb = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    reduced = tracered.load_xplane(pb[-1]) if pb else None
    shutil.rmtree(trace_dir, ignore_errors=True)
    k, round_s = ctx["traced_rounds"], ctx["round_s"]
    # The clock's percentiles leave the traced rounds out where enough remain.
    ctx.update(trace=reduced,
               clock_round_s=round_s[k:] if len(round_s) - k >= 10
               else round_s)
    metrics = {}
    for m in manifest.metrics_of(workload, "per_layer"):
        spec = manifest.metric_file(m["name"])
        value = reader(spec["reader"], manifest.root)(ctx, spec)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if reduced is None or not reduced["devices"]:
        return metrics, None
    busy, win_s = tracered.busy_and_window_s(reduced)
    return metrics, {"busy_s": busy, "window_s": win_s, "breakdown": {
        "device_ops": tracered.heaviest(tracered.first_device(reduced)),
        "idle_gaps": tracered.idle_gaps(reduced)}}


def write_round_file(checkout: str, record: dict) -> str:
    base = os.path.join(out_dir(checkout), "rounds")
    os.makedirs(base, exist_ok=True)
    stem = (f"{record['workload']}.seed{record['seed']}"
            f".trace{record['trace']}")
    n = len(glob.glob(os.path.join(base, stem + ".*.json")))
    path = os.path.join(base, f"{stem}.{n}.json")
    with open(path, "w") as f:
        json.dump(record, f)
    return path

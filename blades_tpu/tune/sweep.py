"""Grid expansion + sequential trial runner (ref: blades/train.py:60-126,
310-408)."""

from __future__ import annotations

import copy
import csv
import itertools
import json
import os
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import yaml


# ---------------------------------------------------------------------------
# grid_search expansion (Tune-compatible)
# ---------------------------------------------------------------------------


def _find_grids(node: Any, path: Tuple = ()) -> List[Tuple[Tuple, List]]:
    """Locate every ``{"grid_search": [...]}`` node (depth-first)."""
    grids = []
    if isinstance(node, dict):
        if set(node.keys()) == {"grid_search"}:
            return [(path, node["grid_search"])]
        for k, v in node.items():
            grids.extend(_find_grids(v, path + (k,)))
    elif isinstance(node, list):
        for i, v in enumerate(node):
            grids.extend(_find_grids(v, path + (i,)))
    return grids


def _set_path(cfg: Any, path: Tuple, value: Any) -> None:
    node = cfg
    for p in path[:-1]:
        node = node[p]
    node[path[-1]] = value


def expand_grid(config: Dict) -> List[Dict]:
    """Cartesian product over every grid_search node; deterministic order."""
    grids = _find_grids(config)
    if not grids:
        return [copy.deepcopy(config)]
    paths = [g[0] for g in grids]
    values = [g[1] for g in grids]
    trials = []
    for combo in itertools.product(*values):
        trial = copy.deepcopy(config)
        for path, v in zip(paths, combo):
            _set_path(trial, path, copy.deepcopy(v))
        trials.append(trial)
    return trials


# ---------------------------------------------------------------------------
# experiment loading (ref: train.py:60-126)
# ---------------------------------------------------------------------------


def load_experiments_from_file(path: str) -> Dict[str, Dict]:
    """YAML file of ``{name: {run, stop, config, ...}}`` experiment specs."""
    with open(path) as f:
        experiments = yaml.safe_load(f)
    if not isinstance(experiments, dict):
        raise ValueError(f"{path} must map experiment names to specs")
    for name, spec in experiments.items():
        if "run" not in spec:
            raise ValueError(f"experiment {name!r} missing 'run' (algorithm name)")
        spec.setdefault("stop", {"training_iteration": 100})
        spec.setdefault("config", {})
    return experiments


# ---------------------------------------------------------------------------
# lane grouping: which trials can share one vmapped program?
# ---------------------------------------------------------------------------

# Trial-dict paths a lane may vary, mapped to tune.lanes flat keys.
_LANE_PATHS = {
    ("dataset_config", "seed"): "seed",
    ("seed",): "seed",
    ("client_config", "lr"): "client_lr",
    ("client_lr",): "client_lr",
    ("server_config", "lr"): "server_lr",
    ("server_lr",): "server_lr",
    ("dp_epsilon",): "dp_epsilon",
    ("dp_clip_threshold",): "dp_clip_threshold",
    ("adversary_config", "scale"): "adversary_scale",
}
_LANE_SENTINEL = "__LANE__"


def _get_path(cfg, path):
    node = cfg
    for p in path:
        if not isinstance(node, dict) or p not in node:
            return None, False
        node = node[p]
    return node, True


def _lane_signature(trial: Dict):
    """(signature-json, {lane_key: value}) — trials with equal signatures
    differ only in lane-traceable knobs."""
    present_paths = {}
    conflict = False
    for path, key in _LANE_PATHS.items():
        val, present = _get_path(trial, path)
        if present and not isinstance(val, (dict, list)):
            if key in present_paths and present_paths[key][1] != val:
                # Two config paths alias the same lane knob (e.g. both
                # `seed` and `dataset_config.seed`) with DIFFERENT
                # values — laning would silently pick one.  Keep such a
                # trial out of lane grouping entirely (its signature is
                # its raw config, so only literally identical trials
                # could share it, with no overrides to mis-apply).
                conflict = True
            else:
                present_paths[key] = (path, val)
    if conflict:
        sig = dict(trial, __lane_conflict__=True)
        return json.dumps(sig, sort_keys=True, default=str), {}
    sig = copy.deepcopy(trial)
    overrides = {}
    for key, (path, val) in present_paths.items():
        overrides[key] = val
        _set_path(sig, path, _LANE_SENTINEL)
    return json.dumps(sig, sort_keys=True, default=str), overrides


def lane_groups(trials: List[Dict]) -> List[List[int]]:
    """Partition trial indices into groups runnable as one vmapped program
    (same static config, differing only in lane knobs).  Singletons mean
    'run sequentially'."""
    by_sig: Dict[str, List[int]] = {}
    for i, t in enumerate(trials):
        sig, _ = _lane_signature(t)
        by_sig.setdefault(sig, []).append(i)
    return list(by_sig.values())


def _lanes_eligible(spec_run: str, trial: Dict, group: List[int]) -> bool:
    """Static gate: is this group safe to vmap? (Dense small-model trials
    only — a vmapped giant-model federation would OOM where the
    sequential driver streams.)"""
    from blades_tpu.algorithms import get_algorithm_class

    if len(group) < 2:
        return False
    try:
        _, cfg = get_algorithm_class(spec_run, return_config=True)
        cfg.update_from_dict(copy.deepcopy(trial))
        cfg.validate()
    except Exception:
        return False
    if not (
        cfg.execution in ("auto", "dense")
        and cfg.num_clients <= 200
        and not cfg.num_devices
    ):
        return False
    if getattr(cfg, "forensics", False):
        # The laned program has no forensics formulation yet — a laned
        # trial would silently drop the per-lane telemetry the user asked
        # for, so it runs sequentially.
        return False
    if getattr(cfg, "fault_config", None):
        # Same for the chaos layer: the laned program has no fault
        # injection, so a faulted trial would silently run failure-free.
        return False
    if getattr(cfg, "state_window", None) is not None:
        # Participation-window / stateless trials run sequentially: the
        # vmapped lane program has no cohort staging (and no stateless
        # re-init), so a laned trial would silently train the resident
        # full-participation round instead.
        return False
    if getattr(cfg, "autotune_mode", None):
        # The vmapped lane program has no plan machinery — an autotuned
        # trial runs sequentially so its plan resolution, provenance
        # stamps and checkpoint plan record all engage.  (The NORMALIZED
        # mode, not the raw value: an explicit autotune: "off" must not
        # knock its lane group back to sequential execution.)
        return False
    if cfg.lr_schedule:
        _, ov = _lane_signature(trial)
        if "server_lr" in ov:
            # Statically known incompatibility (the schedule interpolation
            # cannot take a traced lr) — skip the group cheaply instead of
            # letting run_lanes raise after building the model.
            return False
    # Bound the vmapped update-matrix footprint (L x n x d f32): a
    # sequential 'auto' trial above the dense budget would stream, but
    # lanes have no streamed formulation — an eligible-looking group
    # would compile-OOM (wasted work) or run with different numerics
    # than the sequential run it must reproduce.
    from blades_tpu.algorithms.fedavg import Fedavg
    from blades_tpu.utils.tree import tree_size

    try:
        import jax

        params_shape = jax.eval_shape(
            lambda: cfg.get_task_spec().build().init_params(
                jax.random.PRNGKey(0))
        )
        d = tree_size(params_shape)
    except Exception as exc:
        import warnings

        warnings.warn(f"lane eligibility probe failed for group {group}: "
                      f"{type(exc).__name__}: {exc}", RuntimeWarning)
        return False
    lane_bytes = len(group) * cfg.num_clients * d * 4
    return lane_bytes <= Fedavg.dense_matrix_hbm_limit()


# ---------------------------------------------------------------------------
# trial runner (ref: train.py:310-408 without the Ray cluster)
# ---------------------------------------------------------------------------


def _trial_name(base: str, idx: int, trial_cfg: Dict) -> str:
    return f"{base}_{idx:05d}"


def _pin_checkpoint_plan(config, tdir: Path) -> None:
    """Pin an autotuned trial's execution plan to the one its latest
    checkpoint was written under (``config.tuned_plan``), so a
    retry/resume REPLAYS the identical plan instead of silently
    re-tuning mid-trajectory (the plan cache may have been invalidated
    or re-measured since the trial started).  No-op without autotune, a
    checkpoint, or a recorded plan."""
    if not getattr(config, "autotune_mode", None):
        return
    ckpt = _latest_checkpoint(tdir)
    if ckpt is None:
        return
    import pickle

    p = ckpt / "algorithm_state.pkl"
    try:
        with open(p, "rb") as f:
            plan = pickle.load(f).get("plan")
    except Exception:
        return  # unreadable checkpoint: restore itself will surface it
    if plan:
        config.tuned_plan = plan


def _read_results(path: Path) -> List[Dict]:
    """Parse a trial's ``result.json`` line stream (tolerant of a torn
    final line from a killed run)."""
    rows = []
    if not path.exists():
        return rows
    for line in path.read_text().splitlines():
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            break
    return rows


def _truncate_results(path: Path, upto_round: int) -> None:
    """Drop result rows past ``upto_round`` before appending a restored
    run's rows — otherwise a restore from a checkpoint older than the last
    written row would duplicate (and regress) ``training_iteration`` in
    the line stream that visualization/resume consume.  Parses EVERY line
    itself (not via :func:`_read_results`, which stops at the first bad
    line): a torn fragment mid-stream — a killed run's tear that a later
    append sealed — must not make truncation silently discard the valid
    records after it.  The undecodable fragments themselves are dropped."""
    if not path.exists():
        return
    lines = path.read_text().splitlines()
    kept = []
    dirty = False
    for line in lines:
        try:
            r = json.loads(line)
        except json.JSONDecodeError:
            dirty = True  # fragment: drop it, keep parsing
            continue
        if r.get("training_iteration", 0) <= upto_round:
            kept.append(line)
        else:
            dirty = True
    if dirty:
        with open(path, "w") as f:
            for line in kept:
                f.write(line + "\n")


def _truncate_csv(path: Path, upto_round: int) -> None:
    """CSV analogue of :func:`_truncate_results` for ``metrics.csv``: drop
    rows past ``upto_round`` by the ``training_iteration`` column so a
    checkpoint-restore retry appends without duplicating rounds.  Parsed
    with the ``csv`` module (quoted cells may contain commas); a row whose
    iteration cell does not parse — e.g. a torn final line from a killed
    run — is KEPT: truncation must never destroy data it cannot read."""
    if not path.exists():
        return
    with open(path, newline="") as f:
        rows = list(csv.reader(f))
    if not rows:
        return
    try:
        col = rows[0].index("training_iteration")
    except ValueError:
        return
    kept = [rows[0]]
    for row in rows[1:]:
        try:
            if int(float(row[col])) > upto_round:
                continue
        except (IndexError, ValueError):
            pass
        kept.append(row)
    if len(kept) != len(rows):
        with open(path, "w", newline="") as f:
            csv.writer(f).writerows(kept)


def _latest_checkpoint(tdir: Path) -> Optional[Path]:
    """Newest periodic checkpoint by round number (``ckpt_<round>``).

    Orphaned ``ckpt_*.tmp`` directories — an atomic checkpoint write
    (:func:`blades_tpu.faults.host.atomic_checkpoint`) that a SIGKILL
    interrupted before its ``os.replace`` — are DELETED here, never
    restored: their contents are of unknown completeness, and the
    previous published checkpoint is the newest trustworthy state.
    """
    import shutil

    ckpts = []
    for p in tdir.glob("ckpt_*"):
        if p.name.endswith(".tmp"):
            shutil.rmtree(p, ignore_errors=True)
        elif p.name != "ckpt_final":
            ckpts.append(p)
    ckpts.sort(key=lambda p: p.name)
    return ckpts[-1] if ckpts else None


def verify_result_rounds(path) -> List[int]:
    """The no-duplicate/no-gap round-sequence check for a trial's
    ``result.json``: ``training_iteration`` must rise by one a row.  A
    resume that
    restored a stale checkpoint without truncating, or skipped rounds,
    fails here.  Returns the iteration list on success, raises
    ``ValueError`` otherwise."""
    rows = _read_results(Path(path))
    its = [r.get("training_iteration") for r in rows]
    if any(i is None for i in its):
        raise ValueError(f"{path}: rows missing training_iteration")
    if not its:
        return its
    if its != list(range(its[0], its[0] + len(its))):
        raise ValueError(
            f"{path}: round sequence has duplicates or gaps: {its[:20]}..."
            if len(its) > 20 else
            f"{path}: round sequence has duplicates or gaps: {its}"
        )
    return its


def _prune_checkpoints(
    tdir: Path, keep_num: Optional[int], scores: Dict[str, float]
) -> None:
    """Keep the ``keep_num`` best checkpoints (by recorded score, newest
    breaking ties) — the reference CLI's checkpoint_keep_num/score_attr
    policy (ref: blades/train.py:175-180)."""
    if not keep_num:
        return
    ckpts = [p for p in tdir.glob("ckpt_*") if p.name != "ckpt_final"]
    if len(ckpts) <= keep_num:
        return
    ckpts.sort(key=lambda p: (scores.get(p.name, float("-inf")), p.name))
    import shutil

    for p in ckpts[: len(ckpts) - keep_num]:
        shutil.rmtree(p, ignore_errors=True)


def _resolve_watchdog(watchdog):
    """Normalize the ``watchdog`` request to a rule tuple (or None):
    ``True``/``"on"`` arms the default rule set; a ``Watchdog`` instance
    or a sequence of :class:`~blades_tpu.obs.watchdog.WatchdogRule`
    supplies custom rules.  Each trial gets its OWN evaluator (rolling
    state is per trial)."""
    if not watchdog:
        return None
    from blades_tpu.obs.watchdog import (Watchdog, default_rules,
                                         rules_from_config)

    if watchdog is True or watchdog == "on":
        return default_rules()
    if isinstance(watchdog, Watchdog):
        return watchdog.rules
    # A sequence of WatchdogRule instances and/or rule DICTS (the
    # --watchdog-rules JSON surface) — rules_from_config fail-fasts on
    # unknown keys/kinds/fields.
    return rules_from_config(list(watchdog))


# Row fields mirrored onto the dispatch span as provenance args, so a
# trace viewer shows the autotuner / fusion / codec decisions inline
# with the time they explain (ISSUE 12).
_TRACE_ROW_ATTRS = (
    "training_iteration", "plan_id", "hbm_passes", "hbm_passes_unfused",
    "agg_domain", "agg_domain_bits", "comm_bytes_up", "codec_bits",
    "comm_compression_ratio", "pack_factor", "packed_lanes",
    "elided_lanes", "compile_cache_hits", "compile_cache_misses",
    "dequant_rows", "num_participating", "num_dropped", "num_straggled",
    "ici_bytes", "preagg_kept", "mesh_shape",
    "gossip_ici_bytes", "num_partitioned_nodes", "topology",
    "spectral_gap",
)


def _run_lane_group(
    spec_run: str,
    trials: List[Dict],
    group: List[int],
    max_rounds: int,
    exp_name: str,
    root: Path,
    verbose: int,
    metrics_csv: bool = False,
    strict_metrics: bool = True,
    trace_dir: Optional[str] = None,
    wd_rules=None,
    flightrec_rounds: int = 0,
) -> Dict[int, Dict]:
    """Run one lane group as a vmapped program; write each member trial's
    ``result.json``/``params.json``/metrics streams exactly as the
    sequential path does and return its summaries keyed by trial index.
    (No stdout heartbeat here: the vmapped program returns all rows only
    after the whole group finishes, so a replayed 'heartbeat' would be a
    post-hoc burst, not a liveness signal.)"""
    from blades_tpu.algorithms import get_algorithm_class
    from blades_tpu.obs import CsvSink, JsonlSink, MetricsLogger
    from blades_tpu.obs.flightrec import FlightRecorder
    from blades_tpu.obs.trace import Timers
    from blades_tpu.obs.watchdog import Watchdog
    from blades_tpu.tune.lanes import run_lanes

    sig_cfg = None
    overrides = []
    for i in group:
        sig, ov = _lane_signature(trials[i])
        overrides.append(ov)
        sig_cfg = sig_cfg or json.loads(sig)

    def strip_sentinels(node):
        if isinstance(node, dict):
            return {k: strip_sentinels(v) for k, v in node.items()
                    if v != _LANE_SENTINEL}
        if isinstance(node, list):
            return [strip_sentinels(v) for v in node]
        return node

    shared = strip_sentinels(sig_cfg)

    def builder():
        _, cfg = get_algorithm_class(spec_run, return_config=True)
        cfg.update_from_dict(copy.deepcopy(shared))
        return cfg

    if verbose:
        print(f"== lane group {exp_name}[{group[0]}..{group[-1]}]: "
              f"{len(group)} trials x {max_rounds} rounds as one program ==",
              flush=True)
    from blades_tpu.perf import cache_stats, fingerprint

    cache_before = cache_stats()
    # Span tracing (obs/trace.py): the group's round dispatches become
    # spans of ONE tree, exported per group when --trace-dir is set.
    tracer = Timers(record=bool(trace_dir))
    gspan = tracer.start("lane_group", experiment=exp_name,
                         trials=len(group), rounds=max_rounds)
    # program_key: the group's SHARED static config (the lane signature
    # with the per-lane knobs already sentinel-ed out) — identical groups
    # across experiments/sweeps reuse one compiled lane program.
    results = run_lanes(builder, overrides, max_rounds,
                        program_key=(spec_run.upper(), fingerprint(sig_cfg),
                                     len(overrides)),
                        tracer=tracer)
    tracer.finish(gspan)
    wall = gspan.duration
    if trace_dir:
        tdir_trace = Path(trace_dir).expanduser()
        tracer.export(tdir_trace / (f"{exp_name}_lanes_"
                                    f"{group[0]:05d}-{group[-1]:05d}"
                                    ".trace.json"))
    cache_after = cache_stats()
    cache_delta = {
        "hits": cache_after["hits"] - cache_before["hits"],
        "misses": cache_after["misses"] - cache_before["misses"],
    }

    out: Dict[int, Dict] = {}
    for lane, i in enumerate(group):
        tname = _trial_name(exp_name, i, trials[i])
        tdir = root / exp_name / tname
        tdir.mkdir(parents=True, exist_ok=True)
        with open(tdir / "params.json", "w") as f:
            json.dump(_jsonable(trials[i]), f, indent=2, default=str)
        rows = results[lane]
        sinks: List = [JsonlSink(tdir / "metrics.jsonl",
                                 strict=strict_metrics)]
        if metrics_csv:
            sinks.append(CsvSink(tdir / "metrics.csv"))
        # Watchdog + flight recorder run POST-hoc here (the vmapped
        # program returns all rows after the group finishes), with
        # fresh per-trial rolling state — the same rules and dump
        # triggers as the sequential path, minus the mid-run liveness.
        wd = Watchdog(wd_rules) if wd_rules is not None else None
        # A stale dump from a previous run in the same storage path
        # describes a PREVIOUS divergence — postmortem poison next to
        # this run's fresh artifacts (same contract as the sequential
        # path's fresh-run cleanup; lane groups never run under resume).
        (tdir / "flightrec.json").unlink(missing_ok=True)
        flightrec = (FlightRecorder(
            tdir / "flightrec.json", capacity=flightrec_rounds,
            experiment=exp_name, trial=tname, algo=spec_run,
            config=trials[i], max_rounds=max_rounds)
            if flightrec_rounds else None)
        with open(tdir / "result.json", "w") as f, MetricsLogger(
            sinks, base={"experiment": exp_name, "trial": tname},
        ) as logger:
            for row in rows:
                row = _jsonable(row)
                if "watchdog_events" in row:
                    # Controlled driver: events already stamped (see the
                    # sequential path's comment).
                    events = list(row["watchdog_events"] or [])
                else:
                    events = [e.as_dict() for e in
                              (wd.observe(row) if wd is not None else [])]
                    if events:
                        row["watchdog_events"] = events
                f.write(json.dumps({**row, "trial": tname}) + "\n")
                logger.log(row)
                if flightrec is not None:
                    flightrec.record(row)
                    trig = flightrec.check(row)
                    if trig is None and events:
                        trig = {"kind": "watchdog",
                                "rules": [e["rule"] for e in events],
                                "round": row.get("training_iteration")}
                    if trig is not None:
                        flightrec.dump(trig)
        best = max((r.get("test_acc", 0.0) for r in rows), default=0.0)
        final = {k: rows[-1][k] for k in ("test_loss", "test_acc",
                                          "test_acc_top3")
                 if k in rows[-1]} if rows else {}
        out[i] = {
            "trial": tname, "rounds": max_rounds,
            "wall_s": round(wall, 2),
            "rounds_per_sec": round(max_rounds * len(group) / wall, 2)
            if wall else None,
            "best_test_acc": best, "final": final, "dir": str(tdir),
            "lanes": len(group),
            "compile_cache": cache_delta,
        }
        comm = _comm_summary(rows[-1] if rows else {})
        if comm:
            out[i]["comm"] = comm
        packing = _packing_summary(rows[-1] if rows else {})
        if packing:
            out[i]["packing"] = packing
    return out


def _packing_summary(row: Dict) -> Optional[Dict]:
    """The lane-packing provenance slice for laned-trial summaries (the
    stamps are static per round, so the last row stands for the
    trial; sequential trials carry the fuller decision dict from
    ``algo.packing_summary`` instead)."""
    packing = {k: row[k] for k in ("pack_factor", "packed_lanes")
               if k in row}
    return packing or None


def _comm_summary(row: Dict) -> Optional[Dict]:
    """The comm subsystem's per-trial summary slice (codec byte
    accounting and the aggregation-domain provenance are static per
    round, so the last row's values stand for the whole trial;
    dequant_rows is a per-round planner constant under a fixed config)."""
    comm = {k: row[k] for k in ("comm_bytes_up", "codec_bits",
                                "comm_compression_ratio", "agg_domain",
                                "agg_domain_bits", "dequant_rows")
            if k in row}
    return comm or None


def _mesh_summary(row: Dict) -> Optional[Dict]:
    """The pod-scale provenance slice for trial summaries (the three
    hierarchical stamps are static per round under a fixed config, so
    the last row stands for the trial — the hbm_passes convention)."""
    mesh = {k: row[k] for k in ("mesh_shape", "ici_bytes", "preagg_kept")
            if k in row}
    return mesh if "ici_bytes" in mesh else None


def _gossip_summary(row: Dict) -> Optional[Dict]:
    """The decentralized-round provenance slice for trial summaries
    (graph stamps are static per run; gossip_ici_bytes is static under a
    fixed config, so the last row stands for the trial)."""
    g = {k: row[k] for k in ("topology", "graph_seed", "spectral_gap",
                             "gossip_ici_bytes", "num_partitioned_nodes",
                             "consensus_dist")
         if k in row}
    return g if "gossip_ici_bytes" in g else None


def _arrivals_summary(row: Dict) -> Optional[Dict]:
    """The buffered-async ingest slice for trial summaries (the final
    row's cumulative counters and staleness digest stand for the trial;
    updates_per_sec is the last cycle's wall-clock ingest rate)."""
    arr = {k: row[k] for k in ("tick", "updates_per_sec",
                               "staleness_mean", "staleness_max",
                               "buffer_fill", "buffer_overflow",
                               "arrivals_dropped", "arrival_seed")
           if k in row}
    return arr if "tick" in arr else None


def run_experiments(
    experiments: Dict[str, Dict],
    storage_path: str = "~/blades_tpu_results",
    verbose: int = 1,
    checkpoint_freq: int = 0,
    checkpoint_at_end: bool = False,
    max_rounds_override: Optional[int] = None,
    resume: bool = False,
    checkpoint_keep_num: Optional[int] = None,
    checkpoint_score_attr: str = "training_iteration",
    max_failures: int = 0,
    lanes: bool = True,
    metrics_csv: bool = False,
    heartbeat_every: int = 10,
    cost_analysis: bool = True,
    strict_metrics: bool = True,
    retry_backoff_base: float = 0.5,
    retry_backoff_cap: float = 30.0,
    preempt_after: Optional[int] = None,
    autotune=None,
    plan_cache_dir: Optional[str] = None,
    trace_dir: Optional[str] = None,
    watchdog=False,
    flightrec_rounds: int = 16,
) -> List[Dict]:
    """Run every trial of every experiment; returns summaries.

    **Observability layer** (ISSUE 12, :mod:`blades_tpu.obs`):

    - ``trace_dir`` (the CLI's ``--trace-dir``): arm the span tracer —
      every trial records a host-side span tree (trial -> round /
      compile / checkpoint -> training_step / evaluate) with round
      provenance (plan_id, hbm_passes, agg_domain, comm_bytes_up, ...)
      stamped on the dispatch spans, exported atomically as
      Chrome/Perfetto trace JSON to ``<trace_dir>/<trial>.trace.json``
      (lane groups export one tree per group).  Composes with the
      ``--trace`` jax-profiler hook: armed spans enter
      ``TraceAnnotation``/``StepTraceAnnotation``, so device work lands
      inside the right host span in the profiler capture.  Off
      (default) the rows/aggregates are bit-identical to a pre-span
      build — the tracer degenerates to the old phase accumulator.
    - ``watchdog`` (the CLI's ``--watchdog``): arm the anomaly watchdog
      (:mod:`blades_tpu.obs.watchdog`) — schema-driven rules (NaN
      aggregate/loss, update-norm spike vs rolling median,
      detection-FPR collapse, round-wall-time regression) evaluated
      host-side on the already-fetched rows, zero extra device syncs.
      Firing rules land in the row as ``watchdog_events`` and trigger
      the flight-recorder dump.  Kill-and-resume rebuilds the rolling
      windows from the truncated on-disk rows, so a restored trial
      replays the same rule decisions.
    - ``flightrec_rounds`` (default 16, 0 disables): each trial keeps a
      bounded ring of its last K row digests and dumps it atomically to
      ``<trial>/flightrec.json`` on a non-finite aggregate, a watchdog
      event, an uncaught exception, or a (simulated) preemption —
      ``tools/replay_round.py`` re-executes the recorded round
      bit-identically from the dump's (config, seed, tick).

    **Round-pipeline perf layer** (:mod:`blades_tpu.perf`):

    - Every trial is a loop of ``algo.train()`` calls: one round
      dispatched, its metrics fetched, its row written — before the
      checkpoint that covers it.
    - JAX's persistent compilation cache is always on, so repeat sweeps
      skip XLA entirely: at ``$JAX_COMPILATION_CACHE_DIR`` when set,
      else the fixed ``<checkout>/.jax_cache``
      (:func:`blades_tpu.perf.enable_persistent_compilation_cache`).
      Independent of the in-process AOT executable cache, whose
      per-trial hit/miss deltas land in each summary under
      ``compile_cache`` (and per round in the metrics stream as
      ``compile_cache_hits``/``compile_cache_misses``).
    - ``autotune`` (the CLI's ``--autotune``): enable the execution
      autotuner (:mod:`blades_tpu.perf.autotune`) on every trial that
      does not set its own ``autotune`` config — ``True``/``"on"`` for
      the numerics-preserving default tier, ``"reassociating"`` to also
      offer the opt-in tier.  Autotuned trials run sequentially (never
      laned), stamp plan provenance into their
      round rows, and surface the full selection record in the summary
      under ``"autotune"``.  Retries and resumes PIN the plan recorded
      in the latest checkpoint (``config.tuned_plan``) so a restored
      trajectory replays the identical plan instead of re-tuning.
      ``plan_cache_dir`` points the persistent plan cache somewhere
      other than ``$BLADES_TPU_PLAN_CACHE_DIR`` / the default.

    **Metrics pipeline** (obs subsystem): every trial also streams one
    schema-validated JSONL record per round to ``<trial>/metrics.jsonl``
    (plus ``metrics.csv`` when ``metrics_csv=True`` and a stdout heartbeat
    every ``heartbeat_every`` rounds at ``verbose > 1``), carrying the
    training/eval metrics, defense-forensics scalars (``forensics=True``
    trials), health counts, and per-phase timings.  Each summary gains
    ``timers`` (sweep-level compile / round / eval / checkpoint phases,
    ``obs/trace.py``; evaluation runs inside ``algo.train()``, so the
    ``eval`` phase OVERLAPS compile/round rather than adding to them —
    subtract it for pure-training estimates) and
    ``cost`` (XLA's compiled FLOPs/bytes for one
    training dispatch — NOTE: ``lower().compile()`` cannot reuse the jit
    cache, so this re-traces and recompiles the dispatch once per trial;
    pass ``cost_analysis=False`` to skip it when compiles are expensive,
    e.g. ResNet-scale models on CPU).  Laned trials (vmapped groups) get
    the same per-round streams but their summaries carry ``lanes`` instead
    of ``timers``/``cost`` — the vmapped program has no per-trial phase
    split.  A schema violation fails the trial FAST (no checkpoint-restart
    retries — it is deterministic); a custom trainable registered into
    ``ALGORITHMS`` that emits unregistered metric keys should either
    register them in ``blades_tpu/obs/schema.py`` or pass
    ``strict_metrics=False``.  A retried trial's streams are truncated to
    its restore round exactly like ``result.json``.

    ``lanes=True`` (default): shape-compatible trial subsets — same static
    config, differing only in lane-traceable knobs (seed, client/server
    lr, DP epsilon/clip, IPM scale; see :mod:`blades_tpu.tune.lanes`) —
    run as ONE vmapped program instead of sequentially, the TPU analogue
    of the reference's concurrent Tune trials (ref:
    blades/train.py:380-386).  Lanes engage only for fresh dense
    small-model runs without checkpointing (checkpoint/resume/fault
    machinery stays per-trial-sequential); everything else is
    unaffected.  Results are written per trial exactly as in sequential
    mode.

    Per trial: ``result.json`` (one JSON line per round, Tune's format) and
    ``params.json`` in ``<storage>/<experiment>/<trial>/``.

    ``resume=True`` (the reference CLI's ``--restore``/``resume``, ref:
    blades/train.py:154,228): trials whose ``result.json`` already reached
    the stop criterion are skipped; in-flight trials restore from their
    latest periodic checkpoint and continue appending.  A 2000-round grid
    killed at any point picks up without redoing finished work.
    ``checkpoint_keep_num`` bounds on-disk checkpoints, keeping the best by
    ``checkpoint_score_attr`` (newest on ties).

    ``max_failures`` is Tune's trial fault tolerance (the reference
    inherits it via ``tune.run_experiments``, SURVEY.md §5): a trial that
    raises is restarted from its latest periodic checkpoint up to
    ``max_failures`` times (the error is appended to ``error.txt`` in the
    trial dir); a trial that exhausts its retries is marked failed in the
    summary and the REMAINING trials still run.  Restarts back off
    exponentially (``retry_backoff_base`` doubling up to
    ``retry_backoff_cap`` seconds) with deterministic jitter seeded from
    the trial — immediate restarts would hammer a persistently failing
    trial (see :func:`blades_tpu.faults.host.retry_backoff`).

    **Checkpoint durability** (chaos layer, :mod:`blades_tpu.faults.host`):
    every checkpoint save is atomic — written to ``ckpt_<round>.tmp``,
    fsynced, then published by one ``os.replace``.  A SIGKILL landing
    mid-write leaves at worst an orphaned ``.tmp`` that restore deletes;
    ``_latest_checkpoint`` can never hand a torn checkpoint to
    ``load_checkpoint``.  ``preempt_after=N`` is the test hook for
    exactly that path: the sweep raises a ``SimulatedPreemption`` once,
    the first time a trial finishes round N (between the result-row write
    and the checkpoint save), so kill-and-resume — crash, backoff,
    restore from an OLDER checkpoint, truncate, re-run with no duplicated
    or skipped rounds — is exercised end-to-end without a real SIGKILL.
    """
    from blades_tpu.algorithms import get_algorithm_class
    from blades_tpu.faults.host import (PreemptionHook, SimulatedPreemption,
                                        atomic_checkpoint, retry_backoff)
    from blades_tpu.obs import CsvSink, JsonlSink, MetricsLogger, StdoutSink
    from blades_tpu.obs.flightrec import FlightRecorder
    from blades_tpu.obs.trace import Timers
    from blades_tpu.obs.watchdog import Watchdog
    from blades_tpu.perf import (cache_stats,
                                 enable_persistent_compilation_cache)

    enable_persistent_compilation_cache()
    wd_rules = _resolve_watchdog(watchdog)
    flightrec_rounds = int(flightrec_rounds or 0)

    def _apply_autotune(config) -> bool:
        """Apply the sweep-level autotune request to a trial config
        (trial-level settings win) and report whether the trial is
        autotuned."""
        if autotune and not getattr(config, "autotune", False):
            config.autotune = (autotune if isinstance(autotune, str)
                               else True)
        if plan_cache_dir and not getattr(config, "autotune_cache_dir",
                                          None):
            config.autotune_cache_dir = plan_cache_dir
        return bool(getattr(config, "autotune_mode", None))

    preempt_hook = PreemptionHook(preempt_after) if preempt_after else None

    root = Path(storage_path).expanduser()
    summaries = []
    for exp_name, spec in experiments.items():
        trials = expand_grid(spec.get("config", {}))
        stop = spec.get("stop", {})
        max_rounds = int(max_rounds_override or stop.get("training_iteration", 100))

        # Vmapped lane groups (concurrent-trial analogue).  Incompatible
        # with checkpoint/resume/fault handling, which stay sequential.
        laned: Dict[int, Dict] = {}
        lane_failed: Dict[int, str] = {}
        if (lanes and not resume and not checkpoint_freq
                and not checkpoint_at_end and max_failures == 0
                and not autotune):
            for group in lane_groups(trials):
                if not _lanes_eligible(spec["run"], trials[group[0]], group):
                    continue
                try:
                    laned.update(_run_lane_group(
                        spec["run"], trials, group, max_rounds, exp_name,
                        root, verbose, metrics_csv=metrics_csv,
                        strict_metrics=strict_metrics,
                        trace_dir=trace_dir, wd_rules=wd_rules,
                        flightrec_rounds=flightrec_rounds,
                    ))
                except Exception as exc:
                    # LOUD fallback: a lane-group failure means the
                    # concurrent path silently diverged from sequential
                    # capability — always warn and stamp the affected
                    # trials' summaries, never swallow.
                    import warnings

                    msg = f"{type(exc).__name__}: {exc}"
                    warnings.warn(
                        f"lane group {exp_name}{group} fell back to "
                        f"sequential execution ({msg})", RuntimeWarning)
                    print(f"   !! lane group {group} fell back to "
                          f"sequential ({msg})", flush=True)
                    for i in group:
                        lane_failed[i] = msg

        for i, trial_cfg in enumerate(trials):
            if i in laned:
                summaries.append(laned[i])
                if verbose:
                    print(f"   -> {laned[i]}", flush=True)
                continue
            tname = _trial_name(exp_name, i, trial_cfg)
            tdir = root / exp_name / tname
            tdir.mkdir(parents=True, exist_ok=True)
            if not resume:
                # Fresh run: clear checkpoints left by a previous sweep in
                # the same storage path, or a transient-crash retry would
                # restore a STALE run's state and skip this run's rounds.
                import shutil

                for p in tdir.glob("ckpt_*"):
                    shutil.rmtree(p, ignore_errors=True)
                for p in (tdir / "metrics.jsonl", tdir / "metrics.csv",
                          # A stale flight-recorder dump describes a
                          # PREVIOUS run's divergence — postmortem
                          # poison for this one.
                          tdir / "flightrec.json"):
                    p.unlink(missing_ok=True)
            prior = _read_results(tdir / "result.json") if resume else []
            best_acc = max((r.get("test_acc", 0.0) for r in prior), default=0.0)
            done = prior[-1].get("training_iteration", 0) if prior else 0
            if resume and done >= max_rounds:
                summary = {
                    "trial": tname, "rounds": done, "wall_s": 0.0,
                    "rounds_per_sec": None, "best_test_acc": best_acc,
                    "final": {}, "dir": str(tdir), "resumed": "skipped",
                }
                if verbose:
                    print(f"== trial {tname}: finished ({done} rounds), "
                          "skipping ==", flush=True)
                summaries.append(summary)
                continue
            algo_cls, config = get_algorithm_class(spec["run"], return_config=True)
            config.update_from_dict(trial_cfg)
            autotuned = _apply_autotune(config)
            cache_before = cache_stats()
            if resume and autotuned:
                # Replay the checkpointed plan, never re-tune a
                # restored trajectory (see _pin_checkpoint_plan).
                _pin_checkpoint_plan(config, tdir)
            algo = config.build()
            resumed_from = None
            if resume:
                ckpt = _latest_checkpoint(tdir)
                if ckpt is not None:
                    algo.load_checkpoint(str(ckpt))
                    resumed_from = algo.iteration
                    _truncate_results(tdir / "result.json", algo.iteration)
                    _truncate_results(tdir / "metrics.jsonl", algo.iteration)
                    _truncate_csv(tdir / "metrics.csv", algo.iteration)
            with open(tdir / "params.json", "w") as f:
                json.dump(_jsonable(trial_cfg), f, indent=2, default=str)
            if verbose:
                tag = (f" (resumed @ round {resumed_from})"
                       if resumed_from else "")
                print(f"== trial {tname}: {max_rounds} rounds{tag} ==",
                      flush=True)
            timers = Timers(record=bool(trace_dir))
            if trace_dir:
                # One span tree per trial: the algorithm's phase timers
                # (training_step / evaluate) nest inside this tracer's
                # trial/round spans, and the tree exports to trace_dir.
                algo.adopt_tracer(timers)
            trial_span = timers.start("trial", experiment=exp_name,
                                      trial=tname)
            start_round = algo.iteration
            ckpt_scores: Dict[str, float] = {}
            failures = 0
            failed_error = None
            compiled = False
            last_row: Dict = {}  # survives the attempt loop (comm summary)
            # Anomaly watchdog + flight recorder (obs subsystem): fresh
            # per-trial state; a resumed trial warms its rolling windows
            # from the truncated on-disk rows so rule decisions replay.
            wd = Watchdog(wd_rules) if wd_rules is not None else None
            flightrec = (FlightRecorder(
                tdir / "flightrec.json", capacity=flightrec_rounds,
                experiment=exp_name, trial=tname, algo=spec["run"],
                config=trial_cfg, max_rounds=max_rounds)
                if flightrec_rounds else None)
            if flightrec is not None:
                # Hand the recorder the trial's client ledger (if armed):
                # dumps then carry a shard-wise CRC digest of the
                # longitudinal records at crash time.
                flightrec.ledger = getattr(algo, "client_ledger", None)
            if resumed_from and (wd is not None or flightrec is not None):
                surviving = _read_results(tdir / "result.json")
                if wd is not None:
                    wd.warm(surviving)
                if flightrec is not None:
                    flightrec.rewind(surviving)
            while True:
                mode = "a" if (resumed_from or failures) else "w"
                logger = None
                try:
                    # Sinks reopen per attempt (inside the fault-tolerance
                    # try: an OSError opening a stream is a trial failure,
                    # not a sweep abort): a retry truncates metrics.jsonl
                    # under any handle left open from the failed attempt,
                    # so the stream must be re-entered at the truncated
                    # offset.
                    sinks: List = [JsonlSink(tdir / "metrics.jsonl",
                                             mode=mode,
                                             strict=strict_metrics)]
                    if metrics_csv:
                        sinks.append(CsvSink(tdir / "metrics.csv", mode=mode))
                    if verbose > 1:
                        sinks.append(StdoutSink(every=heartbeat_every))
                    logger = MetricsLogger(
                        sinks, base={"experiment": exp_name, "trial": tname}
                    )
                    # last_row deliberately NOT reset per attempt: a retry
                    # that restores at the stop round emits no new rows,
                    # and the checkpoint-score / comm summaries below must
                    # still see the last row the trial produced.
                    with open(tdir / "result.json", mode) as f:

                        def emit(result):
                            nonlocal best_acc, last_row
                            result["trial"] = tname
                            row = _jsonable(result)
                            if "watchdog_events" in row:
                                # Controlled driver (blades_tpu/
                                # control): it owns its own watchdog
                                # and stamped the events — observing
                                # again would double-fire the
                                # rolling rules.
                                events = list(
                                    row["watchdog_events"] or [])
                            else:
                                events = [
                                    e.as_dict() for e in
                                    (wd.observe(row)
                                     if wd is not None else [])]
                                if events:
                                    row["watchdog_events"] = events
                            f.write(json.dumps(row) + "\n")
                            logger.log(row)
                            if flightrec is not None:
                                flightrec.record(row)
                                trig = flightrec.check(row)
                                if trig is None and events:
                                    trig = {
                                        "kind": "watchdog",
                                        "rules": [e["rule"]
                                                  for e in events],
                                        "round": row.get(
                                            "training_iteration"),
                                    }
                                if trig is not None:
                                    flightrec.dump(trig)
                            if trace_dir:
                                # Round provenance onto the span
                                # that dispatched this row (the
                                # first dispatch is the "compile"
                                # span).
                                timers.stamp_latest_of(
                                    ("round", "compile"),
                                    {k: row[k]
                                     for k in _TRACE_ROW_ATTRS
                                     if k in row})
                            best_acc = max(best_acc,
                                           result.get("test_acc", 0.0))
                            last_row = result

                        while algo.iteration < max_rounds:
                            # The first dispatch pays XLA compilation; split
                            # it from steady-state rounds so neither timing
                            # pollutes the other.  `step` puts the armed
                            # span under a StepTraceAnnotation, so device
                            # work correlates in a profiler capture.
                            with timers.time("round" if compiled
                                             else "compile",
                                             step=algo.iteration):
                                result = algo.train()
                            compiled = True
                            emit(result)
                            checkpoint_due = bool(
                                checkpoint_freq
                                and algo.iteration % checkpoint_freq == 0)
                            if preempt_hook is not None:
                                # Fires BETWEEN the row write and the
                                # checkpoint save — the widest window a
                                # real preemption lands in, so restore
                                # must come from an older checkpoint.
                                preempt_hook.check(algo.iteration)
                            if checkpoint_due:
                                # The no-gap contract ("every round a
                                # checkpoint covers is on disk first")
                                # needs the rows DURABLE, not just
                                # written: the checkpoint below is
                                # fsynced, so a kill right after it must
                                # not find these rows still in the
                                # userspace file buffer.
                                f.flush()
                                os.fsync(f.fileno())
                                name = f"ckpt_{algo.iteration:06d}"
                                with timers.time("checkpoint"):
                                    atomic_checkpoint(algo.save_checkpoint,
                                                      tdir / name)
                                ckpt_scores[name] = float(
                                    last_row.get(checkpoint_score_attr,
                                                 algo.iteration)
                                )
                                _prune_checkpoints(tdir, checkpoint_keep_num, ckpt_scores)
                    break
                except KeyboardInterrupt:
                    raise
                except Exception as exc:  # Tune's trial fault tolerance
                    from blades_tpu.obs.schema import SchemaError

                    failures += 1
                    import traceback

                    with open(tdir / "error.txt", "a") as ef:
                        ef.write(f"attempt {failures}: {exc!r}\n")
                        ef.write(traceback.format_exc() + "\n")
                    if flightrec is not None:
                        # The postmortem artifact: the last K rounds'
                        # digests, durable before any retry/abort.
                        flightrec.dump({
                            "kind": ("preemption"
                                     if isinstance(exc,
                                                   SimulatedPreemption)
                                     else "exception"),
                            "error": repr(exc),
                            "round": algo.iteration,
                        })
                    # SchemaError is deterministic metrics-schema drift, not
                    # a transient fault: every retry would re-pay the compile
                    # and fail identically on its first record.  Fail fast
                    # (without inflating the reported attempt count).
                    fail_fast = isinstance(exc, SchemaError)
                    if fail_fast or failures > max_failures:
                        failed_error = repr(exc)
                        if verbose:
                            print(f"   !! trial {tname} FAILED after "
                                  f"{failures} attempt(s): {exc!r}", flush=True)
                        break
                    # Exponential backoff with deterministic jitter before
                    # the restart: an immediate retry of a persistently
                    # failing trial hammers it (and whatever shared
                    # resource it is failing against) at full speed.
                    delay = retry_backoff(
                        failures,
                        trial_seed=f"{tname}:{trial_cfg.get('seed', 0)}",
                        base=retry_backoff_base, cap=retry_backoff_cap,
                    )
                    if delay > 0:
                        time.sleep(delay)
                    # Fresh build + restore from the latest checkpoint, the
                    # reference's restart-from-checkpoint trial retry.
                    _, config = get_algorithm_class(spec["run"], return_config=True)
                    config.update_from_dict(trial_cfg)
                    if _apply_autotune(config):
                        # A restarted autotuned trial replays the plan its
                        # latest checkpoint recorded — the cache may have
                        # been re-measured since the trial started, and a
                        # new winner mid-trajectory is exactly the silent
                        # re-tune drift the checkpoint record exists to
                        # prevent.
                        _pin_checkpoint_plan(config, tdir)
                    algo = config.build()
                    if trace_dir:
                        algo.adopt_tracer(timers)
                    compiled = False  # fresh build recompiles
                    ckpt = _latest_checkpoint(tdir)
                    if ckpt is not None:
                        algo.load_checkpoint(str(ckpt))
                    _truncate_results(tdir / "result.json", algo.iteration)
                    _truncate_results(tdir / "metrics.jsonl", algo.iteration)
                    _truncate_csv(tdir / "metrics.csv", algo.iteration)
                    if flightrec is not None:
                        # The rebuilt algorithm owns a fresh ledger
                        # (restored from the checkpoint above); re-point
                        # the recorder at it or dumps digest a dead one.
                        flightrec.ledger = getattr(
                            algo, "client_ledger", None)
                    if wd is not None or flightrec is not None:
                        # Replay the surviving rows into the rolling
                        # windows / the digest ring: the restarted trial
                        # sees the same history a straight-through run
                        # would, and the ring holds no stale ticks from
                        # the failed attempt.
                        surviving = _read_results(tdir / "result.json")
                        if wd is not None:
                            wd.warm(surviving)
                        if flightrec is not None:
                            flightrec.rewind(surviving)
                    if verbose:
                        print(f"   .. retrying {tname} from round "
                              f"{algo.iteration} (failure {failures}/"
                              f"{max_failures})", flush=True)
                finally:
                    if logger is not None:
                        logger.close()
            if checkpoint_at_end and failed_error is None:
                with timers.time("checkpoint"):
                    atomic_checkpoint(algo.save_checkpoint, tdir / "ckpt_final")
            timers.finish(trial_span)
            wall = trial_span.duration
            if trace_dir:
                # Per-trial Chrome/Perfetto trace, written atomically
                # (load in chrome://tracing or ui.perfetto.dev).
                timers.stamp_latest("trial", {"rounds": algo.iteration,
                                              "failures": failures})
                timers.export(Path(trace_dir).expanduser()
                              / f"{tname}.trace.json")
            new_rounds = algo.iteration - start_round
            # Sweep-level phase timings (satellite: compile / round / eval /
            # checkpoint): eval runs INSIDE algo.train(), so its phase
            # comes from the algorithm's own timers (getattr: custom
            # trainables registered into ALGORITHMS may not carry Timers)
            # and its time is also contained in the compile/round phases —
            # subtract 'eval' from 'round' for pure-training estimates.
            phase_timers = timers.summary()
            algo_timers = (algo.timers.summary()
                           if hasattr(algo, "timers") else {})
            if "evaluate" in algo_timers:
                phase_timers["eval"] = algo_timers["evaluate"]
            summary = {
                "trial": tname, "rounds": algo.iteration, "wall_s": round(wall, 2),
                "rounds_per_sec": round(new_rounds / wall, 2) if wall else None,
                "best_test_acc": best_acc, "final": algo._last_eval,
                "dir": str(tdir),
                "timers": phase_timers,
            }
            cache_after = cache_stats()
            cache_delta = {
                "hits": cache_after["hits"] - cache_before["hits"],
                "misses": cache_after["misses"] - cache_before["misses"],
            }
            if cache_delta["hits"] or cache_delta["misses"]:
                # AOT executable cache traffic attributable to THIS trial:
                # an identically-shaped sweep reports misses on its first
                # trial only, hits everywhere else.
                summary["compile_cache"] = cache_delta
            comm = _comm_summary(last_row)
            if comm:
                # Codec byte accounting (blades_tpu/comm), mirrored from
                # the per-round metrics stream into the trial summary.
                summary["comm"] = comm
            arrivals = _arrivals_summary(last_row)
            if arrivals:
                # Buffered-async ingest digest (blades_tpu/arrivals),
                # mirrored from the final row like the comm block.
                summary["arrivals"] = arrivals
            mesh = _mesh_summary(last_row)
            if mesh:
                # Pod-scale hierarchical-round digest (parallel/hier.py),
                # mirrored from the final row like the comm block.
                summary["mesh"] = mesh
            gossip = _gossip_summary(last_row)
            if gossip:
                # Decentralized-round digest (blades_tpu/topology),
                # mirrored from the final row like the mesh block.
                summary["gossip"] = gossip
            packing = getattr(algo, "packing_summary", None)
            if packing:
                # Lane-packing decision (parallel/packed.py): present
                # whenever packing was REQUESTED — a fallback shows
                # pack_factor 1 plus the reason, so operators can tell
                # packed from unpacked runs without reading logs.
                summary["packing"] = packing
            if wd is not None and wd.events:
                # Anomaly-watchdog digest: which rules fired, how often
                # (the full event dicts ride the rows' watchdog_events).
                summary["watchdog"] = {
                    "events": len(wd.events),
                    "rules": sorted({e.rule for e in wd.events}),
                }
            control = getattr(algo, "control_summary", None)
            if control:
                # Closed-loop controller digest (blades_tpu/control):
                # actions journaled, live actuator view, quarantine/
                # probation sets — the full journal rides the rows'
                # control_actions.
                summary["control"] = control
            if flightrec is not None and flightrec.dumps:
                summary["flightrec"] = {
                    "dumps": flightrec.dumps,
                    "path": str(tdir / "flightrec.json"),
                }
            plan_summary = getattr(algo, "plan_summary", None)
            if plan_summary:
                # Execution-autotuner provenance (perf/autotune.py):
                # selection mode (measured / heuristic / cache / pinned),
                # the full candidate list with per-candidate timings (or
                # None medians under the heuristic fallback), the winner
                # and the cache hit/miss flag — the complete selection
                # record the round rows only carry scalars of.
                summary["autotune"] = _jsonable(plan_summary)
            if (cost_analysis and failed_error is None
                    and hasattr(algo, "cost_analysis")):
                cost = algo.cost_analysis()
                if cost:
                    summary["cost"] = cost
            state_block = getattr(algo, "state_summary", None)
            if state_block:
                # Out-of-core client state (blades_tpu/state): store
                # backend + window + the staging peak, mirrored from the
                # row stamps like the comm/arrivals blocks.
                summary["state_store"] = state_block
            data_block = getattr(algo, "data_summary", None)
            if data_block:
                # Out-of-core training data (blades_tpu/data/store):
                # backend + partition geometry + the last gather's
                # staging stats + streaming-eval chunk count.
                summary["data_store"] = data_block
            ledger_block = getattr(algo, "ledger_summary", None)
            if ledger_block:
                # Client-lifetime ledger (blades_tpu/obs/ledger): fleet
                # telemetry — clients seen, flagged fractions, top
                # suspects — folded into the trial summary.
                summary["ledger"] = ledger_block
            if hasattr(algo, "stop"):
                # Release trial-scoped resources (the window store's
                # temp/memmap directories, the staging worker); the
                # Trainable surface documents stop() as idempotent.
                algo.stop()
            if failed_error is not None:
                summary["status"] = "ERROR"
                summary["error"] = failed_error
            if resumed_from is not None:
                summary["resumed"] = f"from round {resumed_from}"
            if i in lane_failed:
                summary["lane_fallback"] = lane_failed[i]
            if verbose:
                print(f"   -> {summary}", flush=True)
            summaries.append(summary)
    return summaries


def _jsonable(obj):

    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    return obj

"""Observability layer (ISSUE 12): span tracing, flight recorder,
anomaly watchdog.

Four layers of coverage:

1. **Unit** — span tree nesting + the rows' ``timers`` aggregation
   contract, Chrome-trace export/validation, watchdog rules (schema
   gate, NaN, spike, ceiling, round-time) + warm(), flight-recorder
   ring/check/dump semantics, the offline validator CLI's three modes.
2. **Bit-identity** — per execution path (dense, streamed, packed,
   wire): arming tracing + watchdog + flight recorder changes NOTHING
   in the emitted rows but ``timers``/``watchdog_events`` (the device
   program is untouched; ``jax.named_scope`` is metadata only).
3. **Postmortem** — a chaos run with injected NaN lane corruption dumps
   ``flightrec.json``, and ``tools/replay_round.py`` reproduces the
   recorded round's digest bit-identically from (config, seed, tick).
4. **Resilience** — kill-and-resume under an armed watchdog keeps the
   no-duplicate/no-gap row contract and replays the trajectory
   identically; the preemption itself leaves a flight-recorder dump.
"""

import dataclasses
import json
import math
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
import sys  # noqa: E402

sys.path.insert(0, str(REPO))

from blades_tpu.obs.flightrec import (  # noqa: E402
    FlightRecorder,
    validate_flightrec,
)
import blades_tpu.obs.trace as trace_mod  # noqa: E402
from blades_tpu.obs.trace import (  # noqa: E402
    Tracer,
    validate_chrome_trace,
)
from blades_tpu.obs.watchdog import (  # noqa: E402
    Watchdog,
    WatchdogRule,
    default_rules,
)
from blades_tpu.tune import run_experiments  # noqa: E402
from blades_tpu.tune.sweep import verify_result_rounds  # noqa: E402


# ---------------------------------------------------------------------------
# span layer
# ---------------------------------------------------------------------------


def test_tracer_summary_keeps_timers_contract():
    """An un-armed tracer is the rows' phase timer: its span() context
    manager aggregates into summary() and mean()."""
    t = Tracer()
    fake = iter(range(100))
    t._clock = lambda: next(fake)
    with t.span("round"):
        with t.span("training_step"):
            pass
    with t.span("round"):
        pass
    s = t.summary()
    assert set(s) == {"round", "training_step"}
    assert s["round"]["count"] == 2
    assert s["round"]["total_s"] == (3 - 0) + (5 - 4)
    assert t.mean("training_step") == 1.0
    # Un-armed: no tree retained.
    assert t._roots == [] and t.record is False


def test_tracer_records_nested_tree_and_attrs():
    tr = Tracer(record=True)
    root = tr.start("trial", trial="t0")
    with tr.span("round", step=1, extra=7) as sp:   # attrs at the open
        with tr.span("training_step"):
            pass
    tr.stamp_latest("round", {"plan_id": "p"})
    tr.stamp_latest_of(("round", "compile"), {"hbm_passes": 2})
    tr.finish(root)
    assert [c.name for c in tr._roots[0].children] == ["round"]
    assert tr._roots[0].children[0].children[0].name == "training_step"
    assert sp.attrs["extra"] == 7
    assert sp.attrs["plan_id"] == "p" and sp.attrs["hbm_passes"] == 2
    assert sp.step == 1
    assert root.duration >= sp.duration >= 0


def test_chrome_export_is_valid_and_atomic(tmp_path):
    tr = Tracer(record=True)
    with tr.span("trial", trial="t"):
        with tr.span("round", step=3, plan_id="x"):
            pass
    out = tmp_path / "t.trace.json"
    tr.export(out)
    assert not (tmp_path / "t.trace.json.tmp").exists()
    n, errors = validate_chrome_trace(out)
    assert n == 2 and errors == []
    doc = json.loads(out.read_text())
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans["round"]["args"] == {"plan_id": "x", "step": 3}
    assert doc["metadata"]["spans_recorded"] == 2


def test_chrome_validator_tolerates_torn_file(tmp_path):
    torn = tmp_path / "torn.trace.json"
    torn.write_text('{"traceEvents": [{"name": "x", "ph": "X", "ts"')
    n, errors = validate_chrome_trace(torn)
    assert n == 0 and len(errors) == 1
    assert "unreadable" in errors[0]


def test_shims_and_unused_wrappers_are_gone():
    """The PR-1 shim modules, the wrappers nothing called and the aliases
    of ``Tracer`` and its ``span`` went; the span layer keeps the names
    that have callers."""
    import importlib

    import blades_tpu.obs.trace as T
    import blades_tpu.utils as U

    for name in ("blades_tpu.utils.timers", "blades_tpu.utils.profiling"):
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module(name)
    assert not hasattr(U, "Timers")
    assert not hasattr(T, "annotate") and not hasattr(T, "xla_dump_flags")
    assert not hasattr(T, "Timers")
    assert not hasattr(Tracer, "time") and not hasattr(Tracer, "annotate")
    assert callable(T.trace) and callable(T.span) and T.Tracer is Tracer


def test_unarmed_tracer_retains_nothing_and_still_aggregates():
    t = Tracer()
    for i in range(1000):
        with t.span("round", step=i, plan_id="p") as sp:
            with trace_mod.span("phase"):
                pass
    assert t._roots == [] and t._stack == [] and t._latest == {}
    assert t._recorded == 0 and sp._ann is None and "_ann" not in sp.attrs
    s = t.summary()
    assert s["round"]["count"] == 1000 and s["phase"]["count"] == 1000
    assert s["round"]["total_s"] >= s["phase"]["total_s"] > 0
    assert trace_mod._open_tracers() == []


def test_module_span_nests_under_the_open_tracer_and_is_inert_alone():
    # No tracer open on this thread: the profiler annotation alone.
    with trace_mod.span("blades/prepare") as sp:
        assert sp is None
    outer, inner = Tracer(record=True), Tracer(record=True)
    with outer.span("trial"):
        with trace_mod.span("blades/a"):
            pass
        with inner.span("blades/round", step=3):
            # Innermost open tracer wins; an armed one nests the span.
            with trace_mod.span("blades/block") as blk:
                assert blk.name == "blades/block"
        with trace_mod.span("blades/b"):
            pass
    assert set(outer.summary()) == {"trial", "blades/a", "blades/b"}
    assert set(inner.summary()) == {"blades/round", "blades/block"}
    assert [c.name for c in inner._roots[0].children] == ["blades/block"]
    assert [c.name for c in outer._roots[0].children] == ["blades/a",
                                                          "blades/b"]
    # A span of another thread's tracer is not this thread's.
    import threading

    seen = []
    with outer.span("trial"):
        th = threading.Thread(
            target=lambda: seen.append(list(trace_mod._open_tracers())))
        th.start()
        th.join(timeout=10)
    assert seen == [[]]
    assert trace_mod._open_tracers() == []


def test_adopted_tracer_keeps_what_the_instance_timed_before():
    mine, theirs = Tracer(), Tracer(record=True)
    with mine.span("blades/setup"):
        pass
    with theirs.span("trial"):
        pass
    theirs.absorb(mine)
    s = theirs.summary()
    assert s["blades/setup"] == mine.summary()["blades/setup"]
    assert s["trial"]["count"] == 1


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


def _row(i, **kw):
    base = {"training_iteration": i, "train_loss": 1.0, "agg_norm": 0.5,
            "update_norm_mean": 1.0 + 0.01 * i}
    base.update(kw)
    return base


def test_watchdog_rules_are_schema_driven():
    with pytest.raises(ValueError, match="not registered"):
        WatchdogRule(name="bogus", kind="ceiling", field="no_such_field")
    with pytest.raises(ValueError, match="kind"):
        WatchdogRule(name="bogus", kind="wat", field="agg_norm")
    # Every default rule names a registered field by construction.
    assert {r.name for r in default_rules()} == {
        "nan_aggregate", "nan_loss", "update_norm_spike",
        "fpr_collapse", "round_time_regression",
        "staleness_runaway", "ingest_collapse", "ingest_stall",
        "reputation_collapse", "flagger_churn"}


def test_watchdog_nonfinite_spike_and_ceiling():
    wd = Watchdog()
    for i in range(1, 7):
        assert wd.observe(_row(i)) == []
    ev = wd.observe(_row(7, update_norm_mean=1e4))
    assert [e.rule for e in ev] == ["update_norm_spike"]
    assert ev[0].value == 1e4 and ev[0].limit < 1e4
    ev = wd.observe(_row(8, agg_norm=float("nan"),
                         train_loss=float("inf")))
    assert {e.rule for e in ev} == {"nan_aggregate", "nan_loss"}
    ev = wd.observe(_row(9, byz_fpr=0.9))
    assert [e.rule for e in ev] == ["fpr_collapse"]
    assert len(wd.events) == 4


def test_watchdog_round_time_regression_from_row_timers():
    wd = Watchdog([WatchdogRule(name="rt", kind="round_time_regression",
                                field="timers", window=4, min_points=3,
                                factor=3.0)])
    total = 0.0
    for i in range(1, 6):
        total += 0.1
        assert wd.observe(_row(i, timers={"training_step":
                                          {"total_s": total}})) == []
    total += 10.0  # a 100x round
    ev = wd.observe(_row(6, timers={"training_step": {"total_s": total}}))
    assert [e.rule for e in ev] == ["rt"]


def test_watchdog_warm_matches_straight_through():
    """Kill-and-resume contract: warming from on-disk rows reproduces
    the rolling windows a straight-through run would hold."""
    rows = [_row(i) for i in range(1, 7)]
    straight = Watchdog()
    for r in rows:
        straight.observe(r)
    resumed = Watchdog()
    resumed.observe(rows[0])  # partial progress before the "kill"
    resumed.warm(rows)        # restore replays the stream
    spike = _row(7, update_norm_mean=1e4)
    assert ([e.rule for e in straight.observe(spike)]
            == [e.rule for e in resumed.observe(spike)]
            == ["update_norm_spike"])


def test_watchdog_nan_never_poisons_spike_window():
    wd = Watchdog([WatchdogRule(name="s", kind="spike",
                                field="update_norm_mean", window=4,
                                min_points=2, factor=10.0)])
    wd.observe(_row(1))
    wd.observe(_row(2, update_norm_mean=float("nan")))
    wd.observe(_row(3))
    assert all(math.isfinite(v) for v in wd._windows["s"])


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flightrec_ring_bound_check_and_dump(tmp_path):
    path = tmp_path / "flightrec.json"
    fr = FlightRecorder(path, capacity=3, experiment="e", trial="t",
                        algo="FEDAVG", config={"seed": 9}, max_rounds=50)
    for i in range(1, 11):
        fr.record(_row(i, timers={"training_step": {"total_s": 1.0}}))
    assert fr.check(_row(11)) is None
    trig = fr.check(_row(11, agg_norm=float("inf")))
    assert trig == {"kind": "nonfinite", "field": "agg_norm",
                    "value": float("inf"), "round": 11}
    assert fr.dump(trig) == str(path)
    assert fr.dump(trig) is None  # rate-limited per kind
    assert fr.dump({"kind": "exception", "error": "x"}) == str(path)
    assert not (tmp_path / "flightrec.json.tmp").exists()
    num, errors = validate_flightrec(path)
    assert errors == [] and num == 3  # ring bound held
    doc = json.loads(path.read_text())
    assert [r["training_iteration"] for r in doc["rounds"]] == [8, 9, 10]
    assert doc["rng"] == {"seed": 9, "tick": 10,
                          "discipline": doc["rng"]["discipline"]}
    assert "timers" not in doc["rounds"][0]  # wall-clock stays out


def test_flightrec_rewind_rebuilds_ring_and_rearms_dump(tmp_path):
    """Checkpoint-restore contract: rewinding to the truncated rows
    leaves no stale ticks from the failed attempt (ascending order
    holds, so replay accepts the post-resume dump) and re-arms the
    per-kind dump rate limit."""
    path = tmp_path / "flightrec.json"
    fr = FlightRecorder(path, capacity=8, algo="FEDAVG",
                        config={"seed": 1})
    for i in range(1, 6):
        fr.record(_row(i))
    assert fr.dump({"kind": "exception", "error": "boom"}) is not None
    # Restore at round 3: rows 4-5 were truncated from disk.
    fr.rewind([_row(i) for i in range(1, 4)])
    for i in range(4, 6):  # re-executed rounds
        fr.record(_row(i))
    trig = {"kind": "nonfinite", "field": "agg_norm",
            "value": float("nan"), "round": 5}
    assert fr.dump(trig) is not None  # rate limit re-armed
    num, errors = validate_flightrec(path)
    assert errors == []
    doc = json.loads(path.read_text())
    assert [r["training_iteration"] for r in doc["rounds"]] \
        == [1, 2, 3, 4, 5]


def test_watchdog_warm_rebuilds_event_log_from_stamps():
    """summary["watchdog"] parity across kill-and-resume: warm()
    restores the event log from the rows' durable watchdog_events
    stamps instead of re-firing rules (which would double-count)."""
    stamped = _row(3, watchdog_events=[
        {"rule": "fpr_collapse", "kind": "ceiling", "field": "byz_fpr",
         "round": 3, "value": 0.9, "limit": 0.5, "message": "m"}])
    wd = Watchdog()
    wd.observe(_row(1, byz_fpr=0.9))  # pre-kill firing, then restore
    wd.warm([_row(1), _row(2), stamped])
    assert [e.rule for e in wd.events] == ["fpr_collapse"]
    assert wd.events[0].round == 3 and wd.events[0].value == 0.9


def test_chrome_export_keeps_children_of_open_spans(tmp_path):
    """A mid-run export (or a forgotten finish() on an explicit start()
    span) must still salvage the finished subtree."""
    tr = Tracer(record=True)
    tr.start("trial")  # never finished
    with tr.span("round", step=1):
        with tr.span("training_step"):
            pass
    out = tmp_path / "open.trace.json"
    tr.export(out)
    doc = json.loads(out.read_text())
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert "trial" not in names  # still open: no event of its own
    assert "round" in names and "training_step" in names


def test_validate_flightrec_reports_torn_and_malformed(tmp_path):
    torn = tmp_path / "flightrec.json"
    torn.write_text('{"version": 1, "rounds": [{')
    num, errors = validate_flightrec(torn)
    assert num == 0 and "unreadable" in errors[0]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99, "rounds": [{"x": 1}]}))
    num, errors = validate_flightrec(bad)
    assert any("version" in e for e in errors)
    assert any("training_iteration" in e for e in errors)


def test_validate_metrics_cli_three_modes(tmp_path, capsys):
    from tools.validate_metrics import main as vm

    # metrics mode: valid line + torn tail is reported, not raised.
    m = tmp_path / "metrics.jsonl"
    m.write_text(json.dumps({"experiment": "e", "trial": "t",
                             "training_iteration": 1}) + "\n"
                 + '{"experiment": "e", "tr')
    assert vm([str(m)]) == 1
    out = capsys.readouterr().out
    assert "1 valid record(s), 1 error(s)" in out
    # flightrec mode.
    fr = FlightRecorder(tmp_path / "fr.json", capacity=2, algo="FEDAVG")
    fr.record(_row(1))
    fr.dump({"kind": "exception", "error": "boom"})
    assert vm(["--flightrec", str(tmp_path / "fr.json")]) == 0
    # trace mode + orphaned .tmp note (torn-write contract).
    tr = Tracer(record=True)
    with tr.span("trial"):
        pass
    tr.export(tmp_path / "t.trace.json")
    (tmp_path / "t.trace.json.tmp").write_text("{")
    assert vm(["--trace", str(tmp_path / "t.trace.json")]) == 0
    assert "orphaned" in capsys.readouterr().out
    assert vm(["--trace", str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------------------------------
# driver integration
# ---------------------------------------------------------------------------

_BASE_CFG = {
    "dataset_config": {"type": "mnist", "num_clients": 4, "train_bs": 8},
    "global_model": "mlp",
    "evaluation_interval": 2,
}


def _experiments(name, rounds=2, **cfg_over):
    cfg = {**_BASE_CFG, **cfg_over}
    return {name: {"run": "FEDAVG", "stop": {"training_iteration": rounds},
                   "config": cfg}}


def _rows(tdir) -> list:
    return [json.loads(line) for line in
            (Path(tdir) / "metrics.jsonl").read_text().splitlines()]


def _strip(rows, drop=("timers", "watchdog_events",
                       # Process-history-dependent (the AOT executable
                       # cache is process-wide, so a second identical
                       # run hits it) — pre-existing behavior, not an
                       # observability effect.
                       "compile_cache_hits", "compile_cache_misses")):
    return [{k: v for k, v in r.items() if k not in drop} for r in rows]


def test_sweep_trace_dir_exports_per_trial_tree(tmp_path):
    trace_dir = tmp_path / "traces"
    [s] = run_experiments(
        _experiments("traced", rounds=3), storage_path=str(tmp_path),
        verbose=0, cost_analysis=False,
        trace_dir=str(trace_dir), watchdog=True)
    out = trace_dir / "traced_00000.trace.json"
    assert out.exists()
    n, errors = validate_chrome_trace(out)
    assert errors == [] and n >= 5  # trial + 3 dispatches + phases
    doc = json.loads(out.read_text())
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"trial", "compile", "round", "training_step",
            "evaluate"} <= names
    # Round provenance rides the dispatch spans' args.
    stamped = [e for e in spans if e["name"] in ("round", "compile")
               and "training_iteration" in e["args"]]
    assert stamped, "no dispatch span carries row provenance"
    # The phase spans nest INSIDE their dispatch span's interval (the
    # export emits depth-first, so the first training_step belongs to
    # the first dispatch — the "compile" span).
    comp = next(e for e in spans if e["name"] == "compile")
    tstep = next(e for e in spans if e["name"] == "training_step")
    assert comp["ts"] <= tstep["ts"]
    assert tstep["ts"] + tstep["dur"] <= comp["ts"] + comp["dur"] + 1e-3
    # Summary keeps the sweep-phase contract.
    assert s["timers"]["compile"]["count"] == 1
    assert s["timers"]["round"]["count"] == 2


_IDENTITY_PATHS = {
    "dense": {},
    "streamed": {"execution": "streamed",
                 "server_config": {"aggregator": {"type": "Median"},
                                   "lr": 1.0}},
    "packed": {"client_packing": 2},
    "wire": {"codec_config": {"type": "quant", "bits": 8},
             "agg_domain": "wire"},
}


@pytest.mark.parametrize("path_name", ["dense"])
def test_observability_off_rows_bit_identical(tmp_path, path_name):
    """The acceptance gate: arming tracer + watchdog + flight recorder
    changes NOTHING in the emitted rows except timers/watchdog_events —
    the device program and every metric value are untouched.  (The
    headline dense path rides tier-1; streamed/packed/wire are the slow
    zoo below, per the budget convention.)"""
    _assert_identity(tmp_path, path_name)


@pytest.mark.slow  # three extra compile-heavy paths (~3-10 s each; budget convention)
@pytest.mark.parametrize("path_name", ["streamed", "packed", "wire"])
def test_observability_off_rows_bit_identical_zoo(tmp_path, path_name):
    _assert_identity(tmp_path, path_name)


def _assert_identity(tmp_path, path_name):
    over = _IDENTITY_PATHS[path_name]
    kw = dict(verbose=0, cost_analysis=False, lanes=False)
    exps = _experiments("ab", rounds=3, **over)
    run_experiments(exps, storage_path=str(tmp_path / "off"),
                    flightrec_rounds=0, **kw)
    run_experiments(exps, storage_path=str(tmp_path / "on"),
                    trace_dir=str(tmp_path / "traces"), watchdog=True,
                    flightrec_rounds=8, **kw)
    off = _rows(tmp_path / "off" / "ab" / "ab_00000")
    on = _rows(tmp_path / "on" / "ab" / "ab_00000")
    off_cmp = [{k: v for k, v in r.items() if k != "trial"}
               for r in _strip(off)]
    on_cmp = [{k: v for k, v in r.items() if k != "trial"}
              for r in _strip(on)]
    assert off_cmp == on_cmp, f"{path_name}: rows diverged"


# ---------------------------------------------------------------------------
# the streamed round's own phases (ISSUE 25)
# ---------------------------------------------------------------------------

_STREAMED_CFG = {
    "dataset_config": {"type": "mnist", "num_clients": 8, "train_bs": 4},
    "global_model": "mlp", "evaluation_interval": 0,
    "num_malicious_clients": 2, "adversary_config": {"type": "ALIE"},
    "execution": "streamed", "client_block": 2,
    "server_config": {"lr": 1.0, "aggregator": {"type": "Median"}},
}
_ROUND_PHASES = ("blades/prepare", "blades/block", "blades/finish",
                 "blades/fetch", "blades/row")


def _streamed_algo():
    from blades_tpu.algorithms import get_algorithm_class

    _, config = get_algorithm_class("FEDAVG", return_config=True)
    config.update_from_dict(json.loads(json.dumps(_STREAMED_CFG)))
    return config.build()


@pytest.fixture(scope="module")
def streamed_two_rounds(tmp_path_factory):
    """Two streamed rounds (8 clients, blocks of 2, the first block
    elided), twice: un-armed under a jax.profiler capture, and with an
    armed tracer adopted."""
    import jax

    plain = _streamed_algo()
    trace_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        plain_rows = [plain.train() for _ in range(2)]
    finally:
        jax.profiler.stop_trace()
    armed = _streamed_algo()
    tracer = Tracer(record=True)
    armed.adopt_tracer(tracer)
    armed_rows = [armed.train() for _ in range(2)]
    return {"plain": plain, "plain_rows": plain_rows, "tracer": tracer,
            "armed_rows": armed_rows, "trace_dir": trace_dir}


def test_streamed_round_counts_its_phases_on_every_row(streamed_two_rounds):
    rows = streamed_two_rounds["plain_rows"]
    assert [r["elided_lanes"] for r in rows] == [2, 2]
    want = {"blades/prepare": 1, "blades/block": 8 // 2 - 1,
            "blades/finish": 1, "blades/fetch": 1, "blades/row": 1,
            "blades/round": 1, "training_step": 1}
    for i, row in enumerate(rows, start=1):
        timers = row["timers"]
        # Taken after the round's spans close: row i holds i whole rounds.
        assert {k: timers[k]["count"] for k in want} == \
            {k: i * c for k, c in want.items()}
        assert timers["blades/setup"]["count"] == 1
        assert sum(timers[k]["total_s"] for k in _ROUND_PHASES) \
            <= timers["blades/round"]["total_s"]
        assert timers["blades/prepare"]["total_s"] \
            + timers["blades/block"]["total_s"] \
            + timers["blades/finish"]["total_s"] \
            + timers["blades/fetch"]["total_s"] \
            <= timers["training_step"]["total_s"]
    # A row's timers minus the previous row's is that round's phase times.
    for k in _ROUND_PHASES + ("blades/round",):
        assert rows[1]["timers"][k]["total_s"] > rows[0]["timers"][k]["total_s"]
    setup = rows[0]["timers"]
    assert {"blades/setup/data", "blades/setup/model",
            "blades/setup/round"} <= set(setup)
    assert max(setup[k]["total_s"] for k in setup
               if k.startswith("blades/setup/")) \
        <= setup["blades/setup"]["total_s"]
    # Un-armed: nothing but the aggregates was kept.
    t = streamed_two_rounds["plain"].timers
    assert t._roots == [] and t._latest == {} and t._recorded == 0


def test_streamed_rows_equal_armed_and_unarmed_but_for_timers(
        streamed_two_rounds):
    plain, armed = (streamed_two_rounds[k]
                    for k in ("plain_rows", "armed_rows"))
    assert _strip(plain) == _strip(armed)
    assert all(math.isfinite(r["train_loss"]) for r in plain)
    # Armed, the same spans are a tree: each phase inside its round.
    roots = streamed_two_rounds["tracer"]._roots
    rounds = [r for r in roots if r.name == "blades/round"]
    assert [r.step for r in rounds] == [0, 1]
    for r in rounds:
        (step,) = [c for c in r.children if c.name == "training_step"]
        assert [c.name for c in step.children] == \
            ["blades/prepare"] + ["blades/block"] * 3 + \
            ["blades/finish", "blades/fetch"]
        assert [c.name for c in r.children] == ["training_step",
                                                "blades/row"]
    # What the instance timed before it adopted the tracer came along.
    assert armed[0]["timers"]["blades/setup"]["count"] == 1


def test_profiler_capture_holds_the_phases_on_the_driving_thread(
        streamed_two_rounds):
    """The un-armed run's spans reached the profiler's trace: on one
    thread line, each inside its ``blades/round``."""
    import glob

    import jax

    (pb,) = glob.glob(str(streamed_two_rounds["trace_dir"]
                          / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(pb)
    lines = []
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events
                   if e.name.startswith("blades/")
                   or e.name == "training_step"]
            if evs:
                lines.append(evs)
    (evs,) = lines                      # one thread line holds them all
    rounds = [e for e in evs if e[0] == "blades/round"]
    assert len(rounds) == 2
    counts = {}
    for name, s, e in evs:
        if name == "blades/round":
            continue
        counts[name] = counts.get(name, 0) + 1
        assert sum(r[1] <= s and e <= r[2] for r in rounds) == 1, name
    assert counts == {"training_step": 2, "blades/prepare": 2,
                      "blades/block": 6, "blades/finish": 2,
                      "blades/fetch": 2, "blades/row": 2}


def _scopes(lowered) -> set:
    import re

    return set(re.findall(r"blades/[a-z]+", lowered.as_text(debug_info=True)))


def test_streamed_programs_carry_the_device_scopes(streamed_two_rounds,
                                                   monkeypatch):
    """Trace-time metadata: the block wears sample/step/store, the
    chunked finish forge/aggregate, and the compact finish, whose forge
    and aggregate are one Mosaic call, aggregate (plus forge for the
    adaptive forge's uniforms)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from blades_tpu.adversaries import get_adversary
    from blades_tpu.ops import pallas_round
    from blades_tpu.parallel.streamed import block_plan, streamed_step

    algo = streamed_two_rounds["plain"]
    step, st, n, d = algo._step, algo.state, 8, algo._num_params
    x, y, ln = algo._train_arrays
    key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, n)
    buf = jnp.zeros((n, d), jnp.bfloat16)
    zeros = jnp.zeros((n,), jnp.float32)
    plan = block_plan(n, 2, 2, jnp.bfloat16, compact=False)
    assert _scopes(step.train_block.lower(
        buf, st.client_opt, st.server.params, x, y, ln, algo.malicious,
        keys, keys, np.uint32(0), plan=plan)) == \
        {"blades/sample", "blades/step", "blades/store"}
    assert _scopes(step.finish.lower(
        st.server, buf, algo.malicious, zeros, zeros, key, key)) == \
        {"blades/forge", "blades/aggregate"}

    monkeypatch.setattr(
        pallas_round, "fused_finish_compact",
        functools.partial(pallas_round.fused_finish_compact, interpret=True))
    for adv, want in (("ALIE", {"blades/aggregate"}),
                      ("Adaptive", {"blades/forge", "blades/aggregate"})):
        fr = dataclasses.replace(
            algo.fed_round,
            adversary=get_adversary(adv, num_clients=n, num_byzantine=2))
        compact = streamed_step(fr, client_block=2, malicious_prefix=2)
        assert _scopes(compact.finish_fused_compact.lower(
            st.server, jnp.zeros((8, 128 * -(-d // 128)), jnp.bfloat16),
            algo.malicious, zeros, key, nb_real=6)) == want


def test_chaos_nan_dump_replays_bit_identically(tmp_path):
    """Satellite acceptance: a chaos run with injected NaN lane
    corruption dumps flightrec.json, and tools/replay_round.py
    reproduces the recorded round's digest bit-identically from
    (config, seed, tick)."""
    from tools.replay_round import main as replay_main

    exps = _experiments(
        "chaos", rounds=2, evaluation_interval=0,
        fault_config={"corrupt_rate": 0.9, "corrupt_mode": "nan",
                      "seed": 7})
    [s] = run_experiments(exps, storage_path=str(tmp_path), verbose=0,
                          cost_analysis=False, watchdog=True)
    dump = tmp_path / "chaos" / "chaos_00000" / "flightrec.json"
    assert dump.exists()
    doc = json.loads(dump.read_text())
    assert doc["trigger"]["kind"] == "nonfinite"
    assert doc["trigger"]["field"] == "agg_norm"
    assert math.isnan(doc["rounds"][-1]["agg_norm"])
    assert s["flightrec"]["dumps"] >= 1
    assert "nan_aggregate" in s["watchdog"]["rules"]
    # The NaN round must be stamped into the rows as watchdog_events.
    rows = _rows(tmp_path / "chaos" / "chaos_00000")
    assert any("watchdog_events" in r for r in rows)
    ev = next(r["watchdog_events"] for r in rows
              if "watchdog_events" in r)
    assert any(e["rule"] == "nan_aggregate" for e in ev)
    # Replay: bit-identical digest (NaN == NaN) from (config, seed, tick).
    assert replay_main([str(dump), "--quiet"]) == 0
    # A tick outside the recorded ring fails loudly, not silently.
    assert replay_main([str(dump), "--tick", "99", "--quiet"]) == 1


@pytest.mark.slow  # two 6-round sweeps + a retry rebuild (~4.5 s; budget convention)
def test_kill_and_resume_with_armed_watchdog(tmp_path):
    """Acceptance: a kill-and-resume under an armed watchdog replays
    identically — no-duplicate/no-gap rows equal to the un-preempted
    run's, the preemption leaves a flight-recorder dump, and the
    watchdog windows are rebuilt from disk on restore."""
    exps = _experiments("wd", rounds=6, evaluation_interval=0)
    run_experiments(exps, storage_path=str(tmp_path / "ref"), verbose=0,
                    cost_analysis=False, watchdog=True)
    [s] = run_experiments(
        exps, storage_path=str(tmp_path / "preempted"), verbose=0,
        cost_analysis=False, watchdog=True,
        checkpoint_freq=2, max_failures=1, preempt_after=3)
    tdir = tmp_path / "preempted" / "wd" / "wd_00000"
    assert verify_result_rounds(tdir / "result.json") == list(range(1, 7))
    assert s["rounds"] == 6 and "status" not in s
    # The preemption dumped the ring before the retry.
    doc = json.loads((tdir / "flightrec.json").read_text())
    assert doc["trigger"]["kind"] == "preemption"
    # Identical trajectory vs the straight-through reference.
    ref = _rows(tmp_path / "ref" / "wd" / "wd_00000")
    got = _rows(tdir)
    assert (_strip([{k: v for k, v in r.items() if k != "trial"}
                    for r in ref])
            == _strip([{k: v for k, v in r.items() if k != "trial"}
                       for r in got]))


@pytest.mark.slow  # per-seed vmapped lane compile (~7 s; budget convention)
def test_lane_group_traces_watchdog_and_rows(tmp_path):
    """Laned trials get the same observability surface: one exported
    trace per group, per-trial watchdog/flightrec over the post-hoc
    rows, schema-valid streams."""
    from blades_tpu.obs.schema import main as schema_main

    cfg = {**_BASE_CFG,
           "dataset_config": {**_BASE_CFG["dataset_config"],
                              "seed": {"grid_search": [0, 1]}}}
    exps = {"laned": {"run": "FEDAVG",
                      "stop": {"training_iteration": 2}, "config": cfg}}
    # A stale dump from a "previous run" in the same storage path must
    # not survive next to this run's fresh artifacts.
    stale = tmp_path / "laned" / "laned_00000" / "flightrec.json"
    stale.parent.mkdir(parents=True)
    stale.write_text("{}")
    summaries = run_experiments(
        exps, storage_path=str(tmp_path), verbose=0, cost_analysis=False,
        trace_dir=str(tmp_path / "traces"), watchdog=True)
    assert not stale.exists()
    assert len(summaries) == 2
    assert all(s.get("lanes") == 2 for s in summaries)
    traces = list((tmp_path / "traces").glob("laned_lanes_*.trace.json"))
    assert len(traces) == 1
    n, errors = validate_chrome_trace(traces[0])
    assert errors == []
    doc = json.loads(traces[0].read_text())
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert "lane_group" in names and "compile" in names \
        and "round" in names and "fetch" in names
    for s in summaries:
        assert schema_main([str(Path(s["dir"]) / "metrics.jsonl")]) == 0


def test_run_experiments_defaults_write_no_observability_artifacts(
        tmp_path):
    """Default sweep (no trace_dir, no watchdog, healthy run): no trace
    files, no flightrec.json, no watchdog_events — the pre-ISSUE-12
    on-disk surface exactly."""
    run_experiments(_experiments("plain", rounds=2,
                                 evaluation_interval=0),
                    storage_path=str(tmp_path), verbose=0,
                    cost_analysis=False)
    tdir = tmp_path / "plain" / "plain_00000"
    assert not (tdir / "flightrec.json").exists()
    assert not list(tmp_path.rglob("*.trace.json"))
    assert all("watchdog_events" not in r for r in _rows(tdir))

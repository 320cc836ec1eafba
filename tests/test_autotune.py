"""Execution autotuner tests (blades_tpu/perf/autotune.py, ISSUE 10):

- plan-space enumeration: baseline-first ordering, tier partition (the
  reassociating tier absent without the opt-in), dedupe, truncation;
- selection: deterministic heuristic fallback off-TPU, measured winner
  under an injected fake clock, tie-break by heuristic rank;
- plan cache: atomic-write durability (orphaned ``.tmp`` cleanup),
  corrupt / stale-version / key-mismatch tolerance (miss => re-tune,
  never a crash), cross-process hits (the module is stdlib-only and
  loaded standalone in a subprocess), ``tools/show_plan.py``;
- driver integration: default-tier tuned runs are BIT-identical to the
  untuned path per aggregator (the acceptance criterion — pinned
  non-baseline default-tier plans, not just the trivial heuristic
  winner), provenance stamped schema-valid into round rows and sweep
  summaries, and kill-and-resume replays the checkpoint-recorded plan
  even when the on-disk cache has a different winner (no silent
  re-tune drift mid-trajectory).

Compile-heavy cases (per-aggregator zoo, streamed builds) are
slow-marked per the tier-1 budget convention (tools/check_tier1_budget).
"""

import json
import pickle
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from blades_tpu.algorithms import FedavgConfig
from blades_tpu.perf.autotune import (
    D_CHUNK_LADDER,
    PLAN_CACHE_VERSION,
    Plan,
    PlanCache,
    apply_plan,
    cache_key,
    enumerate_plans,
    select_plan,
    timed_measure_fn,
)

AUTOTUNE_PY = (Path(__file__).resolve().parents[1]
               / "blades_tpu" / "perf" / "autotune.py")


def tiny_config(**overrides):
    cfg = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=6, seed=3)
        .training(global_model="mlp", server_lr=1.0, train_batch_size=8,
                  aggregator={"type": "Mean"})
        .client(lr=0.1)
        .evaluation(evaluation_interval=0)
    )
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _params(algo):
    return [np.asarray(p) for p in jax.tree.leaves(algo.state.server.params)]


def _run_rounds(cfg, rounds=3):
    algo = cfg.build()
    rows = [algo.train() for _ in range(rounds)]
    return algo, rows


# ---------------------------------------------------------------------------
# Plan / enumeration
# ---------------------------------------------------------------------------


def test_default_chunk_constants_agree():
    """autotune.py is stdlib-only by design (the cross-process cache
    test loads it standalone), so it repeats the canonical chunk
    literal instead of importing it — this pins the agreement."""
    from blades_tpu.parallel.streamed import DEFAULT_D_CHUNK

    assert Plan().d_chunk == DEFAULT_D_CHUNK
    assert FedavgConfig().d_chunk == DEFAULT_D_CHUNK
    assert DEFAULT_D_CHUNK in D_CHUNK_LADDER


def test_plan_validates_fields():
    with pytest.raises(ValueError, match="execution"):
        Plan(execution="warp")
    with pytest.raises(ValueError, match="mxu_finish"):
        Plan(mxu_finish="sometimes")
    with pytest.raises(ValueError, match="tier"):
        Plan(tier="experimental")
    with pytest.raises(ValueError, match="d_chunk"):
        Plan(d_chunk=512)


def test_plan_dict_roundtrip_and_unknown_fields():
    p = Plan(execution="streamed", d_chunk=1 << 16, mxu_finish="counts")
    assert Plan.from_dict(p.as_dict()) == p
    # A plan dict written by a FUTURE layout must read as stale, never be
    # half-applied.
    with pytest.raises(ValueError, match="unknown plan fields"):
        Plan.from_dict({**p.as_dict(), "warp_factor": 9})
    with pytest.raises(ValueError, match="dict"):
        Plan.from_dict("dense")


@pytest.mark.parametrize("where", ["from_dict", "config_pin"])
def test_a_plan_pin_that_names_a_dispatch_window_is_refused(where):
    """A round is one dispatch.  A pin written when plans carried a
    window (a checkpoint's plan, an operator's ``tuned_plan``) is input
    from outside the program: a window of 1 is what every round does
    and reads as the same plan; any other is refused by name."""
    old_one = {**Plan().as_dict(), "rounds_per_dispatch": 1}
    old_four = {**Plan().as_dict(), "rounds_per_dispatch": 4}
    if where == "from_dict":
        assert Plan.from_dict(old_one) == Plan()
        with pytest.raises(ValueError, match="rounds_per_dispatch=4"):
            Plan.from_dict(old_four)
    else:
        cfg = tiny_config()
        cfg.resources(autotune=True, tuned_plan=old_one)
        cfg.validate()
        cfg = tiny_config()
        cfg.resources(autotune=True, tuned_plan=old_four)
        with pytest.raises(ValueError, match="dispatch windows were removed"):
            cfg.validate()


def test_enumerate_baseline_first_and_default_tier_only():
    space = enumerate_plans(
        executions=["dense"], d_chunks=[1 << 17],
        prefetch_options=[False, True],
    )
    assert space.baseline == Plan()  # today's heuristic resolution
    assert [p.prefetch for p in space.candidates] == [False, True]
    assert all(p.tier == "default" for p in space.candidates)
    assert space.truncated == 0


def test_enumerate_reassociating_tier_requires_opt_in():
    kw = dict(
        executions=["streamed", "dense"],  # baseline streamed
        d_chunks=[1 << 17, 1 << 16],
        mxu_modes=["", "counts", "all"],
        pack_factors=[1, 2],
    )
    default = enumerate_plans(**kw)
    # Without the opt-in: streamed-only (the dense switch reassociates),
    # no "all" finish (stats reassociate), no packing.
    assert all(p.execution == "streamed" for p in default.candidates)
    assert all(p.mxu_finish in ("", "counts") for p in default.candidates)
    assert default.baseline.d_chunk == 1 << 17
    both = enumerate_plans(allow_reassociating=True, **kw)
    tiers = {p.tier for p in both.candidates}
    assert tiers == {"default", "reassociating"}
    assert any(p.execution == "dense" for p in both.candidates)
    assert any(p.mxu_finish == "all" for p in both.candidates)
    # Every default-tier candidate survives the filter unchanged, in order.
    assert [p for p in both.candidates if p.tier == "default"] == \
        list(default.candidates)


def test_enumerate_dedupes_and_truncates():
    space = enumerate_plans(executions=["dense"], d_chunks=[1 << 17],
                            prefetch_options=[False, False, True])
    assert len(space.candidates) == 2  # duplicate collapsed
    tight = enumerate_plans(executions=["streamed"],
                            d_chunks=list(D_CHUNK_LADDER),
                            mxu_modes=["", "counts"],
                            max_candidates=4)
    assert len(tight.candidates) == 4
    assert tight.truncated == 2  # 3 chunks x 2 modes - 4, recorded loudly


# ---------------------------------------------------------------------------
# selection: heuristic fallback + injected-clock measured path
# ---------------------------------------------------------------------------


def test_select_heuristic_fallback_is_rank_zero():
    space = enumerate_plans(executions=["dense"], d_chunks=[1 << 17],
                            prefetch_options=[False, True])
    plan, prov = select_plan(space, measure_fn=None)
    assert plan == space.baseline
    assert prov["mode"] == "heuristic" and prov["timed"] is False
    assert [c["median_s"] for c in prov["candidates"]] == [None, None]
    assert prov["winner_id"] == plan.plan_id


def test_select_measured_picks_fastest_and_breaks_ties_by_rank():
    space = enumerate_plans(executions=["dense"], d_chunks=[1 << 17],
                            prefetch_options=[False, True])
    times = {False: 0.5, True: 0.2}
    plan, prov = select_plan(space,
                             measure_fn=lambda p: times[p.prefetch])
    assert plan.prefetch is True
    assert prov["mode"] == "measured" and prov["timed"] is True
    assert prov["candidates"][1]["median_s"] == 0.2
    # Exact tie: heuristic rank (enumeration order) wins => deterministic.
    plan, _ = select_plan(space, measure_fn=lambda p: 0.3)
    assert plan == space.baseline
    # Every measurement failing degrades to the heuristic, not a crash.
    plan, prov = select_plan(space, measure_fn=lambda p: None)
    assert plan == space.baseline and prov["mode"] == "heuristic"


def test_timed_measure_fn_injected_clock_deterministic():
    """The timed trial harness under a fake clock and a fake build:
    warmup dispatches are not timed, the median of reps is reported,
    and a candidate whose build raises is ranked out with a warning."""
    ticks = iter(range(1000))

    class FakeAlgo:
        trained = 0

        def train(self):
            FakeAlgo.trained += 1

    cfg = tiny_config()
    cfg.validate()
    measure = timed_measure_fn(
        cfg, warmup=1, reps=3,
        clock=lambda: float(next(ticks)),
        build=lambda cand: FakeAlgo(),
    )
    t = measure(Plan())
    # clock pairs (0,1), (2,3), (4,5): every timed dispatch spans one
    # tick under this clock -> median exactly 1.0, reproducibly.
    assert t == 1.0
    assert FakeAlgo.trained == 4  # 1 warmup + 3 reps

    def broken_build(cand):
        raise RuntimeError("no such kernel")

    bad = timed_measure_fn(cfg, clock=lambda: 0.0, build=broken_build)
    with pytest.warns(RuntimeWarning, match="no such kernel"):
        assert bad(Plan()) is None


def test_apply_plan_materialises_knobs():
    cfg = tiny_config()
    apply_plan(cfg, Plan(execution="streamed", d_chunk=1 << 16,
                         mxu_finish="counts"))
    assert cfg.execution == "streamed"
    assert cfg.d_chunk == 1 << 16
    assert cfg.mxu_finish == "counts"
    assert cfg.client_packing == "off"
    cfg2 = tiny_config()
    apply_plan(cfg2, Plan(client_packing=2, prefetch=True))
    assert cfg2.client_packing == 2
    assert cfg2.prefetch is True


# ---------------------------------------------------------------------------
# plan cache: durability + corrupt tolerance
# ---------------------------------------------------------------------------


def _key(tmp_path, tier="default"):
    return cache_key("fp-abc", tier=tier, device_kind="cpu",
                     jaxlib_version="0.0-test")


def test_cache_roundtrip_and_key_scoping(tmp_path):
    cache = PlanCache(tmp_path)
    key = _key(tmp_path)
    assert cache.get(key) is None  # cold miss
    plan = Plan(prefetch=True)
    path = cache.put(key, plan, {"mode": "measured"})
    assert path is not None and Path(path).is_file()
    entry = cache.get(key)
    assert Plan.from_dict(entry["plan"]) == plan
    assert entry["provenance"]["mode"] == "measured"
    # A different tier / device / jaxlib is a different key: no crosstalk
    # (a reassociating-tier winner must never serve a default-tier run).
    assert cache.get(_key(tmp_path, tier="reassociating")) is None
    assert cache.get(cache_key("fp-abc", device_kind="tpu-v5e",
                               jaxlib_version="0.0-test")) is None


def test_cache_orphaned_tmp_cleanup(tmp_path):
    """A writer SIGKILLed before its os.replace leaves ``<entry>.tmp``;
    the next read deletes it and reports a miss (re-tune)."""
    cache = PlanCache(tmp_path)
    key = _key(tmp_path)
    tmp = cache._path(key).with_name(cache._path(key).name + ".tmp")
    tmp.parent.mkdir(parents=True, exist_ok=True)
    tmp.write_text('{"half": "written')
    assert cache.get(key) is None
    assert not tmp.exists()  # cleaned up, not left to accumulate
    # The published entry from a COMPLETED write is unaffected by a later
    # torn .tmp from a killed writer.
    cache.put(key, Plan())
    tmp.write_text("garbage")
    assert cache.get(key) is not None
    assert not tmp.exists()


@pytest.mark.parametrize("poison", [
    "not json at all {{{",
    json.dumps(["a", "list"]),
    json.dumps({"version": PLAN_CACHE_VERSION + 1, "key": {},
                "plan": Plan().as_dict()}),          # future version
    json.dumps({"version": PLAN_CACHE_VERSION, "key": {},
                "plan": {"execution": "warp"}}),     # unparsable plan
    json.dumps({"version": PLAN_CACHE_VERSION, "key": {"other": "key"},
                "plan": Plan().as_dict()}),          # key mismatch
])
def test_cache_corrupt_and_stale_entries_fall_back_to_retune(tmp_path,
                                                             poison):
    cache = PlanCache(tmp_path)
    key = _key(tmp_path)
    cache._path(key).parent.mkdir(parents=True, exist_ok=True)
    cache._path(key).write_text(poison)
    assert cache.get(key) is None  # miss => re-tune; never an exception
    # ...and the slot is recoverable: a fresh put over the bad file wins.
    cache.put(key, Plan(prefetch=True))
    assert Plan.from_dict(cache.get(key)["plan"]).prefetch is True


def test_cache_entries_surface_corruption_and_invalidate(tmp_path):
    cache = PlanCache(tmp_path)
    key = _key(tmp_path)
    cache.put(key, Plan())
    (tmp_path / "deadbeef.json").write_text("torn")
    entries = dict(cache.entries())
    assert entries["deadbeef"] is None  # reported, not hidden
    assert entries[PlanCache.digest(key)] is not None
    removed = cache.invalidate("deadbeef")
    assert removed == ["deadbeef.json"]
    assert cache.invalidate() == [f"{PlanCache.digest(key)}.json"]
    assert cache.entries() == []


def test_cache_cross_process_hit(tmp_path):
    """On-disk persistence across processes: a winner written here is
    served to a separate interpreter (the module is stdlib-only, loaded
    standalone — no jax import in the subprocess)."""
    cache = PlanCache(tmp_path)
    key = _key(tmp_path)
    cache.put(key, Plan(prefetch=True), {"mode": "measured"})
    script = f"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("at_sub", {str(AUTOTUNE_PY)!r})
at = importlib.util.module_from_spec(spec)
sys.modules["at_sub"] = at  # dataclasses resolves fields via sys.modules
spec.loader.exec_module(at)
cache = at.PlanCache({str(tmp_path)!r})
key = at.cache_key("fp-abc", tier="default", device_kind="cpu",
                   jaxlib_version="0.0-test")
entry = cache.get(key)
assert entry is not None, "cross-process miss"
print(json.dumps(entry["plan"]))
"""
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert Plan.from_dict(json.loads(out.stdout)) == Plan(prefetch=True)


def test_show_plan_cli(tmp_path, capsys):
    """tools/show_plan.py: list names winners and flags corrupt entries;
    show dumps the full entry; invalidate removes by digest prefix."""
    from tools.show_plan import main as show_plan_main

    cache = PlanCache(tmp_path)
    key = _key(tmp_path)
    cache.put(key, Plan(prefetch=True), {"mode": "measured",
                                         "winner_id": Plan(prefetch=True)
                                         .plan_id})
    (tmp_path / "deadbeef.json").write_text("torn")
    digest = PlanCache.digest(key)

    assert show_plan_main(["--cache-dir", str(tmp_path)]) == 0
    listing = capsys.readouterr().out
    assert digest[:12] in listing and "CORRUPT/STALE" in listing
    assert Plan(prefetch=True).plan_id in listing

    assert show_plan_main(["--cache-dir", str(tmp_path), "show",
                           digest[:8]]) == 0
    shown = json.loads(capsys.readouterr().out)
    assert Plan.from_dict(shown["plan"]) == Plan(prefetch=True)

    assert show_plan_main(["--cache-dir", str(tmp_path), "invalidate",
                           digest[:8]]) == 0
    capsys.readouterr()
    assert show_plan_main(["--cache-dir", str(tmp_path), "show",
                           digest[:8]]) == 1
    capsys.readouterr()
    assert show_plan_main(["--cache-dir", str(tmp_path), "invalidate",
                           "--all"]) == 0
    assert cache.entries() == []


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_config_autotune_mode_normalization():
    cfg = tiny_config()
    assert cfg.autotune_mode is None
    for v in (True, 1, "on", "default"):
        cfg.autotune = v
        assert cfg.autotune_mode == "default"
    cfg.autotune = "reassociating"
    assert cfg.autotune_mode == "reassociating"
    for v in (False, None, 0, "off", ""):
        cfg.autotune = v
        assert cfg.autotune_mode is None
    cfg.autotune = "sometimes"
    with pytest.raises(ValueError, match="autotune"):
        cfg.autotune_mode


def test_config_validate_rejects_bad_autotune_settings():
    # Multi-chip tuning is legal since the pod-scale tier (ISSUE 18) —
    # only an EXPLICIT execution='hier' pin conflicts with the tuner.
    cfg = tiny_config()
    cfg.resources(autotune=True, num_devices=2)
    cfg.validate()
    cfg.resources(execution="hier")
    with pytest.raises(ValueError, match="autotune × execution='hier'"):
        cfg.validate()
    cfg2 = tiny_config()
    cfg2.resources(tuned_plan={"execution": "warp"})
    with pytest.raises(ValueError, match="execution"):
        cfg2.validate()
    cfg3 = tiny_config()
    cfg3.resources(mxu_finish="sometimes")
    with pytest.raises(ValueError, match="mxu_finish"):
        cfg3.validate()


# ---------------------------------------------------------------------------
# driver integration: selection, provenance, bit-identity
# ---------------------------------------------------------------------------


def test_heuristic_selection_off_tpu_matches_untuned_resolution(tmp_path):
    """On the CPU backend there is nothing meaningful to time, so the
    deterministic ranked heuristic selects candidates[0] — exactly what
    the hand-written heuristics resolve — and a second build serves the
    SAME plan from the on-disk cache."""
    cfg = tiny_config()
    cfg.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo = cfg.build()
    prov = algo.plan_summary
    assert prov["mode"] == "heuristic" and prov["cache_hit"] is False
    assert algo.plan.execution == "dense"
    assert algo.plan.tier == "default"
    assert len(prov["candidates"]) >= 1
    assert prov["candidates"][0]["plan_id"] == algo.plan.plan_id

    cfg2 = tiny_config()
    cfg2.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo2 = cfg2.build()
    assert algo2.plan == algo.plan
    assert algo2.plan_summary["mode"] == "cache"
    assert algo2.plan_summary["cache_hit"] is True

    row = algo2.train()
    assert row["plan_id"] == algo2.plan.plan_id
    assert row["autotune_cache_hit"] is True
    assert row["autotune_timed"] is False
    assert row["autotune_candidates"] == len(prov["candidates"])


def test_default_tier_pinned_plan_bit_identical_dense(tmp_path):
    """Acceptance: a NON-baseline default-tier plan (prefetch forced on,
    the dense path's non-default knob) reproduces the untuned trajectory
    bit for bit — not just the trivial heuristic winner."""
    base, rows0 = _run_rounds(tiny_config())
    pin = Plan(prefetch=True).as_dict()
    cfg = tiny_config()
    cfg.resources(autotune=True, tuned_plan=pin,
                  autotune_cache_dir=str(tmp_path))
    tuned, rows1 = _run_rounds(cfg)
    assert tuned.plan_summary["mode"] == "pinned"
    assert tuned._prefetcher is not None  # the plan actually engaged
    for a, b in zip(_params(base), _params(tuned)):
        np.testing.assert_array_equal(a, b)
    for r0, r1 in zip(rows0, rows1):
        assert r0["train_loss"] == r1["train_loss"]


@pytest.mark.slow
@pytest.mark.parametrize("aggregator", ["Median", "Trimmedmean"])
def test_default_tier_chunk_ladder_bit_identical_streamed(tmp_path,
                                                          aggregator):
    """Acceptance zoo (streamed): a default-tier plan moving the chunk
    width off the baseline (2^17 -> 2^16) on a chunk-invariant finish is
    bit-identical to the untuned streamed round, per aggregator."""
    def streamed_cfg():
        return tiny_config(execution="streamed",
                           aggregator={"type": aggregator})

    base, rows0 = _run_rounds(streamed_cfg(), rounds=2)
    pin = Plan(execution="streamed", d_chunk=1 << 16).as_dict()
    cfg = streamed_cfg()
    cfg.resources(autotune=True, tuned_plan=pin,
                  autotune_cache_dir=str(tmp_path))
    tuned, rows1 = _run_rounds(cfg, rounds=2)
    assert tuned.config.d_chunk == 1 << 16
    for a, b in zip(_params(base), _params(tuned)):
        np.testing.assert_array_equal(a, b)
    for r0, r1 in zip(rows0, rows1):
        assert r0["train_loss"] == r1["train_loss"]


def test_plan_space_pins_explicit_knobs(tmp_path):
    """Composition contract: a knob the user set explicitly is never
    varied — prefetch pinned off collapses the dense space to the
    baseline candidate only."""
    cfg = tiny_config()
    cfg.prefetch = "off"
    cfg._explicit.add("prefetch")
    cfg.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo = cfg.build()
    assert len(algo.plan_summary["candidates"]) == 1
    assert algo.plan.prefetch is False


@pytest.mark.parametrize("stale", ["windowed_space", "not_in_space"])
def test_stale_cached_plan_retunes_not_applies(tmp_path, stale):
    """A cached winner the current run may not use is re-tuned and
    overwritten, never applied: an entry of the plan space that still
    had a dispatch window (cache version 1, a ``rounds_per_dispatch``
    field) reads as a miss; a parsable winner that is not among this
    run's legal candidates is marked ``cache_stale``."""
    cfg = tiny_config()
    cfg.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo = cfg.build()
    valid_plan = algo.plan
    cache = PlanCache(tmp_path)
    entries = cache.entries()
    assert entries
    for digest, entry in entries:
        if stale == "windowed_space":
            old = dict(entry, version=1,
                       plan={**entry["plan"], "rounds_per_dispatch": 8})
            (tmp_path / f"{digest}.json").write_text(json.dumps(old))
        else:
            cache.put(entry["key"],
                      Plan(execution="streamed", d_chunk=1 << 16),
                      {"mode": "measured"})
    cfg2 = tiny_config()
    cfg2.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo2 = cfg2.build()
    assert algo2.plan == valid_plan  # re-tuned, not the stale winner
    assert algo2.plan_summary["cache_hit"] is False
    assert (algo2.plan_summary.get("cache_stale", False)
            is (stale == "not_in_space"))
    # ...and the re-tune overwrote the stale entry: third build hits.
    cfg3 = tiny_config()
    cfg3.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    assert cfg3.build().plan_summary["cache_hit"] is True


def test_reassociating_tier_pins_explicit_packing_off(tmp_path):
    """Composition contract: client_packing='off' set EXPLICITLY is
    never varied, even by the reassociating tier — only 'auto' (a
    standing request to resolve) or the untouched default may be."""
    cfg = tiny_config()
    cfg.resources(autotune="reassociating", client_packing="off",
                  autotune_cache_dir=str(tmp_path))
    algo = cfg.build()
    assert "client_packing" in cfg._explicit
    assert all("|p1|" in c["plan_id"]
               for c in algo.plan_summary["candidates"])
    assert algo.plan.client_packing == 1


def test_lanes_gate_uses_normalized_autotune_mode():
    """An explicit autotune: 'off' in a trial config must not knock its
    lane group back to sequential execution (the gate reads the
    NORMALIZED mode, not raw truthiness of the string)."""
    from blades_tpu.tune.sweep import _lanes_eligible

    trial = {
        "dataset_config": {"type": "mnist", "num_clients": 6,
                           "train_bs": 8, "seed": 3},
        "global_model": "mlp",
        "server_config": {"lr": 1.0},
        "autotune": "off",
    }
    assert _lanes_eligible("FEDAVG", trial, [0, 1]) is True
    assert _lanes_eligible("FEDAVG", {**trial, "autotune": "on"},
                           [0, 1]) is False


def test_measured_selection_with_fake_timer_is_deterministic(tmp_path,
                                                             monkeypatch):
    """Drive the MEASURED path off-TPU: timing_available patched true
    and a deterministic fake measure ranking the non-baseline candidate
    fastest — the tuner must pick it, stamp timed provenance, and
    persist it for the next process."""
    from blades_tpu.perf import autotune as at

    monkeypatch.setattr(at, "timing_available", lambda: True)
    fake_times = {False: 0.9, True: 0.4}
    monkeypatch.setattr(
        at, "timed_measure_fn",
        lambda config, **kw: (lambda plan: fake_times[plan.prefetch]))
    cfg = tiny_config()
    cfg.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo = cfg.build()
    assert algo.plan.prefetch is True  # the measured winner, not rank 0
    prov = algo.plan_summary
    assert prov["mode"] == "measured" and prov["timed"] is True
    assert [c["median_s"] for c in prov["candidates"]] == [0.9, 0.4]
    row = algo.train()
    assert row["autotune_timed"] is True
    # The winner persisted: an UNPATCHED build in this cache dir serves
    # the measured plan without re-measuring (cross-build cache hit).
    monkeypatch.undo()
    cfg2 = tiny_config()
    cfg2.resources(autotune=True, autotune_cache_dir=str(tmp_path))
    algo2 = cfg2.build()
    assert algo2.plan.prefetch is True
    assert algo2.plan_summary["mode"] == "cache"


# ---------------------------------------------------------------------------
# sweep integration: provenance, schema, kill-and-resume plan pinning
# ---------------------------------------------------------------------------


def _sweep_experiments(rounds=4):
    return {
        "at": {
            "run": "FEDAVG",
            "stop": {"training_iteration": rounds},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 6,
                                   "train_bs": 8, "seed": 3},
                "global_model": "mlp",
                "evaluation_interval": 2,
                "server_config": {"lr": 1.0},
            },
        }
    }


def test_sweep_autotune_provenance_and_schema(tmp_path):
    """--autotune end to end: rows stream schema-valid with the plan
    fields stamped, and the summary carries the full selection record."""
    from blades_tpu.obs import validate_jsonl
    from blades_tpu.tune import run_experiments

    summaries = run_experiments(
        _sweep_experiments(), storage_path=str(tmp_path / "sweep"),
        verbose=0, autotune=True, plan_cache_dir=str(tmp_path / "plans"),
        cost_analysis=False,
    )
    (s,) = summaries
    assert "status" not in s
    at = s["autotune"]
    assert at["mode"] in ("heuristic", "measured")
    assert at["winner_id"] and at["candidates"]
    assert at["cache_hit"] is False
    tdir = tmp_path / "sweep" / "at" / "at_00000"
    # Schema-valid stream with the plan fields on every row.
    num_valid, errors = validate_jsonl(tdir / "metrics.jsonl")
    assert errors == [] and num_valid == 4
    rows = [json.loads(l) for l
            in (tdir / "metrics.jsonl").read_text().splitlines()]
    assert all(r["plan_id"] == at["winner_id"] for r in rows)
    assert all(r["autotune_candidates"] == len(at["candidates"])
               for r in rows)
    # The winner persisted: a second identical sweep is a cache hit.
    second = run_experiments(
        _sweep_experiments(), storage_path=str(tmp_path / "sweep2"),
        verbose=0, autotune=True, plan_cache_dir=str(tmp_path / "plans"),
        cost_analysis=False,
    )
    assert second[0]["autotune"]["mode"] == "cache"
    assert second[0]["autotune"]["cache_hit"] is True


def test_checkpoint_records_plan_and_resume_pins_it(tmp_path):
    """Kill-and-resume replays the IDENTICAL plan (the satellite's
    no-silent-re-tune-drift contract): the checkpoint payload records
    the resolved plan, and a --resume sweep pins it back via tuned_plan
    even when the on-disk plan cache now holds a DIFFERENT winner."""
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    plans = tmp_path / "plans"
    first = run_experiments(
        _sweep_experiments(rounds=8), storage_path=str(tmp_path / "s"),
        verbose=0, autotune=True, plan_cache_dir=str(plans),
        checkpoint_freq=2, preempt_after=5, cost_analysis=False,
    )
    assert first[0].get("status") == "ERROR"  # preempted, max_failures=0
    tdir = tmp_path / "s" / "at" / "at_00000"
    ckpts = sorted(tdir.glob("ckpt_*"))
    assert ckpts
    with open(ckpts[-1] / "algorithm_state.pkl", "rb") as f:
        saved = pickle.load(f)
    original_plan = saved["plan"]
    assert original_plan is not None
    assert Plan.from_dict(original_plan).tier == "default"

    # Sabotage: every cache entry now names a DIFFERENT default-tier
    # winner. A resume that consulted the cache would silently re-tune;
    # the checkpoint pin must beat it.
    cache = PlanCache(plans)
    drifted = Plan(**{**original_plan,
                      "prefetch": not original_plan["prefetch"]})
    for digest, entry in cache.entries():
        cache.put(entry["key"], drifted, {"mode": "measured"})

    second = run_experiments(
        _sweep_experiments(rounds=8), storage_path=str(tmp_path / "s"),
        verbose=0, autotune=True, plan_cache_dir=str(plans),
        checkpoint_freq=2, resume=True, cost_analysis=False,
    )
    (s,) = second
    assert "status" not in s and s["rounds"] == 8
    assert s["autotune"]["mode"] == "pinned"
    assert s["autotune"]["winner"] == original_plan
    assert verify_result_rounds(tdir / "result.json") == list(range(1, 9))
    # Every post-resume row ran under the original plan, not the
    # drifted cache winner.
    rows = [json.loads(l) for l
            in (tdir / "metrics.jsonl").read_text().splitlines()]
    assert all(r["plan_id"] == Plan.from_dict(original_plan).plan_id
               for r in rows)


def test_direct_api_resume_warns_on_plan_drift(tmp_path):
    """Fedavg.load_checkpoint (no sweep runner pinning) surfaces plan
    drift instead of silently continuing under a different plan."""
    cfg = tiny_config()
    cfg.resources(autotune=True, autotune_cache_dir=str(tmp_path / "p1"))
    algo = cfg.build()
    algo.train()
    algo.save_checkpoint(str(tmp_path / "ck"))

    pin = Plan(prefetch=not algo.plan.prefetch).as_dict()
    cfg2 = tiny_config()
    cfg2.resources(autotune=True, tuned_plan=pin,
                   autotune_cache_dir=str(tmp_path / "p1"))
    algo2 = cfg2.build()
    with pytest.warns(RuntimeWarning, match="pin the saved plan"):
        algo2.load_checkpoint(str(tmp_path / "ck"))


# ---------------------------------------------------------------------------
# pod-scale plan space (ISSUE 18)
# ---------------------------------------------------------------------------


def test_plan_id_mesh_free_regression_pin():
    """Mesh-free plan ids carry no mesh marker: the ids round rows and
    checkpoints are stamped with."""
    assert Plan().plan_id == "dense|c131072|p1|mxu=off|nopre"
    assert (Plan(execution="streamed", mxu_finish="counts").plan_id
            == "streamed|c131072|p1|mxu=counts|nopre")


def test_plan_id_mesh_markers_only_when_engaged():
    assert (Plan(mesh_shape=(4, 2), tier="reassociating").plan_id
            == "dense|c131072|p1|mxu=off|nopre|mesh=4x2")
    p = Plan(mesh_shape=(4, 2), collective="hier", tier="reassociating")
    assert p.plan_id.endswith("|mesh=4x2|hier")
    assert Plan.from_dict(p.as_dict()) == p
    # JSON round-trips tuples as lists; normalization restores equality.
    assert Plan.from_dict({**p.as_dict(), "mesh_shape": [4, 2]}) == p
    with pytest.raises(ValueError, match="needs a mesh_shape"):
        Plan(collective="hier")
    with pytest.raises(ValueError, match="collective"):
        Plan(collective="mesh")


def test_enumerate_mesh_candidates_require_devices_and_opt_in():
    kw = dict(executions=["dense"], d_chunks=[1 << 17],
              mesh_shapes=[None, (4, 2)], collectives=["ring", "hier"],
              num_devices=8)
    space = enumerate_plans(**kw)  # no opt-in: the mesh tier is absent
    assert space.baseline == Plan()
    assert all(p.mesh_shape is None for p in space.candidates)
    both = enumerate_plans(allow_reassociating=True, **kw)
    assert both.baseline == Plan()  # baseline-first even with the tier
    mesh = [p for p in both.candidates if p.mesh_shape is not None]
    assert mesh and all(p.tier == "reassociating" for p in mesh)
    assert any(p.collective == "hier" for p in mesh)
    for p in mesh:
        if p.collective == "hier":
            # hier never composes with packing / prefetch / the
            # window store.
            assert p.client_packing == 1
            assert p.prefetch is False and p.state_window is None
    with pytest.raises(ValueError, match="num_devices > 1"):
        enumerate_plans(executions=["dense"], d_chunks=[1 << 17],
                        mesh_shapes=[(4, 2)])
    with pytest.raises(ValueError, match="tile exactly"):
        enumerate_plans(executions=["dense"], d_chunks=[1 << 17],
                        mesh_shapes=[(4, 2)], num_devices=16)


def test_apply_plan_mesh_sets_layout_and_hier_execution():
    cfg = tiny_config()
    apply_plan(cfg, Plan(mesh_shape=(4, 2), tier="reassociating"))
    assert cfg.mesh_shape == (4, 2)
    assert cfg.execution == "dense"
    cfg2 = tiny_config()
    apply_plan(cfg2, Plan(mesh_shape=(4, 2), collective="hier",
                          tier="reassociating"))
    assert cfg2.execution == "hier"
    assert cfg2.mesh_shape == (4, 2)


def test_plan_space_offers_hier_on_multichip_runs():
    """Multi-chip tuning (legal since ISSUE 18): the config's own mesh
    resolution stays candidates[0], and the reassociating tier adds
    exactly one hierarchical candidate on the config's mesh shape
    (defaulting to the flat (n, 1) layout)."""
    cfg = tiny_config(num_clients=8)
    cfg.resources(autotune="reassociating", num_devices=8)
    algo = cfg.build()
    try:
        space = algo._plan_space(allow_reassociating=True)
        assert space.baseline.mesh_shape is None  # today's resolution
        hier = [p for p in space.candidates if p.collective == "hier"]
        assert [p.mesh_shape for p in hier] == [(8, 1)]
    finally:
        algo.stop()
    cfg2 = tiny_config(num_clients=8)
    cfg2.resources(autotune="reassociating", num_devices=8,
                   mesh_shape=(4, 2))
    algo2 = cfg2.build()
    try:
        space2 = algo2._plan_space(allow_reassociating=True)
        assert space2.baseline.mesh_shape == (4, 2)
        assert "|mesh=4x2" in space2.baseline.plan_id
        hier2 = [p for p in space2.candidates if p.collective == "hier"]
        assert [p.mesh_shape for p in hier2] == [(4, 2)]
    finally:
        algo2.stop()

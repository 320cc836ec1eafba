"""Closed-loop control plane (ISSUE 17, blades_tpu/control).

Layers under test:

1. **Policy** — the pure decision functions: every actuator move
   bounded and one-directional, ``rederive_action`` bit-identical to
   the live decision, fail-fast config parsing.
2. **Controller** — per-family cooldown hysteresis (no oscillation by
   construction), the quarantine -> probe -> readmit/requarantine
   lifecycle, ``state()``/``restore()`` byte-identity.
3. **Config gates** — campaign x sync, quarantine's forensics/ledger
   prerequisites, the agg starvation ceiling, ``--watchdog-rules``
   CLI fail-fast.
4. **Driver integration** — a controlled async run whose journal is
   byte-identical across straight / kill-and-resume, re-derivable
   offline by ``replay_round.py --action``, schema-valid rows.
5. **Acceptance (slow)** — a multi-day diurnal simulation under two
   campaign adversaries where the controlled config beats every
   static config in its comparison sweep on final accuracy.
"""

import copy
import json

import pytest

from blades_tpu.control import (
    ControlAction,
    ControlPolicy,
    Controller,
    LIFECYCLE_RULE,
    rederive_action,
)
from blades_tpu.control.policy import (
    decide_agg_every,
    decide_buffer,
    decide_probation,
    decide_probe,
    decide_quarantine,
    decide_replan,
    decide_window,
)

N = 8  # tiny-federation size for the driver tests


# ---------------------------------------------------------------------------
# policy: actions + config parsing
# ---------------------------------------------------------------------------


def test_control_action_validation_and_roundtrip():
    with pytest.raises(ValueError, match="actuator"):
        ControlAction(seq=0, round=1, tick=2, rule="r", actuator="warp")
    act = ControlAction(seq=3, round=7, tick=11, rule="staleness_runaway",
                        actuator="agg_every", old=8, new=4,
                        pre={"old": 8}, message="shrink")
    d = act.as_dict()
    assert d["clients"] == [] and isinstance(d["clients"], list)
    assert ControlAction.from_dict(d) == act
    # json round-trip (the journal is json-serialized into checkpoints).
    assert ControlAction.from_dict(json.loads(json.dumps(d))) == act


def test_policy_from_config_fail_fast_and_rules_merge():
    assert ControlPolicy.from_config(None) == ControlPolicy()
    p = ControlPolicy(cooldown_rounds=3)
    assert ControlPolicy.from_config(p) is p
    with pytest.raises(ValueError, match="must be a dict"):
        ControlPolicy.from_config([1, 2])
    with pytest.raises(ValueError, match="unknown key"):
        ControlPolicy.from_config({"cool_down": 4})
    with pytest.raises(ValueError, match="rule names to actuator"):
        ControlPolicy.from_config({"rules": ["staleness_runaway"]})
    # "enabled" is the config-side arming knob, not a policy field
    # (from_config normalizes the table order, so compare as dicts).
    armed = ControlPolicy.from_config({"enabled": True})
    assert armed.as_config() == ControlPolicy().as_config()
    # rules merge over the default table; "off" removes a response.
    p = ControlPolicy.from_config(
        {"rules": {"staleness_runaway": "off", "suspect_ceiling": "quarantine"}})
    table = dict(p.rule_table)
    assert "staleness_runaway" not in table
    assert table["suspect_ceiling"] == "quarantine"
    assert table["ingest_collapse"] == "buffer"  # default survived
    with pytest.raises(ValueError, match="unknown actuator"):
        ControlPolicy.from_config({"rules": {"x": "teleport"}})
    # as_config() round-trips through from_config() for additive rule
    # overrides ("off" removals re-merge over the defaults, so a
    # removal round-trips as the default mapping, not as absence).
    q = ControlPolicy.from_config(
        {"cooldown_rounds": 3, "rules": {"suspect_ceiling": "quarantine"}})
    assert ControlPolicy.from_config(q.as_config()) == q


@pytest.mark.parametrize("bad", [
    {"cooldown_rounds": 0},
    {"quarantine_rounds": -1},
    {"quarantine_max": 0},
    {"max_quarantine_fraction": 0.0},
    {"max_quarantine_fraction": 1.5},
    {"agg_every_factor": 1},
    {"buffer_factor": 1},
    {"cutoff_factor": 1},
    {"min_agg_every": 0},
    {"window_factor": 1},
    {"min_window": 0},
])
def test_policy_knob_validation(bad):
    with pytest.raises(ValueError):
        ControlPolicy(**bad)


def test_decide_agg_every_bounded_one_directional():
    p = ControlPolicy(min_agg_every=2, agg_every_factor=2)
    act = decide_agg_every(p, seq=0, round_idx=5, tick=9,
                           rule="staleness_runaway", pre={"old": 8})
    assert (act.actuator, act.old, act.new) == ("agg_every", 8, 4)
    # At the floor: bounded means silent, not clamped re-fires.
    assert decide_agg_every(p, seq=0, round_idx=5, tick=9,
                            rule="staleness_runaway", pre={"old": 2}) is None
    # Sync driver has no agg cadence.
    assert decide_agg_every(p, seq=0, round_idx=5, tick=9,
                            rule="staleness_runaway", pre={"old": None}) is None


def test_decide_window_bounded_one_directional():
    """ISSUE 20: the out-of-core window family mirrors agg_every —
    shrink-only toward min_window, silent at the floor, None on
    drivers without a window to move."""
    p = ControlPolicy(min_window=4, window_factor=2)
    act = decide_window(p, seq=0, round_idx=5, tick=9,
                        rule="staleness_runaway", pre={"old": 16})
    assert (act.actuator, act.old, act.new) == ("window", 16, 8)
    # Factor overshooting the floor clamps TO the floor, once.
    act = decide_window(p, seq=0, round_idx=5, tick=9,
                        rule="staleness_runaway", pre={"old": 6})
    assert act.new == 4
    # At the floor: bounded means silent, not clamped re-fires.
    assert decide_window(p, seq=0, round_idx=5, tick=9,
                         rule="staleness_runaway", pre={"old": 4}) is None
    assert decide_window(p, seq=0, round_idx=5, tick=9,
                         rule="staleness_runaway", pre={"old": None}) is None


def test_decide_buffer_grows_then_relaxes_cutoff():
    p = ControlPolicy(buffer_factor=2, max_buffer_capacity=16,
                      cutoff_factor=2, max_weight_cutoff=8)
    act = decide_buffer(p, seq=0, round_idx=1, tick=2, rule="ingest_collapse",
                        pre={"old": 8, "cutoff": 4})
    assert (act.actuator, act.old, act.new) == ("buffer_capacity", 8, 16)
    # At the capacity cap the fallback relaxes the staleness cutoff.
    act = decide_buffer(p, seq=0, round_idx=1, tick=2, rule="ingest_collapse",
                        pre={"old": 16, "cutoff": 4})
    assert (act.actuator, act.old, act.new) == ("weight_cutoff", 4, 8)
    # Both bounds hit -> no further relief.
    assert decide_buffer(p, seq=0, round_idx=1, tick=2, rule="ingest_collapse",
                         pre={"old": 16, "cutoff": 8}) is None
    assert decide_buffer(p, seq=0, round_idx=1, tick=2, rule="ingest_collapse",
                         pre={"old": None, "cutoff": None}) is None


def test_decide_quarantine_ceiling_and_exclusions():
    p = ControlPolicy(quarantine_rounds=5, quarantine_max=3,
                      max_quarantine_fraction=0.5)
    # Suspects may be bare ids or (id, score) pairs; held ids skipped.
    act = decide_quarantine(p, seq=2, round_idx=10, tick=20, rule="fpr_collapse",
                            pre={"excluded": [4], "active": 1},
                            suspects=[(4, 0.9), (1, 0.8), 6, (2, 0.5)],
                            num_clients=8)
    assert act.clients == (1, 6, 2)  # ceiling 4 - active 1 = room 3; 4 held
    assert act.until == 15 and (act.old, act.new) == (1, 4)
    # Room at the fleet ceiling truncates below quarantine_max.
    act = decide_quarantine(p, seq=2, round_idx=10, tick=20, rule="fpr_collapse",
                            pre={"excluded": [4], "active": 2},
                            suspects=[(4, 0.9), (1, 0.8), 6, (2, 0.5)],
                            num_clients=8)
    assert act.clients == (1, 6)
    # quarantine_rounds=0 disables the family entirely.
    p0 = ControlPolicy(quarantine_rounds=0)
    assert decide_quarantine(p0, seq=0, round_idx=0, tick=0, rule="fpr_collapse",
                             pre={}, suspects=[1], num_clients=8) is None
    # No room at the fleet ceiling.
    act = decide_quarantine(p, seq=0, round_idx=0, tick=0, rule="fpr_collapse",
                            pre={"excluded": [0, 1, 2, 3], "active": 4},
                            suspects=[5, 6], num_clients=8)
    assert act is None


def test_decide_replan_gated_on_allowed():
    p = ControlPolicy()
    assert decide_replan(p, seq=0, round_idx=0, tick=0,
                         rule="round_time_regression",
                         pre={"allowed": False}) is None
    act = decide_replan(p, seq=0, round_idx=0, tick=0,
                        rule="round_time_regression", pre={"allowed": True})
    assert act.actuator == "replan"


def test_decide_probe_and_probation_lifecycle():
    p = ControlPolicy(quarantine_rounds=4)
    assert decide_probe(p, seq=0, round_idx=3, tick=0, pre={"due": []}) is None
    act = decide_probe(p, seq=5, round_idx=3, tick=7,
                       pre={"due": [2, 6], "active": 3})
    assert (act.rule, act.actuator) == (LIFECYCLE_RULE, "probe")
    assert act.clients == (2, 6) and (act.old, act.new) == (3, 1)
    # Probation: flagged probationers requarantined, clean ones
    # readmitted, consecutive seqs in (requarantine, readmit) order.
    pre = {"probation": [2, 6], "participants": [1, 2, 6], "flagged": [6]}
    acts = decide_probation(p, round_idx=10, tick=0, pre=pre, seq0=8)
    assert [(a.seq, a.actuator, a.clients) for a in acts] == [
        (8, "requarantine", (6,)), (9, "readmit", (2,))]
    assert acts[0].until == 14
    # No probationer participated -> nothing to diagnose.
    assert decide_probation(p, round_idx=10, tick=0, seq0=0,
                            pre={"probation": [2], "participants": [5],
                                 "flagged": []}) == []


def test_rederive_action_every_actuator():
    p = ControlPolicy(quarantine_rounds=5, quarantine_max=2)
    suspects = [(3, 0.9), (5, 0.7)]
    cases = [
        decide_agg_every(p, seq=0, round_idx=1, tick=2,
                         rule="staleness_runaway", pre={"old": 8}),
        decide_buffer(p, seq=1, round_idx=2, tick=3, rule="ingest_collapse",
                      pre={"old": 8, "cutoff": 4}),
        decide_quarantine(p, seq=2, round_idx=3, tick=4, rule="fpr_collapse",
                          pre={"excluded": [], "active": 0},
                          suspects=suspects, num_clients=8),
        decide_replan(p, seq=3, round_idx=4, tick=5,
                      rule="round_time_regression", pre={"allowed": True}),
        decide_probe(p, seq=4, round_idx=5, tick=6,
                     pre={"due": [3], "active": 2}),
        decide_window(p, seq=7, round_idx=8, tick=9,
                      rule="staleness_runaway", pre={"old": 16}),
    ] + decide_probation(p, round_idx=6, tick=7, seq0=5,
                         pre={"probation": [3, 5], "participants": [3, 5],
                              "flagged": [3]})
    assert len(cases) == 8  # probation emitted the (requarantine, readmit) pair
    for act in cases:
        d = act.as_dict()
        re = rederive_action(p, json.loads(json.dumps(d)),
                             suspects=suspects, num_clients=8)
        assert json.dumps(re, sort_keys=True) == json.dumps(d, sort_keys=True)
    with pytest.raises(ValueError, match="unknown actuator"):
        rederive_action(p, dict(cases[0].as_dict(), actuator="warp"))


# ---------------------------------------------------------------------------
# controller: hysteresis, lifecycle, checkpoint state
# ---------------------------------------------------------------------------


def _ctl(**kw):
    policy = kw.pop("policy", None) or ControlPolicy(**kw.pop("knobs", {}))
    defaults = dict(num_clients=8, agg_every=16, buffer_capacity=8,
                    weight_cutoff=4)
    defaults.update(kw)
    return Controller(policy, **defaults)


def test_controller_cooldown_prevents_oscillation():
    c = _ctl(knobs=dict(cooldown_rounds=4, min_agg_every=2))
    ev = {"rule": "staleness_runaway"}
    fired = []
    for r in range(12):
        # The sensor fires EVERY round; the family cooldown must thin
        # that to one bounded move per window.
        acts = c.step(round_idx=r, tick=r, events=[ev])
        fired += [(a.round, a.old, a.new) for a in acts]
    assert fired == [(0, 16, 8), (4, 8, 4), (8, 4, 2)]
    assert c.values["agg_every"] == 2
    # At the floor further fires are silent: no clamped re-moves, and
    # by construction no move exists that could grow agg_every back —
    # an A->B->A oscillation is structurally impossible.
    assert c.step(round_idx=12, tick=12, events=[ev]) == []
    assert len(c.journal) == 3
    # Unmapped rules and rules mapped "off" produce no action at all.
    assert c.step(round_idx=13, tick=13, events=[{"rule": "nan_loss"}]) == []


def test_controller_window_family_rides_cooldown():
    """ISSUE 20: a rule mapped to the window family drives bounded
    shrink-only moves on the controller's ``window`` view, with the
    same per-family cooldown hysteresis as agg_every; an unseeded
    window (non-ooc driver) stays silent."""
    policy = ControlPolicy(
        rule_table=(("staleness_runaway", "window"),),
        cooldown_rounds=4, min_window=4)
    c = Controller(policy, num_clients=8, window=16)
    ev = {"rule": "staleness_runaway"}
    fired = []
    for r in range(9):
        acts = c.step(round_idx=r, tick=r, events=[ev])
        fired += [(a.round, a.actuator, a.old, a.new) for a in acts]
    assert fired == [(0, "window", 16, 8), (4, "window", 8, 4)]
    assert c.values["window"] == 4
    assert c.step(round_idx=9, tick=9, events=[ev]) == []  # at the floor
    # The window view rides state()/restore() with the other values.
    resumed = Controller(policy, num_clients=8, window=16)
    resumed.restore(json.loads(json.dumps(c.state())))
    assert resumed.values["window"] == 4
    # Unseeded window (sync / resident drivers): nothing to move.
    idle = Controller(policy, num_clients=8)
    assert idle.step(round_idx=0, tick=0, events=[ev]) == []


def test_controller_quarantine_probe_readmit_cycle():
    c = _ctl(knobs=dict(cooldown_rounds=1, quarantine_rounds=2,
                        quarantine_max=2, max_quarantine_fraction=0.5))
    ev = {"rule": "fpr_collapse"}
    (q,) = c.step(round_idx=0, tick=0, events=[ev],
                  suspects=[(3, 0.9), (5, 0.8)])
    assert q.actuator == "quarantine" and q.clients == (3, 5) and q.until == 2
    assert c.quarantined_clients() == {3, 5}
    # While held, a re-fire has no fresh suspects to pick.
    assert c.step(round_idx=1, tick=1, events=[ev], suspects=[(3, 0.9)]) == []
    # Expiry releases to probation (probe on next participation).
    (probe,) = c.step(round_idx=2, tick=2)
    assert probe.actuator == "probe" and probe.clients == (3, 5)
    assert c.quarantine == {} and set(c.probation) == {3, 5}
    # Diagnosis: 5 flagged again -> requarantined; 3 clean -> readmitted.
    acts = c.step(round_idx=3, tick=3, participants=[1, 3, 5], flagged=[5])
    assert [a.actuator for a in acts] == ["requarantine", "readmit"]
    assert c.quarantined_clients() == {5} and c.probation == {}
    # Seqs are strictly consecutive across the whole journal.
    assert [a["seq"] for a in c.journal] == list(range(len(c.journal)))


def test_controller_state_restore_resumes_exact_journal():
    def drive(c, rounds):
        ev_q = {"rule": "fpr_collapse"}
        ev_s = {"rule": "staleness_runaway"}
        for r in rounds:
            c.step(round_idx=r, tick=2 * r, events=[ev_q, ev_s],
                   suspects=[(r % 8, 0.9), ((r + 3) % 8, 0.8)],
                   participants=[r % 8, (r + 1) % 8],
                   flagged=[(r + 1) % 8] if r % 3 == 0 else [])

    knobs = dict(cooldown_rounds=2, quarantine_rounds=2, quarantine_max=1,
                 max_quarantine_fraction=0.5)
    straight = _ctl(knobs=dict(knobs))
    drive(straight, range(10))

    first = _ctl(knobs=dict(knobs))
    drive(first, range(5))
    snap = json.loads(json.dumps(first.state()))  # checkpoint round-trip
    resumed = _ctl(knobs=dict(knobs))
    resumed.restore(copy.deepcopy(snap))
    drive(resumed, range(5, 10))
    assert json.dumps(resumed.journal, sort_keys=True) == \
        json.dumps(straight.journal, sort_keys=True)
    assert resumed.state() == straight.state()


# ---------------------------------------------------------------------------
# config gates + CLI fail-fast
# ---------------------------------------------------------------------------


_SUSPECT_RULE = {"name": "suspect_ceiling", "kind": "ceiling",
                 "field": "suspected_fraction", "threshold": 0.05,
                 "min_points": 1}


def _controlled_config(**over):
    from blades_tpu.algorithms.config import FedavgConfig

    arrivals = {"rate": 0.4, "agg_every": 4, "staleness_cap": 4, "seed": 7}
    arrivals.update(over.pop("arrivals", {}))
    control = {"cooldown_rounds": 2, "quarantine_rounds": 3,
               "quarantine_max": 2, "rules": {"suspect_ceiling": "quarantine"}}
    control.update(over.pop("control", {}))
    cfg = (FedavgConfig()
           .data(dataset="mnist", num_clients=N, seed=7)
           .training(global_model="mlp", aggregator={"type": "Signguard"})
           .adversary(num_malicious_clients=3,
                      adversary_config=over.pop("adversary", {
                          "type": "DiurnalALIE", "period": 8, "duty": 0.99,
                          "high": 1.5}))
           .resources(execution="async")
           .arrivals(**arrivals)
           .observability(forensics=True, ledger=True,
                          watchdog_rules=[dict(_SUSPECT_RULE)])
           .control(**control))
    for k, v in over.items():
        setattr(cfg, k, v)
    cfg.validate()  # the tune-runner step: infers shapes, runs the gates
    return cfg


def test_config_control_gates():
    from blades_tpu.algorithms.config import FedavgConfig

    # control_enabled: None disarmed, bare .control() arms defaults,
    # enabled=False disarms an otherwise-populated spec.
    assert not FedavgConfig().control_enabled
    assert FedavgConfig().control().control_enabled
    cfg = FedavgConfig().control(cooldown_rounds=4).control(enabled=False)
    assert not cfg.control_enabled and cfg.get_control_policy() is None
    # Unknown policy keys in a raw control_config dict (the builder's
    # keywords can't typo) die at validate(), not mid-run.
    cfg = _controlled_config()
    cfg.control_config = dict(cfg.control_config, warp_factor=9)
    with pytest.raises(ValueError, match="unknown key"):
        cfg.validate()
    # Campaign adversaries need the async tick clock.
    with pytest.raises(ValueError, match="tick clock"):
        (FedavgConfig()
         .data(dataset="mnist", num_clients=N, seed=7)
         .training(global_model="mlp")
         .adversary(num_malicious_clients=3,
                    adversary_config={"type": "DiurnalALIE", "period": 8,
                                      "duty": 0.5})
         .validate())
    # Quarantine moves need forensics + ledger + async ingest.
    with pytest.raises(ValueError, match="forensics"):
        _controlled_config().observability(forensics=False).validate()
    with pytest.raises(ValueError, match="ledger"):
        _controlled_config().observability(ledger=False).validate()
    # The fleet ceiling may not starve the aggregation trigger.
    with pytest.raises(ValueError, match="starving"):
        _controlled_config(
            control={"max_quarantine_fraction": 0.9}).validate()
    # The tuned recipe itself validates clean.
    _controlled_config().validate()


def test_campaign_schedule_validation():
    from blades_tpu.adversaries.campaigns import (
        DiurnalALIECampaign,
        LazyRampCampaign,
    )

    with pytest.raises(ValueError, match="period"):
        DiurnalALIECampaign(period=1)
    for duty in (0.0, 1.0, -0.1):
        with pytest.raises(ValueError, match="duty"):
            DiurnalALIECampaign(period=8, duty=duty)
    adv = DiurnalALIECampaign(num_clients=8, num_byzantine=3, period=8,
                              duty=0.5)
    assert adv.wants_ticks and adv.requires_virtual_time
    with pytest.raises(ValueError, match="start at tick 0"):
        LazyRampCampaign(ramp=((4, 0.5),))
    with pytest.raises(ValueError, match="strictly increasing"):
        LazyRampCampaign(ramp=((0, 0.0), (8, 0.5), (8, 1.0)))
    with pytest.raises(ValueError, match="non-empty"):
        LazyRampCampaign(ramp=())
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        LazyRampCampaign(ramp=((0, 1.5),))
    ramp = LazyRampCampaign(num_clients=8, num_byzantine=3,
                            ramp=((0, 0.0), (8, 1.0)))
    assert ramp.wants_ticks and ramp.requires_virtual_time


def test_watchdog_rules_cli_fail_fast(tmp_path, capsys):
    from blades_tpu.train import main

    base = ["run", "FEDAVG", "--storage-path", str(tmp_path)]
    # Invalid JSON, non-list JSON, and a bad rule kind all die in
    # argparse (SystemExit 2) before any experiment is built.
    for bad, msg in (("{not json", "not valid JSON"),
                     ('{"name": "x"}', "must be a JSON list"),
                     ('[{"name": "x", "kind": "warp", "field": "tick"}]',
                      "kind")):
        with pytest.raises(SystemExit):
            main(base + ["--watchdog-rules", bad])
        err = capsys.readouterr().err
        assert "--watchdog-rules" in err and msg in err, err
    assert not any(tmp_path.iterdir()), "an experiment was built anyway"


# ---------------------------------------------------------------------------
# driver integration: journal determinism, offline rederivation, schema
# ---------------------------------------------------------------------------


_CONTROL_REPLAY = ("tick", "cycle_ticks", "arrivals_quarantined",
                   "control_actions_total", "quarantine_size",
                   "train_loss", "agg_norm", "suspected_fraction")


def _run_controlled(cfg_builder, rounds):
    from blades_tpu.algorithms.fedavg import Fedavg

    algo = Fedavg(cfg_builder())
    try:
        return [algo.train() for _ in range(rounds)], algo
    except BaseException:
        algo.stop()
        raise


def _journal_of(rows):
    return [a for r in rows for a in (r.get("control_actions") or [])]


def test_controlled_run_journal_resume_bit_identity(tmp_path):
    from blades_tpu.algorithms.fedavg import Fedavg

    rows_a, algo_a = _run_controlled(_controlled_config, 12)
    journal_a = _journal_of(rows_a)
    assert len(journal_a) >= 4, "scenario lost its control activity"
    assert [a["seq"] for a in journal_a] == list(range(len(journal_a)))
    algo_a.stop()

    # Kill after 5 rounds, restore into a FRESH build, finish to 12.
    rows_b, algo_b = _run_controlled(_controlled_config, 5)
    path = algo_b.save_checkpoint(str(tmp_path))
    algo_b.stop()
    algo_c = Fedavg(_controlled_config())
    algo_c.load_checkpoint(path)
    try:
        rows_c = [algo_c.train() for _ in range(7)]
    finally:
        algo_c.stop()

    resumed = _journal_of(rows_b) + _journal_of(rows_c)
    assert json.dumps(resumed, sort_keys=True) == \
        json.dumps(journal_a, sort_keys=True)
    for ra, rb in zip(rows_a, rows_b + rows_c):
        for f in _CONTROL_REPLAY:
            assert ra.get(f) == rb.get(f), f


def test_rederive_actions_and_report_roundtrip(tmp_path, capsys):
    from tools.control_report import main as report_main
    from tools.replay_round import rederive_actions

    rows, algo = _run_controlled(_controlled_config, 12)
    cfg = algo.config
    algo.stop()
    # Mirror the real flightrec artifact shape: the fleet size lives
    # under dataset_config, not at the top level of the dumped config.
    dump = {
        "config": {"dataset_config": {"type": "mnist",
                                      "num_clients": cfg.num_clients},
                   "control_config": dict(cfg.control_config)},
        "rounds": [{k: v for k, v in r.items()
                    if k in ("training_iteration", "tick", "control_actions",
                             "ledger_top_suspects")} for r in rows],
    }
    assert sum(len(r.get("control_actions") or []) for r in dump["rounds"]) > 0
    # Every journaled action re-derives bit-identically from (policy,
    # pre, suspects) alone — the replay contract's control half.
    assert rederive_actions(dump, quiet=True) == 0
    # A tampered journal is caught, not replayed over.
    bad = json.loads(json.dumps(dump))
    for r in bad["rounds"]:
        for a in r.get("control_actions") or []:
            if a["actuator"] == "quarantine":
                a["clients"] = [c + 1 for c in a["clients"]]
    assert bad != dump
    assert rederive_actions(bad, quiet=True) == 1
    # The forensics report reads the same artifact.
    p = tmp_path / "dump.json"
    p.write_text(json.dumps(dump))
    assert report_main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "quarantine" in out
    assert report_main([str(p), "--json"]) == 0


def test_controlled_rows_schema_valid():
    from blades_tpu.obs.schema import validate_record

    rows, algo = _run_controlled(_controlled_config, 4)
    algo.stop()
    for i, row in enumerate(rows):
        rec = dict(row, experiment="ctl", trial="t0", training_iteration=i + 1)
        validate_record(rec)
        assert rec["control_actions_total"] >= 0
        assert rec["quarantine_size"] >= 0


# ---------------------------------------------------------------------------
# acceptance (slow): the controller beats every static under campaigns
# ---------------------------------------------------------------------------


def _campaign_config(adversary, *, controlled, aggregator=None, rounds=50):
    """The 24h-simulation scenario: one simulated day = 24 virtual
    ticks; 50 rounds cover several days of the campaign schedule.  The
    synthetic task is hardened (noise/heterogeneity) so attack damage
    is visible in final accuracy instead of saturating at 1.0."""
    from blades_tpu.algorithms.config import FedavgConfig

    cfg = (FedavgConfig()
           .data(dataset={"type": "mnist", "synthetic_noise": 3.0,
                          "synthetic_heterogeneity": 0.6},
                 num_clients=N, seed=7)
           .training(global_model="mlp", num_classes=10,
                     input_shape=(28, 28, 1),
                     aggregator=aggregator or {"type": "Signguard"},
                     server_lr=0.5, train_batch_size=32,
                     num_batch_per_round=2)
           .client(lr=0.1)
           .adversary(num_malicious_clients=3, adversary_config=adversary)
           .evaluation(evaluation_interval=rounds)
           .resources(execution="async")
           .arrivals(rate=0.4, agg_every=4, staleness_cap=4, seed=7)
           .observability(forensics=True, ledger=True,
                          watchdog_rules=[dict(_SUSPECT_RULE)]))
    if controlled:
        cfg = cfg.control(cooldown_rounds=2, quarantine_rounds=100,
                          quarantine_max=3, max_quarantine_fraction=0.4,
                          rules={"suspect_ceiling": "quarantine"})
    return cfg


_DIURNAL = {"type": "DiurnalALIE", "period": 24, "duty": 0.9, "high": 8.0}
_RAMP = {"type": "LazyRamp", "ramp": ((0, 0.0), (16, 1.0)),
         "copy_scale": 8.0, "noise_std": 0.05}


def _final_acc(cfg, rounds=50):
    from blades_tpu.algorithms.fedavg import Fedavg

    algo = Fedavg(cfg)
    try:
        rows = [algo.train() for _ in range(rounds)]
    finally:
        algo.stop()
    acc = next(r["test_acc"] for r in reversed(rows)
               if r.get("test_acc") is not None)
    return float(acc), rows


@pytest.mark.slow
def test_campaign_acceptance_controlled_beats_every_static(tmp_path):
    """Two campaign adversaries, one controller, a static comparison
    sweep along the axes the controller tunes (the identical config
    uncontrolled, and the defense-axis Median static).  The controlled
    config must win on final accuracy under EVERY campaign — the
    static configs each have a regime they lose."""
    from blades_tpu.algorithms.fedavg import Fedavg
    from tools.replay_round import rederive_actions

    margins = {}
    for name, adv in (("diurnal", _DIURNAL), ("ramp", _RAMP)):
        acc_ctl, rows_ctl = _final_acc(
            _campaign_config(dict(adv), controlled=True))
        # The controller actually acted: campaign attackers quarantined.
        assert rows_ctl[-1]["quarantine_size"] == 3
        statics = {
            "static_signguard": _campaign_config(dict(adv), controlled=False),
            "static_median": _campaign_config(
                dict(adv), controlled=False, aggregator={"type": "Median"}),
        }
        for label, cfg in statics.items():
            acc_static, _ = _final_acc(cfg)
            margins[(name, label)] = acc_ctl - acc_static
            assert acc_ctl > acc_static, (
                f"{name}: controlled {acc_ctl:.3f} lost to {label} "
                f"{acc_static:.3f}")
        if name == "diurnal":
            journal_straight = _journal_of(rows_ctl)
            # Kill mid-campaign (inside the second simulated day),
            # resume from the checkpoint, and the journal continues
            # byte-identically.
            algo = Fedavg(_campaign_config(dict(adv), controlled=True))
            try:
                rows_b = [algo.train() for _ in range(20)]
                path = algo.save_checkpoint(str(tmp_path))
            finally:
                algo.stop()
            algo2 = Fedavg(_campaign_config(dict(adv), controlled=True))
            algo2.load_checkpoint(path)
            try:
                rows_c = [algo2.train() for _ in range(30)]
                cfg_resumed = algo2.config
            finally:
                algo2.stop()
            resumed = _journal_of(rows_b) + _journal_of(rows_c)
            assert json.dumps(resumed, sort_keys=True) == \
                json.dumps(journal_straight, sort_keys=True)
            # Every action in the resumed journal re-derives offline.
            dump = {"config": {
                        "dataset_config": {
                            "type": "mnist",
                            "num_clients": cfg_resumed.num_clients},
                        "control_config": dict(cfg_resumed.control_config)},
                    "rounds": rows_b + rows_c}
            assert rederive_actions(dump, quiet=True) == 0
    # The wins are decisive, not numerical noise.
    assert min(margins.values()) > 0.05, margins

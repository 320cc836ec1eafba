"""The readers of device time by the program's own phases
(``readers/device_phase.py``) and of the compiler's memory account
(``readers/hbm_need.py``) on a small hand-made reduced trace and the map a
program's registry gives for it, kept with the benchmark
(``perfbench/testdata/device_phases_two_rounds.json``), through the metrics'
own files."""

import json
import os
import sys

import pytest

from pb import tracered as T
from pb.manifest import CHECKOUT, Manifest, reader

M = Manifest()
DOC = json.load(open(os.path.join(CHECKOUT, "perfbench", "testdata",
                                  "device_phases_two_rounds.json")))
TRACE, EXPECT = DOC["trace"], DOC["expect"]
METRICS = sorted(EXPECT["metrics"])
PHASE_METRICS = [m for m in METRICS if m != "round_hbm_need_gb"]


@pytest.fixture
def registry(monkeypatch):
    """The program's registry answering with the file's map and accounts."""
    from blades_tpu.obs import device_phases

    monkeypatch.setattr(device_phases, "op_phases", lambda: DOC["op_phases"])
    monkeypatch.setattr(device_phases, "hbm_need_bytes",
                        lambda: DOC["hbm_need_bytes"])
    return device_phases


def read(name, trace=TRACE, notes=None):
    spec = M.metric_file(name)
    ctx = {"trace": trace, "traced_rounds": 2, "rows": [],
           "notes": {} if notes is None else notes}
    return reader(spec["reader"])(ctx, spec)


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_reads_its_phases(registry, name):
    assert read(name) == pytest.approx(EXPECT["metrics"][name])


def test_an_operation_name_of_two_programs_keeps_each_programs_phase(
        registry):
    """``fusion.1`` runs in the block (``blades/step``), the finish and the
    round counters (no scope): the phase comes from the launch around it."""
    notes = {}
    read("unphased_ms_per_round", notes=notes)
    split = notes["device_phase_ms_per_round"]
    assert split == pytest.approx(EXPECT["ms_per_round"])
    assert split["step"] == pytest.approx(0.015)


@pytest.mark.parametrize("ids", [("(11)", "(99)"), ("(5)", "(6)")])
def test_two_programs_of_one_name_each_keep_their_phases(registry, ids):
    """Two kept programs of the block: the trace's ids name the older one
    exactly (the CPU's fingerprint), or neither (a TPU's): each launch
    then takes the program whose instructions it ran."""
    older = {"fusion.9": "head", "fusion.1": "embed"}
    registry.op_phases = lambda: {**DOC["op_phases"],
                                  "jit__train_block(11)": older}
    trace = json.loads(json.dumps(TRACE))
    dev = trace["devices"]["/device:TPU:0"]
    launches = [m for m in dev["modules"] if m[0] == "jit__train_block(11)"]
    launches[0][0] = "jit__train_block" + ids[0]   # runs the older program
    launches[1][0] = "jit__train_block" + ids[1]   # runs the newest
    for op in dev["ops"]:
        if launches[0][1] <= op[1] < launches[0][1] + launches[0][2] \
                and op[0] != "fusion.1":
            op[0] = "fusion.9"
    notes = {}
    read("unphased_ms_per_round", trace=trace, notes=notes)
    split = notes["device_phase_ms_per_round"]
    # Round 1's block: fusion.1 0.015 ms (embed), the rest 0.025 (head;
    # the 0.002 that overlaps an earlier operation counts once).
    assert split["embed"] == pytest.approx(0.015 / 2)
    assert split["head"] == pytest.approx(0.025 / 2)
    assert split["step"] == pytest.approx(0.015 / 2)
    assert sum(split.values()) == pytest.approx(EXPECT["busy_ms_per_round"])


def test_the_phases_and_the_rest_sum_to_the_busy_time(registry):
    notes = {}
    read("store_ms_per_round", notes=notes)
    busy, _ = T.busy_and_window_s(TRACE)
    total = sum(notes["device_phase_ms_per_round"].values())
    assert total == pytest.approx(1e3 * busy / 2)
    assert total == pytest.approx(EXPECT["busy_ms_per_round"])


@pytest.mark.parametrize("name", METRICS)
def test_the_compilers_account_goes_into_the_notes(registry, name):
    notes = {}
    read(name, notes=notes)
    key = "hbm_need_gb" if name == "round_hbm_need_gb" \
        else "device_phase_ms_per_round"
    assert key in notes


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_no_trace_or_no_operations_line_reads_nothing(registry, name):
    assert read(name, trace=None) is None
    assert read(name, trace={"devices": {}, "host": TRACE["host"]}) is None
    bare = {"devices": {"/device:TPU:0": {
        "modules": TRACE["devices"]["/device:TPU:0"]["modules"], "ops": []}},
        "host": TRACE["host"]}
    assert read(name, trace=bare) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_that_noted_nothing_reads_nothing(monkeypatch, name):
    from blades_tpu.obs import device_phases

    monkeypatch.setattr(device_phases, "op_phases", dict)
    monkeypatch.setattr(device_phases, "hbm_need_bytes", dict)
    assert read(name) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_registry_reads_nothing_and_does_not_raise(
        monkeypatch, name):
    """The parent's program has no ``blades_tpu.obs.device_phases``."""
    import blades_tpu.obs

    monkeypatch.delattr(blades_tpu.obs, "device_phases", raising=False)
    monkeypatch.setitem(sys.modules, "blades_tpu.obs.device_phases", None)
    assert read(name) is None


def test_a_phase_that_did_not_run_reads_nothing(registry):
    no_routing = {m: {i: (None if p in ("dispatch", "combine") else p)
                      for i, p in t.items()}
                  for m, t in DOC["op_phases"].items()}
    registry.op_phases = lambda: no_routing
    assert read("expert_dispatch_ms_per_round") is None
    assert read("unphased_ms_per_round") == pytest.approx(0.017)

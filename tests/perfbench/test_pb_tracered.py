"""The reduction from a trace to numbers, on a small hand-made trace and on
a recorded one kept with the benchmark (``perfbench/testdata``)."""

import glob
import json
import os

import pytest

from pb import tracered as T
from pb.manifest import CHECKOUT

US = 1e3  # ns

# Two rounds on the host; the device runs two train blocks and a finish in
# each, with a gap while the host fetches the metrics.
SMALL = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__train_block(1)", 10 * US, 40 * US],
                    ["jit__train_block(1)", 50 * US, 40 * US],
                    ["jit__finish_fused_compact(2)", 95 * US, 10 * US],
                    ["jit__train_block(1)", 130 * US, 40 * US],
                    ["jit__train_block(1)", 170 * US, 40 * US],
                    ["jit__finish_fused_compact(2)", 212 * US, 10 * US],
                    ["jit_outside(3)", 400 * US, 10 * US]],
        "ops": [["fusion.1", 10 * US, 30 * US], ["fusion.2", 35 * US, 15 * US],
                ["fusion.1", 50 * US, 40 * US], ["custom-call.7", 95 * US, 10 * US],
                ["fusion.1", 130 * US, 80 * US], ["custom-call.7", 212 * US, 10 * US],
                ["fusion.9", 400 * US, 10 * US]],
    }},
    "host": [["bench/round", 0.0, 120 * US],
             ["PjitFunction(_train_block)", 2 * US, 5 * US],
             ["device_get", 100 * US, 18 * US],
             ["bench/round", 125 * US, 105 * US],
             ["device_get", 224 * US, 5 * US]],
}


def test_busy_union_merges_overlaps():
    evs = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 35, 1]]
    assert T.busy_union(evs) == [[0, 15], [30, 36]]
    assert T.busy_ns(evs) == 21


def test_window_is_the_round_spans_and_clips_what_lies_outside():
    t0, t1, rounds = T.traced_window(SMALL)
    assert (t0, t1, rounds) == (0.0, 230 * US, 2)
    names = [n for n, _, _ in T.first_device(SMALL, "modules")]
    assert "jit_outside(3)" not in names and len(names) == 6


def test_busy_idle_and_per_name_time():
    busy, win = T.busy_and_window_s(SMALL)
    # ops: [10,50] [50,90] [95,105] [130,210] [212,222] us
    assert busy == pytest.approx(180e-6)
    assert win == pytest.approx(230e-6)
    mods = T.first_device(SMALL, "modules")
    secs, launches = T.time_by_pattern(mods, ["_train_block"])
    assert (launches, secs) == (4, pytest.approx(160e-6))
    secs, launches = T.time_by_pattern(mods, ["_finish", "_rowgeom_"])
    assert (launches, secs) == (2, pytest.approx(20e-6))
    assert T.time_by_pattern(mods, ["no_such_program"]) == (0.0, 0)
    assert T.heaviest(T.first_device(SMALL))[0] == ["fusion.1",
                                                    pytest.approx(150e-6)]


def test_gaps_go_to_the_host_span_that_covers_them():
    gaps = dict(T.idle_gaps(SMALL))
    # idle: [0,10] [90,95] [105,130] [210,212] [222,230] us; each goes to
    # the innermost host span over its middle.
    assert gaps["device_get"] == pytest.approx((25 + 8) * 1e-6)
    assert gaps["PjitFunction(_train_block)"] == pytest.approx(10e-6)
    assert gaps["bench/round"] == pytest.approx((5 + 2) * 1e-6)
    assert sum(gaps.values()) == pytest.approx(50e-6)


def test_no_round_span_is_an_error():
    with pytest.raises(ValueError):
        T.traced_window({"devices": {}, "host": []})


RECORDED = sorted(glob.glob(os.path.join(CHECKOUT, "perfbench", "testdata",
                                         "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace_reduces(path):
    doc = json.load(open(path))
    trace, want = doc["trace"], doc["expect"]
    busy, win = T.busy_and_window_s(trace)
    assert 0 < busy <= win
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert win == pytest.approx(want["window_s"], rel=1e-9)
    mods = T.first_device(trace, "modules")
    for pattern, (secs, launches) in want["by_pattern"].items():
        got = T.time_by_pattern(mods, [pattern])
        assert got[1] == launches and got[0] == pytest.approx(secs, rel=1e-9)
    assert sum(s for _, s in T.idle_gaps(trace, k=10 ** 6)) == \
        pytest.approx(win - busy, rel=1e-6)

"""The readers of the program's own phases (``row_timer``, ``idle_in_span``,
``worst_excess``) on a small hand-made reduced trace and fabricated rows
kept with the benchmark (``perfbench/testdata/phases_two_rounds.json``),
through the metrics' own files."""

import json
import os

import pytest

from pb import tracered as T
from pb.manifest import CHECKOUT, Manifest, reader

M = Manifest()
DOC = json.load(open(os.path.join(CHECKOUT, "perfbench", "testdata",
                                  "phases_two_rounds.json")))
TRACE, EXPECT = DOC["trace"], DOC["expect"]
IDLE = sorted(EXPECT["idle_ms_per_round"])
ROW_BASED = sorted(EXPECT["quiet"])


def read(name, rows=None, trace=None):
    spec = M.metric_file(name)
    return reader(spec["reader"])({"rows": rows or [], "trace": trace}, spec)


@pytest.mark.parametrize("name", IDLE)
def test_idle_under_the_programs_spans(name):
    # Idle on the device: [0,10] [90,95] [105,130] [210,212] [222,230] us.
    # Under prepare+block+finish ([2,18], [127,138]): 8 + 3; under fetch
    # ([18,110], [138,222]): 5 + 5 + 2; under row ([111,118], [223,228]):
    # 7 + 5; under none: [0,2] [110,111] [118,127] [222,223] [228,230].
    assert read(name, trace=TRACE) == \
        pytest.approx(EXPECT["idle_ms_per_round"][name])


def test_idle_parts_sum_to_the_devices_idle_time():
    busy, window = T.busy_and_window_s(TRACE)
    parts = [read(name, trace=TRACE) for name in IDLE]
    assert sum(parts) == pytest.approx(1e3 * (window - busy) / 2)
    assert sum(parts) == pytest.approx(EXPECT["idle_total_ms_per_round"])


@pytest.mark.parametrize("name", ROW_BASED)
def test_row_based_metrics_on_a_quiet_window(name):
    assert read(name, rows=DOC["rows_quiet"]) == \
        pytest.approx(EXPECT["quiet"][name], abs=1e-6)


@pytest.mark.parametrize("rows,side", [("rows_wait", "wait"),
                                       ("rows_host", "host")])
def test_a_planted_long_round_is_found_on_its_own_side(rows, side):
    for name, want in EXPECT[side].items():
        assert read(name, rows=DOC[rows]) == pytest.approx(want, abs=1e-6), \
            name


@pytest.mark.parametrize("name", IDLE + ROW_BASED)
def test_a_program_without_the_spans_gives_nothing_and_does_not_raise(name):
    """The parent's rows carry ``training_step`` alone and its trace no
    ``blades/*`` span: every new reader returns ``None``."""
    bare = dict(TRACE, host=[e for e in TRACE["host"]
                             if not e[0].startswith("blades/")])
    assert read(name, rows=DOC["rows_parent"], trace=bare) is None
    assert read(name, rows=[], trace=None) is None
    assert read(name, rows=DOC["rows_quiet"][:1],
                trace={"devices": {}, "host": TRACE["host"]}) is None \
        or name == "setup_build_s"

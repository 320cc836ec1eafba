"""``correct`` is decided by a comparison that has been shown to fail.

One federation at a size a test run can hold (the traffic file's
``rehearsal`` block: ResNet-10 at full width, 8 clients of which 2 forge,
batch 4, float32 compute on the CPU, the bf16 update matrix), through the
harness's own ``run_cell`` with its look for a chip skipped (``rehearse``):

- the program as it stands comes out correct under the cell's real limits;
- the control (the reference put in the program's place, computed in fp8)
  does not;
- nor does a run with the timed path broken underneath, once for each fault
  a one-chip training cell can have: a step that returns its state
  unchanged, half of every batch left out, an aggregate altered where it is
  applied.
"""

import json
import os
import time

import jax
import pytest

from pb import cell as C
from pb import compare, sut
from pb.manifest import CHECKOUT, Manifest

WORKLOAD = "r10_median"
SEED = 2_500_000_033          # over 2**31, as the driver's seeds are
# The rehearsal's sizes, handed over through the environment as a builder
# hands over his own.
TINY = Manifest(CHECKOUT).cell(WORKLOAD)["traffic"]["rehearsal"]["overrides"]


@pytest.fixture(scope="module")
def shared(tmp_path_factory):
    os.environ["PERFBENCH_OUT"] = str(tmp_path_factory.mktemp("pbout"))
    os.environ["PERFBENCH_REHEARSE"] = json.dumps(TINY)
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(CHECKOUT, ".no_data")
    yield {"reference_cache": {}}
    for k in ("PERFBENCH_OUT", "PERFBENCH_REHEARSE"):
        os.environ.pop(k, None)


def _run(shared, spoil=None, trace=False):
    code, result = C.run_cell(CHECKOUT, WORKLOAD, SEED, 0.1, trace,
                              time.perf_counter(), rehearse=True,
                              spoil=spoil,
                              reference_cache=shared["reference_cache"])
    assert code == 0
    return result


def test_sound_run_is_correct_and_its_line_has_the_contract_keys(shared):
    result = _run(shared)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu"   # names what it ran on
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    held = {n: c for n, c in result["compared"].items()
            if c["limit"] is not None}
    assert {"agg1_worst_leaf", "change_worst_leaf", "change_diff"} <= set(held)
    for name, c in held.items():
        assert c["value"] <= c["limit"], name
    rounds = os.listdir(os.path.join(os.environ["PERFBENCH_OUT"], "rounds"))
    rec = json.load(open(os.path.join(os.environ["PERFBENCH_OUT"], "rounds",
                                      rounds[0])))
    assert len(rec["window_round_s"]) == result["attempted"]
    assert len(rec["warmup_round_s"]) == 3
    assert rec["window_compiles"]["backend_compiles"] == 0


def _state_unchanged(cell):
    algo, train = cell.algo, cell.algo.train
    keep = algo.state.server.params

    def broken():
        row = train()
        sut.place_weights(algo, keep)
        return row

    algo.train = broken


def _half_batch(cell):
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["overrides"]["dataset_config"]["train_bs"] //= 2
    config = sut.build_config(
        sut.trial_dict(cell.manifest.checkout, traffic), cell.seed)
    cell.algo.stop()
    cell.algo = sut.build(config, cell.kind, cell.data)
    sut.place_weights(cell.algo, cell.family.init_params(cell.cfg, cell.seed))


def _aggregate_altered(cell):
    algo, train = cell.algo, cell.algo.train

    def broken():
        before = algo.state.server.params
        row = train()
        sut.place_weights(algo, jax.tree.map(
            lambda a, b: b + 1.1 * (a - b), algo.state.server.params,
            before))
        return row

    algo.train = broken


@pytest.mark.parametrize("spoil", [_state_unchanged, _half_batch,
                                   _aggregate_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(shared, spoil):
    result = _run(shared, spoil)
    assert result["correct"] is False
    over = [n for n, c in result["compared"].items()
            if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert over, result["compared"]
    if spoil is _state_unchanged:
        assert result["compared"]["agg1_worst_leaf"]["value"] == \
            pytest.approx(1.0)


@pytest.mark.parametrize("workload,fails", [
    (WORKLOAD, "change_diff"), ("r18_median", "change_energy")])
def test_the_control_in_fp8_is_not_correct(shared, workload, fails):
    cell = C.Cell(Manifest(CHECKOUT), workload, SEED, rehearse=True)
    assert cell.describe["clients"] == 8 and cell.reference_block == 2
    key = (workload, SEED, True)
    ref = shared["reference_cache"].get(key) or cell.follow()
    control = cell.follow(quant="fp8")
    cell.free()
    ok, report = compare.decide(compare.numbers(control, ref), cell.limits)
    assert not ok, report
    # The number that catches it on the chip catches it here too.
    assert fails in [n for n, v, lim in report if lim is not None and v > lim]


def test_without_a_chip_and_without_the_flag_there_is_no_result(capsys):
    code, result = C.run_cell(CHECKOUT, WORKLOAD, 1, 0.1, False,
                              time.perf_counter(), rehearse=False)
    assert code != 0 and result is None
    assert "never a measurement" in capsys.readouterr().err

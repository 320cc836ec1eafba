"""Sweep / CLI tests (model: blades/train.py behavior, SURVEY.md §2.1)."""

import json
from pathlib import Path

import pytest
import yaml

from blades_tpu.tune import expand_grid, load_experiments_from_file, run_experiments


def test_expand_grid_no_grids():
    cfg = {"a": 1, "b": {"c": 2}}
    assert expand_grid(cfg) == [cfg]


def test_expand_grid_cartesian_product():
    cfg = {
        "x": {"grid_search": [1, 2]},
        "nested": {"y": {"grid_search": ["a", "b", "c"]}},
        "fixed": 0,
    }
    trials = expand_grid(cfg)
    assert len(trials) == 6
    assert {(t["x"], t["nested"]["y"]) for t in trials} == {
        (i, s) for i in (1, 2) for s in "abc"
    }
    assert all(t["fixed"] == 0 for t in trials)


def test_expand_grid_dict_values():
    cfg = {"agg": {"grid_search": [{"type": "Mean"}, {"type": "Median"}]}}
    trials = expand_grid(cfg)
    assert [t["agg"]["type"] for t in trials] == ["Mean", "Median"]


def test_load_experiments_requires_run(tmp_path):
    f = tmp_path / "bad.yaml"
    f.write_text(yaml.safe_dump({"exp": {"config": {}}}))
    with pytest.raises(ValueError, match="run"):
        load_experiments_from_file(str(f))


def test_run_experiments_end_to_end(tmp_path):
    experiments = {
        "smoke": {
            "run": "FEDAVG",
            "stop": {"training_iteration": 6},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 6, "train_bs": 16},
                "global_model": "mlp",
                "evaluation_interval": 3,
                "server_config": {"lr": 1.0,
                                  "aggregator": {"grid_search": [
                                      {"type": "Mean"}, {"type": "Median"}]}},
            },
        }
    }
    summaries = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0, checkpoint_at_end=True
    )
    assert len(summaries) == 2  # aggregator grid
    for s in summaries:
        tdir = Path(s["dir"])
        lines = (tdir / "result.json").read_text().strip().splitlines()
        assert len(lines) == 6
        last = json.loads(lines[-1])
        assert last["training_iteration"] == 6
        assert "test_acc" in last
        assert (tdir / "ckpt_final" / "algorithm_state.pkl").exists()
        assert (tdir / "params.json").exists()
        assert s["best_test_acc"] > 0.3


def test_cli_file_command(tmp_path):
    from blades_tpu.train import main

    exp = {
        "cli_smoke": {
            "run": "FEDAVG",
            "stop": {"training_iteration": 3},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 4, "train_bs": 8},
                "global_model": "mlp",
                "evaluation_interval": 3,
                "server_config": {"lr": 1.0},
            },
        }
    }
    f = tmp_path / "exp.yaml"
    f.write_text(yaml.safe_dump(exp))
    rc = main(["file", str(f), "--storage-path", str(tmp_path / "out")])
    assert rc == 0
    assert (tmp_path / "out" / "cli_smoke").exists()


_TUNED_EXAMPLES = Path(__file__).parent.parent / "blades_tpu" / "tuned_examples"


@pytest.mark.parametrize(
    "yaml_name", sorted(p.name for p in _TUNED_EXAMPLES.glob("*.yaml")))
def test_tuned_examples_parse_and_expand(yaml_name):
    """Every shipped YAML grid must load and expand (the reference's
    tuned_examples are its canonical envelope, SURVEY.md §6), and every
    trial of it must pass the config's own gates: no unknown key, no
    refused pair."""
    from blades_tpu.algorithms import get_algorithm_class

    exps = load_experiments_from_file(str(_TUNED_EXAMPLES / yaml_name))
    assert exps
    for spec in exps.values():
        trials = expand_grid(spec["config"])
        assert len(trials) >= 1
        for trial in trials:
            _, config = get_algorithm_class(spec["run"], return_config=True)
            config.update_from_dict(trial)
            config.validate()


def test_tuned_examples_are_all_there():
    assert len(list(_TUNED_EXAMPLES.glob("*.yaml"))) >= 5


def test_a_round_row_is_on_disk_before_its_checkpoint(tmp_path, monkeypatch):
    """The sweep's one loop: train(), write the row, then the checkpoint.
    At every checkpoint save, result.json as another reader finds it on
    disk already ends with the round the checkpoint covers."""
    from blades_tpu.faults import host

    seen = []
    real = host.atomic_checkpoint

    def spying(save_fn, path):
        rows = (Path(path).parent / "result.json").read_text().splitlines()
        seen.append((Path(path).name,
                     [json.loads(r)["training_iteration"] for r in rows]))
        return real(save_fn, path)

    monkeypatch.setattr(host, "atomic_checkpoint", spying)
    [s] = run_experiments(_resume_experiments(6), storage_path=str(tmp_path),
                          verbose=0, cost_analysis=False, checkpoint_freq=2)
    assert s["rounds"] == 6 and "scan_window" not in s
    assert seen == [("ckpt_000002", [1, 2]),
                    ("ckpt_000004", [1, 2, 3, 4]),
                    ("ckpt_000006", [1, 2, 3, 4, 5, 6])]


def _resume_experiments(rounds):
    return {
        "resumable": {
            "run": "FEDAVG",
            "stop": {"training_iteration": rounds},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 4, "train_bs": 8},
                "global_model": "mlp",
                "evaluation_interval": 2,
                "server_config": {"lr": 1.0},
            },
        }
    }


def test_sweep_resume_kill_and_rerun(tmp_path):
    """The reference CLI's --restore/resume semantics (ref: blades/
    train.py:154,228): a killed grid continues from checkpoints without
    redoing finished trials."""
    # Phase 1: "killed" after 4 of 8 rounds (checkpoint every 2).
    run_experiments(_resume_experiments(4), storage_path=str(tmp_path),
                    verbose=0, checkpoint_freq=2)
    tdir = tmp_path / "resumable" / "resumable_00000"
    assert (tdir / "ckpt_000004").exists()

    # Phase 2: resume to 8 rounds — must restore from round 4, not restart.
    [s] = run_experiments(_resume_experiments(8), storage_path=str(tmp_path),
                          verbose=0, checkpoint_freq=2, resume=True)
    assert s["resumed"] == "from round 4"
    assert s["rounds"] == 8
    lines = (tdir / "result.json").read_text().strip().splitlines()
    iters = [json.loads(ln)["training_iteration"] for ln in lines]
    assert iters == [1, 2, 3, 4, 5, 6, 7, 8]  # appended, no rework

    # Phase 3: rerun — the finished trial is skipped untouched.
    mtime = (tdir / "result.json").stat().st_mtime
    [s2] = run_experiments(_resume_experiments(8), storage_path=str(tmp_path),
                           verbose=0, resume=True)
    assert s2["resumed"] == "skipped"
    assert s2["rounds"] == 8
    assert (tdir / "result.json").stat().st_mtime == mtime


def test_sweep_checkpoint_keep_num(tmp_path):
    run_experiments(_resume_experiments(8), storage_path=str(tmp_path),
                    verbose=0, checkpoint_freq=2, checkpoint_keep_num=2)
    tdir = tmp_path / "resumable" / "resumable_00000"
    kept = sorted(p.name for p in tdir.glob("ckpt_*"))
    assert kept == ["ckpt_000006", "ckpt_000008"]


def test_centralized_benchmark_smoke(capsys):
    """The standalone centralized baseline (benchmarks/main.py, ref:
    blades/benchmarks/main.py) runs end-to-end on a tiny config."""
    from blades_tpu.benchmarks.main import main

    rc = main(["--model", "mlp", "--dataset", "mnist", "--epochs", "1",
               "--batch-size", "32"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "test_acc" in out

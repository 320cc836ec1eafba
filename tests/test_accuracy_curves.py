"""The accuracy-curve harness (VERDICT r1 #10): one command -> PNG + JSON."""

import json

import pytest


@pytest.mark.slow  # full curve-harness sweep (~20 s; the harness is also driven by the resume test in the slow lane)
def test_accuracy_curves_one_command(tmp_path):
    from blades_tpu.benchmarks.accuracy_curves import main

    rc = main([
        "--dataset", "mnist", "--rounds", "6", "--num-clients", "8",
        "--aggregators", "Mean", "Median", "--malicious", "0", "2",
        "--out", str(tmp_path),
    ])
    assert rc == 0
    table = json.loads((tmp_path / "curves.json").read_text())
    assert len(table["rows"]) == 4
    assert "SYNTHETIC" in table["source"]  # no raw files in CI
    for row in table["rows"]:
        assert row["rounds"] == 6
        assert 0.0 <= row["final_test_acc"] <= 1.0
    # "complete" means the full REFERENCE grid (9 aggregators x 0-30%),
    # which this 2x2 smoke run is NOT; "planned_complete" tracks the
    # invocation's own rows (VERDICT r4 weak #6).
    assert table["planned_complete"] is True
    assert table["complete"] is False
    assert "Centeredclipping@0" in table["reference_cells_missing"]
    png = (tmp_path / "curves.png").read_bytes()
    assert png[:8] == b"\x89PNG\r\n\x1a\n"


@pytest.mark.slow  # second full grid run (~16 s; the one-command path stays tier-1)
def test_resume_from_completes_a_grid(tmp_path):
    """--resume-from seeds prior cells, skips them, and the stitched
    table/plot cover the union (the mechanism for completing the IPM
    grids to the reference matrix without re-running finished cells)."""
    from blades_tpu.benchmarks.accuracy_curves import main

    first = tmp_path / "a"
    rc = main(["--dataset", "mnist", "--rounds", "4", "--num-clients", "8",
               "--aggregators", "Mean", "--malicious", "0", "2",
               "--out", str(first)])
    assert rc == 0

    second = tmp_path / "b"
    rc = main(["--dataset", "mnist", "--rounds", "4", "--num-clients", "8",
               "--aggregators", "Mean", "Median", "--malicious", "0", "2",
               "--out", str(second),
               "--resume-from", str(first / "curves.json")])
    assert rc == 0
    table = json.loads((second / "curves.json").read_text())
    cells = {(r["aggregator"], r["num_malicious"]) for r in table["rows"]}
    assert cells == {("Mean", 0), ("Mean", 2), ("Median", 0), ("Median", 2)}
    assert table["planned_complete"] is True
    # Seeded cells were not re-run: their results carry over verbatim.
    prior = json.loads((first / "curves.json").read_text())["rows"]
    for r in prior:
        assert r in table["rows"]

    # A mismatched configuration refuses to stitch.
    import pytest

    with pytest.raises(SystemExit, match="mismatch"):
        main(["--dataset", "mnist", "--rounds", "6", "--num-clients", "8",
              "--aggregators", "Mean", "--malicious", "0",
              "--out", str(tmp_path / "c"),
              "--resume-from", str(first / "curves.json")])


def test_synthetic_heterogeneity_widens_benign_spread():
    """The per-client drift dial must actually widen the benign update
    spread (the mechanism VERDICT r4 #3 asks for): with h > 0 the
    per-client class-conditional means differ, so client gradients
    disagree more — measured here directly on the data: the
    across-client dispersion of per-class feature means grows, while
    h=0 reproduces the historical generator bit-for-bit."""
    import numpy as np

    from blades_tpu.data import DatasetCatalog

    base = DatasetCatalog.get_dataset(
        {"type": "cifar10", "synthetic_noise": 3.0}, num_clients=12, seed=3)
    het = DatasetCatalog.get_dataset(
        {"type": "cifar10", "synthetic_noise": 3.0,
         "synthetic_heterogeneity": 2.0}, num_clients=12, seed=3)
    zero = DatasetCatalog.get_dataset(
        {"type": "cifar10", "synthetic_noise": 3.0,
         "synthetic_heterogeneity": 0.0}, num_clients=12, seed=3)

    assert base.synthetic and het.synthetic
    # h=0 is exactly the historical generator.
    np.testing.assert_array_equal(base.train.x, zero.train.x)
    np.testing.assert_array_equal(base.train.y, zero.train.y)
    # Labels (the Dirichlet/IID partition) are untouched by h.
    np.testing.assert_array_equal(base.train.y, het.train.y)
    np.testing.assert_array_equal(base.train.lengths, het.train.lengths)

    def class_mean_dispersion(part):
        # Per-COORDINATE across-client std of each class's per-client
        # mean vector (a scalar all-coordinate mean would cancel the
        # zero-mean directional shifts), averaged over coords + classes.
        disps = []
        for c in range(10):
            per_client = []
            for i in range(part.num_clients):
                n = int(part.lengths[i])
                yi, xi = part.y[i, :n], part.x[i, :n]
                if (yi == c).any():
                    per_client.append(
                        xi[yi == c].reshape(-1, xi[0].size).mean(axis=0))
            if len(per_client) >= 2:
                disps.append(np.std(np.stack(per_client), axis=0).mean())
        return float(np.mean(disps))

    assert class_mean_dispersion(het.train) > \
        3.0 * class_mean_dispersion(base.train)

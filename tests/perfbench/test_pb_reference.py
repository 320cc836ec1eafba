"""The reference's own aggregation, against numpy on a small matrix: what
the forge and the two defenses compute, chunk seams included."""

import numpy as np
import pytest

from pb import reference


def _federation(kind, n=12, f=3):
    return {"num_clients": n, "num_malicious_clients": f,
            "aggregator": {"type": kind}}


def _rows(fed, benign):
    z = reference.alie_z(fed["num_clients"], fed["num_malicious_clients"])
    forged = benign.mean(0) + z * benign.std(0, ddof=1)
    return np.concatenate(
        [np.tile(forged, (fed["num_malicious_clients"], 1)), benign])


@pytest.fixture(scope="module")
def benign():
    rng = np.random.default_rng(5)
    return rng.normal(size=(9, 1000)).astype(np.float32)


def test_alie_z_is_the_inverse_cdf_of_the_papers_share():
    # n=12, f=3: s = 12 // 2 + 1 - 3 = 4, cdf = (9 - 4) / 9
    from statistics import NormalDist

    assert reference.alie_z(12, 3) == pytest.approx(
        NormalDist().inv_cdf(5 / 9))


def test_median_of_benign_and_forged_rows(benign):
    fed = _federation("Median")
    got = np.asarray(reference.aggregate(benign, fed, chunk=384))
    rows = np.sort(_rows(fed, benign), axis=0)
    want = (rows[5] + rows[6]) / 2        # 12 rows: the two central ones
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_geomed_is_weiszfeld_from_the_mean(benign):
    fed = _federation("GeoMed")
    info = {}
    got = np.asarray(reference.aggregate(benign, fed, chunk=384, info=info))
    rows = _rows(fed, benign).astype(np.float64)
    med = rows.mean(0)
    for _ in range(info["geomed_steps"][0]):
        w = 1.0 / np.maximum(np.linalg.norm(rows - med, axis=1), 1e-6)
        med = (w[:, None] * rows).sum(0) / w.sum()
    np.testing.assert_allclose(got, med, rtol=1e-4, atol=1e-5)
    assert 1 <= info["geomed_steps"][0] <= 100


def test_an_unknown_defense_has_no_reference(benign):
    with pytest.raises(ValueError):
        reference.aggregate(benign, _federation("Multikrum"))

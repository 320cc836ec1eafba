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


# -- the comparison holds one leaf at a time, and reads what it read --------


def _old_leaves(tree, path=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _old_leaves(tree[k], path + (k,))
    else:
        yield "/".join(path), np.asarray(tree, np.float64)


def _old_leaf_norms(after, before):
    b = dict(_old_leaves(before))
    return {k: float(np.linalg.norm(a - b[k]))
            for k, a in _old_leaves(after)}


def _old_diff_share(prog_after, ref_after, before_prog, before_ref):
    p0, r0 = dict(_old_leaves(before_prog)), dict(_old_leaves(before_ref))
    p1 = dict(_old_leaves(prog_after))
    num = den = 0.0
    for k, r1 in _old_leaves(ref_after):
        step = r1 - r0[k]
        num += float(np.sum(np.square((p1[k] - p0[k]) - step)))
        den += float(np.sum(np.square(step)))
    gap = np.sqrt(num / max(den, 1e-300))
    return float(gap) if np.isfinite(gap) else float("inf")


def _old_numbers(prog, ref):
    """``compare.numbers`` as it stood at PR 27: every tree turned into a
    dict of float64 leaves first."""
    from pb import compare

    out = {}
    for k in range(len(ref["losses"])):
        p, r = float(prog["losses"][k]), float(ref["losses"][k])
        gap = abs(p - r) / abs(r)
        out[f"loss_r{k + 1}"] = gap if np.isfinite(gap) else float("inf")
    a_prog = _old_leaf_norms(prog["params"][0], prog["params0"])
    a_ref = _old_leaf_norms(ref["params"][0], ref["params0"])
    out["agg1_worst_leaf"], where_a = compare.worst_leaf_gap(a_prog, a_ref)
    med = float(np.median(list(a_ref.values())))
    still = [k for k, v in a_ref.items() if v < 1e-3 * med]
    c_prog = _old_leaf_norms(prog["params"][-1], prog["params0"])
    c_ref = _old_leaf_norms(ref["params"][-1], ref["params0"])
    out["change_worst_leaf"], where_c = compare.worst_leaf_gap(
        c_prog, c_ref, still)
    out["agg1_diff"] = _old_diff_share(prog["params"][0], ref["params"][0],
                                       prog["params0"], ref["params0"])
    out["change_diff"] = _old_diff_share(prog["params"][-1], ref["params"][-1],
                                         prog["params0"], ref["params0"])
    out["_where"] = {"agg1_worst_leaf": where_a,
                     "change_worst_leaf": where_c, "left_out": still}
    return out


def _side(rng, shapes, start, noise):
    def tree(scale):
        return {m: {k: (start[m][k] + scale * rng.normal(size=s)).astype(
            np.float32) for k, s in leaves.items()}
            for m, leaves in shapes.items()}

    return {"losses": list(2.3 - 0.1 * rng.random(3) * noise),
            "params0": start, "params": [tree(0.01 * noise),
                                         tree(0.03 * noise)]}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_streamed_comparison_reads_the_whole_tree_one_to_the_last_bit(
        seed):
    from pb import compare

    rng = np.random.default_rng(seed)
    shapes = {"Conv_0": {"kernel": (3, 3, 3, 16)},
              "Block_1": {"scale": (16,), "bias": (16,)},
              "Dense_0": {"kernel": (16, 10), "bias": (10,)}}
    start = {m: {k: rng.normal(size=s).astype(np.float32)
                 for k, s in leaves.items()} for m, leaves in shapes.items()}
    ref = _side(rng, shapes, start, 1.0)
    prog = _side(rng, shapes, start, 1.02)
    # A leaf the reference all but leaves alone is left out of the change.
    for side in (ref, prog):
        for after in side["params"]:
            after["Block_1"]["bias"] = start["Block_1"]["bias"] + np.float32(
                1e-9)
    got, want = compare.numbers(prog, ref), _old_numbers(prog, ref)
    # PR 28 added one number, the square of another; the rest read as before.
    assert got.pop("change_energy") == got["change_diff"] ** 2
    assert got == want                      # floats compared bit for bit
    assert list(got) == list(want)
    assert got["_where"]["left_out"] == ["Block_1/bias"]


def test_trees_that_differ_are_not_compared():
    from pb import compare

    with pytest.raises(ValueError):
        compare.leaf_norms({"a": np.ones(3), "b": np.ones(3)},
                           {"a": np.ones(3)})
    with pytest.raises(ValueError):
        compare.leaf_norms({"a": np.ones(3)}, {"b": np.ones(3)})

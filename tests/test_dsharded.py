"""d-sharded (all-to-all) giant-federation round tests on the 8-device
CPU mesh — exactness vs the all_gather formulation (SURVEY.md §7.3).

The d-sharded path must cover the FULL aggregator suite (all 10) and the
full adversary suite: every combination here compares end-round server
params against :func:`shard_map_step` (same keys -> same local training,
so any difference is aggregation/forging math).

Tier-2 (``slow``): the 33 aggregator x adversary combinations each
compile an 8-virtual-device shard_map program — minutes of wall clock on
a 2-core CPU host, far past the tier-1 budget.  Tier-1 keeps a d-sharded
end-to-end signal via ``test_faults.py``'s d-sharded health-check round.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.adversaries import get_adversary, make_malicious_mask
from blades_tpu.algorithms import get_algorithm_class
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.parallel import make_mesh, shard_federation, shard_map_step
from blades_tpu.ops import layout as L
from blades_tpu.parallel.dsharded import dsharded_step
from blades_tpu.utils.tree import ravel_fn

pytestmark = pytest.mark.slow

N = 16
F = 4

ALL_AGGREGATORS = [
    "Mean", "Median", "Trimmedmean", "GeoMed", "DnC", "Multikrum",
    "Centeredclipping", "Signguard", "Clippedclustering", "FLTrust",
]


def make_fr(aggregator, adversary=None, server_kwargs=None):
    task = TaskSpec(model="mlp", lr=0.1, input_shape=(28, 28, 1)).build()
    server = Server.from_config(aggregator=aggregator, num_byzantine=F, lr=1.0,
                                **(server_kwargs or {}))
    adv = get_adversary(adversary, num_clients=N, num_byzantine=F) if adversary else None
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=8)
    if aggregator == "FLTrust":
        rng = np.random.default_rng(7)
        tx = jnp.asarray(rng.normal(size=(32, 28, 28, 1)), jnp.float32)
        ty = jnp.asarray(rng.integers(0, 10, size=(32,)), jnp.int32)
        fr = dataclasses.replace(fr, trusted_data=(tx, ty))
    return fr


@pytest.fixture(scope="module")
def data():
    from blades_tpu.data import DatasetCatalog

    ds = DatasetCatalog.get_dataset("mnist", num_clients=N)
    return (
        jnp.array(ds.train.x), jnp.array(ds.train.y), jnp.array(ds.train.lengths),
        make_malicious_mask(N, F),
    )


def run_both_paths(fr, data, key=42, rounds=1):
    x, y, ln, mal = data
    mesh = make_mesh()
    results = []
    for step_fn in (shard_map_step, dsharded_step):
        st = fr.init(jax.random.PRNGKey(0), N)
        st, (xs, ys, lns, mals) = shard_federation(mesh, st, (x, y, ln, mal))
        step = step_fn(fr, mesh)
        for r in range(rounds):
            st, m = step(st, xs, ys, lns, mals,
                         jax.random.fold_in(jax.random.PRNGKey(key), r))
        results.append((st, m))
    return results


def assert_paths_match(fr, data, tol=2e-5, rounds=1):
    (st_a, m_a), (st_b, m_b) = run_both_paths(fr, data, rounds=rounds)
    ravel, _, _ = ravel_fn(st_a.server.params)
    np.testing.assert_allclose(
        np.asarray(ravel(st_a.server.params)),
        np.asarray(ravel(st_b.server.params)), atol=tol, rtol=1e-3,
    )
    np.testing.assert_allclose(float(m_a["train_loss"]), float(m_b["train_loss"]),
                               rtol=1e-5)


def test_psum_pairwise_matches_dense():
    mesh = make_mesh()
    rows = jax.random.normal(jax.random.PRNGKey(0), (6, 64))

    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    shard = L.ShardInfo(axis="clients", num_shards=8, global_d=64, width=8)

    @partial(shard_map, mesh=mesh, in_specs=(P(None, "clients"),),
             out_specs=P(), check_vma=False)
    def sharded(rows_shard):
        return L.pairwise_sq_dists(rows_shard, shard)

    d2 = sharded(rows)
    dense = ((rows[:, None, :] - rows[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(dense), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("aggregator", ALL_AGGREGATORS)
def test_dsharded_matches_gather_path(data, aggregator):
    fr = make_fr(aggregator, adversary="ALIE")
    # Same keys -> same local training; aggregation math must agree up to
    # float reassociation (GeoMed: fixed iters vs early-stop tolerance).
    tol = 2e-3 if aggregator == "GeoMed" else 2e-5
    assert_paths_match(fr, data, tol=tol)


@pytest.mark.parametrize("aggregator", ["Centeredclipping", "Clippedclustering"])
def test_dsharded_stateful_aggregator_state_matches(data, aggregator):
    """Multi-round: the threaded aggregator state (momentum / norm history)
    must evolve identically on both paths — and stays layout-compatible
    (replicated), so checkpoints are interchangeable."""
    fr = make_fr(aggregator, adversary="IPM")
    (st_a, _), (st_b, _) = run_both_paths(fr, data, rounds=3)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3
        ),
        st_a.server.agg_state, st_b.server.agg_state,
    )


# The VERDICT r1 landmine: SignGuard-evading attacks negate the GLOBAL
# first half of the coordinate axis — per-shard local negation would be a
# different attack.  These combinations force that code path.
@pytest.mark.parametrize("adversary,aggregator", [
    ("ALIE", "Signguard"),          # _negate_first_half under sharding
    ("MinMax", "Signguard"),        # psum'd distances + negate
    ("MinMax", "Median"),           # psum'd distances, no negate
    ("Adaptive", "Trimmedmean"),    # global-width uniform draw, sliced
    ("SignGuard", "Signguard"),     # psum'd sign census + global perm
    ("Attackclippedclustering", "Clippedclustering"),  # psum'd cosine geometry
    ("IPM", "Multikrum"),
])
def test_dsharded_adversaries_match_gather_path(data, adversary, aggregator):
    fr = make_fr(aggregator, adversary=adversary)
    assert_paths_match(fr, data, tol=5e-5)


def test_dsharded_noise_adversary_runs(data):
    """Noise draws are i.i.d. per layout (keys fold the shard index), so
    paths are not bit-equal — both must still train finite."""
    fr = make_fr("Median", adversary="Noise")
    (_, m_a), (_, m_b) = run_both_paths(fr, data)
    assert np.isfinite(float(m_a["train_loss"]))
    assert np.isfinite(float(m_b["train_loss"]))


def test_dsharded_full_server_optimizer_matches(data):
    """momentum + weight decay + LR schedule: the d-sharded server step is
    the identical replicated optax program (round-1 restricted this path
    to plain SGD)."""
    fr = make_fr("Median", adversary="ALIE", server_kwargs=dict(
        momentum=0.9, weight_decay=1e-4,
        lr_schedule_points=[[0, 1.0], [2, 0.1]],
    ))
    assert_paths_match(fr, data, rounds=3, tol=5e-5)


def test_elision_client_order_layout():
    from blades_tpu.parallel.dsharded import elision_client_order

    # Even split: every chip [1 malicious | 1 benign].
    order = elision_client_order(16, 8, 8)
    mal = np.arange(16) < 8  # canonical prefix mask
    m = mal[order].reshape(8, 2)
    assert m[:, 0].all() and not m[:, 1:].any()
    assert sorted(order.tolist()) == list(range(16))

    # Remainder: f=10 over 8 chips -> fl=1 everywhere, the 2 leftover
    # malicious clients train in the first chips' tails.
    order = elision_client_order(32, 10, 8)
    m = (np.arange(32) < 10)[order].reshape(8, 4)
    assert m[:, 0].all()              # every elided prefix is malicious
    assert m[:, 1:].sum() == 2        # the remainder trains in tails
    assert sorted(order.tolist()) == list(range(32))

    with pytest.raises(ValueError, match="divide"):
        elision_client_order(17, 8, 8)


@pytest.mark.parametrize("aggregator,adversary", [
    ("Median", "ALIE"),
    ("GeoMed", "IPM"),
    ("Signguard", "MinMax"),
])
def test_dsharded_elision_is_exact(data, aggregator, adversary):
    """Skipping the dead malicious-lane training on the strided layout
    must reproduce the full d-sharded round bit-for-bit: forged rows
    come from benign statistics only and replace whatever the malicious
    lanes trained.  F=8 over the 8-chip mesh -> one elided lane per
    chip (f < n_dev would elide nothing)."""
    from blades_tpu.parallel.dsharded import elision_client_order

    F = 8
    x, y, ln, _ = data
    order = jnp.asarray(elision_client_order(N, F, 8))
    mal = (jnp.arange(N) < F)[order]
    x, y, ln = x[order], y[order], ln[order]
    mesh = make_mesh()
    fr = make_fr(aggregator, adversary=adversary)
    key = jax.random.PRNGKey(23)

    results = []
    for prefix in (None, F):
        st = fr.init(jax.random.PRNGKey(0), N)
        st, (xs, ys, lns, mals) = shard_federation(mesh, st, (x, y, ln, mal))
        step = dsharded_step(fr, mesh, malicious_prefix=prefix)
        for r in range(2):
            st, m = step(st, xs, ys, lns, mals, jax.random.fold_in(key, r))
        results.append((st, m))
    (st_a, m_a), (st_b, m_b) = results
    for a, b in zip(jax.tree.leaves(st_a.server.params),
                    jax.tree.leaves(st_b.server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_array_equal(np.asarray(m_a[k]), np.asarray(m_b[k]))
    # Elision telemetry (VERDICT item 6): floor(F/n_dev) lanes elided on
    # each of the 8 chips; the non-elided round carries no such key.
    assert int(m_b["elided_lanes"]) == (F // 8) * 8
    assert "elided_lanes" not in m_a


def test_dsharded_elision_ignored_for_training_attacks(data):
    """SignFlip trains for real — the prefix hint must not skip it."""
    from blades_tpu.parallel.dsharded import _build_dsharded_body

    fr = make_fr("Mean", adversary="SignFlip")
    body = _build_dsharded_body(fr, make_mesh(), malicious_prefix=8)
    assert body.f_local == 0  # gate: no update forge -> no elision
    # The forging counterpart DOES elide at the same prefix.
    fr2 = make_fr("Median", adversary="ALIE")
    assert _build_dsharded_body(fr2, make_mesh(),
                                malicious_prefix=8).f_local == 1


def test_dsharded_elision_validates_mask(data):
    x, y, ln, _ = data
    mesh = make_mesh()
    fr = make_fr("Median", adversary="ALIE")
    st = fr.init(jax.random.PRNGKey(0), N)
    bad_mask = jnp.arange(N) < 8  # contiguous prefix, NOT strided
    st, (xs, ys, lns, mals) = shard_federation(mesh, st, (x, y, ln, bad_mask))
    step = dsharded_step(fr, mesh, malicious_prefix=8)
    with pytest.raises(ValueError, match="elision"):
        step(st, xs, ys, lns, mals, jax.random.PRNGKey(1))


def test_dsharded_elision_through_config():
    """The Fedavg driver auto-applies the strided layout + elision for a
    forging adversary on execution='dsharded'."""
    _, cfg = get_algorithm_class("FEDAVG", return_config=True)
    cfg.update_from_dict({
        "dataset_config": {"type": "mnist", "num_clients": 16, "train_bs": 8},
        "global_model": "mlp",
        "evaluation_interval": 2,
        "execution": "dsharded",
        "num_malicious_clients": 8,
        "adversary_config": {"type": "ALIE"},
        "server_config": {"lr": 1.0, "aggregator": {"type": "Median"}},
    })
    cfg.resources(num_devices=8)
    algo = cfg.build()
    # The mask is strided per chip: [1 malicious | 1 benign] x 8.
    m = np.asarray(algo.malicious).reshape(8, 2)
    assert m[:, 0].all() and not m[:, 1].any()
    r = algo.train()
    assert np.isfinite(r["train_loss"])
    assert 0.0 <= algo.evaluate()["test_acc"] <= 1.0


def test_checkpoint_realigns_client_state_across_layouts(tmp_path):
    """A checkpoint saved in natural client order (dense run) resumed
    on the d-sharded elision layout must remap per-client optimizer
    state to the permuted rows — not silently pair client i's momentum
    with client j's data."""
    from blades_tpu.parallel.dsharded import elision_client_order

    def build(execution, num_devices=None):
        _, cfg = get_algorithm_class("FEDAVG", return_config=True)
        cfg.update_from_dict({
            "dataset_config": {"type": "mnist", "num_clients": 16,
                               "train_bs": 8},
            "global_model": "mlp",
            "evaluation_interval": 100,
            "execution": execution,
            "num_malicious_clients": 8,
            "adversary_config": {"type": "ALIE"},
            "client_config": {"lr": 0.1, "momentum": 0.9},
            "server_config": {"lr": 1.0, "aggregator": {"type": "Median"}},
        })
        if num_devices:
            cfg.resources(num_devices=num_devices)
        return cfg.build()

    a = build("dense")
    a.train()  # client momentum becomes client-distinct
    ckpt = a.save_checkpoint(str(tmp_path))

    b = build("dsharded", num_devices=8)
    b.load_checkpoint(ckpt)
    order = elision_client_order(16, 8, 8)
    for src, dst in zip(jax.tree.leaves(a.state.client_opt),
                        jax.tree.leaves(b.state.client_opt)):
        np.testing.assert_array_equal(np.asarray(src)[order],
                                      np.asarray(dst))
    # And the realigned state trains on.
    r = b.train()
    assert np.isfinite(r["train_loss"])


def test_dsharded_trains_under_attack(data):
    x, y, ln, mal = data
    mesh = make_mesh()
    fr = make_fr("Median", adversary="IPM")
    st = fr.init(jax.random.PRNGKey(0), N)
    st, (x, y, ln, mal) = shard_federation(mesh, st, (x, y, ln, mal))
    step = dsharded_step(fr, mesh)
    losses = []
    for r in range(10):
        st, m = step(st, x, y, ln, mal, jax.random.fold_in(jax.random.PRNGKey(5), r))
        losses.append(float(m["train_loss"]))
    assert losses[-1] < losses[0]
    assert int(m["round"]) == 10

"""Pod-scale hierarchical round (ISSUE 18): (clients, d) mesh tests.

The headline tier-1 contract is the one :mod:`blades_tpu.parallel.hier`
pins in its docstring: with ``bucket_size=1`` the hierarchical round is
**bit-identical** to the single-chip dense ``FedRound.step`` — same
batches, same local rounds, same forging, same defense — so the
robustness grid below asserts EXACT equality (tolerance zero), not
allclose.  The ICI reconciliation test checks the trace-time recorder
against :mod:`blades_tpu.parallel.comm_model` in both directions, event
by event, and the 10k-registered-client test is the scaled acceptance
run on the 8 virtual CPU devices.

Budget note: the mesh compiles here ride tier-1 deliberately (the ISSUE
18 acceptance runs the hierarchical path on the CPU tier-1 box); every
federation is kept tiny (MLP(8, 8) on 4x4x1 inputs, d = a few hundred)
and dense/hier trajectories are cached per config so each program
compiles exactly once.  check_tier1_budget.py audits the wall clock.
The full 10-aggregator zoo is slow-marked and rides tier 2.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from blades_tpu.adversaries import get_adversary, make_malicious_mask
from blades_tpu.algorithms import FedavgConfig
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.models.mlp import MLP
from blades_tpu.ops.preagg import (
    bucket_count,
    bucket_representatives,
    nnm_representatives,
)
from blades_tpu.parallel.comm_model import hier_round_volumes, hier_wire_bytes
from blades_tpu.parallel.hier import hier_kept_counts
from blades_tpu.utils.tree import ravel_fn

N_CLIENTS = 8
N_BYZ = 2
ROWS = 4
SHAPE = (4, 4, 1)
MESH_2D = (4, 2)  # exercises the two-phase (clients, d) gather


def _tiny_round(agg="Median", attack="ALIE", n=N_CLIENTS, f=N_BYZ, seed=0):
    """A raw FedRound on the tiny synthetic task (d = 226 params)."""
    task = TaskSpec(model=MLP(hidden1=8, hidden2=8, num_classes=2),
                    num_classes=2, input_shape=SHAPE, lr=0.1).build()
    server = Server.from_config(aggregator=agg, num_byzantine=f or None,
                                lr=0.5)
    adv = (get_adversary(attack, num_clients=n, num_byzantine=f)
           if attack is not None else None)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=2,
                  num_batches_per_round=1, num_clients=n)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, ROWS) + SHAPE), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(n, ROWS)), jnp.int32)
    lengths = jnp.full((n,), ROWS, jnp.int32)
    mal = make_malicious_mask(n, f)
    return fr, (x, y, lengths, mal)


def _run_dense(fr, data, rounds):
    """Single-chip dense trajectory: (losses, final server params)."""
    x, y, lengths, mal = data
    state = fr.init(jax.random.PRNGKey(0), N_CLIENTS)
    step = jax.jit(fr.step)
    losses = []
    for r in range(rounds):
        state, m = step(state, x, y, lengths, mal,
                        jax.random.fold_in(jax.random.PRNGKey(9), r))
        losses.append(float(m["train_loss"]))
    return losses, jax.tree.map(np.asarray, state.server.params)


def _run_hier(fr, data, rounds, *, mesh_shape=MESH_2D, preagg="bucket",
              bucket_size=1):
    """Hierarchical trajectory on the 2-D mesh.

    Returns ``(losses, params, recorder, last_metrics)``.
    """
    from blades_tpu.parallel import (hier_step, make_mesh,
                                     replicated_sharding, shard_federation)

    x, y, lengths, mal = data
    mesh = make_mesh(num_devices=int(np.prod(mesh_shape)),
                     mesh_shape=mesh_shape)
    state = fr.init(jax.random.PRNGKey(0), N_CLIENTS)
    state, (x, y, lengths) = shard_federation(mesh, state, (x, y, lengths))
    mal = jax.device_put(mal, replicated_sharding(mesh))
    step, rec = hier_step(fr, mesh, preagg=preagg, bucket_size=bucket_size)
    losses, m = [], None
    for r in range(rounds):
        state, m = step(state, x, y, lengths, mal,
                        jax.random.fold_in(jax.random.PRNGKey(9), r))
        losses.append(float(m["train_loss"]))
    return (losses, jax.tree.map(np.asarray, state.server.params), rec,
            {k: np.asarray(v) for k, v in m.items()})


_DENSE_CACHE = {}
_HIER_CACHE = {}


def _dense(agg, attack, rounds=2):
    key = (agg, attack, rounds)
    if key not in _DENSE_CACHE:
        fr, data = _tiny_round(agg, attack)
        _DENSE_CACHE[key] = _run_dense(fr, data, rounds)
    return _DENSE_CACHE[key]


def _hier(agg, attack, rounds=2, *, mesh_shape=MESH_2D, preagg="bucket",
          bucket_size=1):
    key = (agg, attack, rounds, mesh_shape, preagg, bucket_size)
    if key not in _HIER_CACHE:
        fr, data = _tiny_round(agg, attack)
        _HIER_CACHE[key] = _run_hier(fr, data, rounds, mesh_shape=mesh_shape,
                                     preagg=preagg, bucket_size=bucket_size)
    return _HIER_CACHE[key]


def _assert_bit_identical(dense, hier):
    d_losses, d_params = dense
    h_losses, h_params = hier[0], hier[1]
    assert d_losses == h_losses, (d_losses, h_losses)
    for a, b in zip(jax.tree.leaves(d_params), jax.tree.leaves(h_params)):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# the robustness grid: >= 3 aggregators x >= 2 attacks, tolerance ZERO
# ---------------------------------------------------------------------------


GRID = [(agg, attack)
        for agg in ("Mean", "Median", "Trimmedmean")
        for attack in ("ALIE", "IPM")]


@pytest.mark.parametrize("agg,attack", GRID,
                         ids=[f"{a}-{k}" for a, k in GRID])
def test_hier_bucket1_grid_bit_identical_to_dense(agg, attack):
    """bucket_size=1 is identity pre-agg: the hierarchical round on the
    (4, 2) mesh must reproduce the single-chip dense trajectory EXACTLY
    (losses and server params) — the pinned tolerance is zero."""
    _assert_bit_identical(_dense(agg, attack), _hier(agg, attack))


def test_hier_nnm_bucket1_bit_identical_to_dense():
    """NNM at bucket_size=1 mixes each lane with only itself — also
    exactly the identity, through the other pre-agg code path."""
    _assert_bit_identical(_dense("Median", "ALIE"),
                          _hier("Median", "ALIE", preagg="nnm"))


def test_hier_bucket2_mean_commutes_to_reassociation():
    """With uniform buckets, no ghosts and no forging, Mean is exactly
    the mean of bucket means — the hierarchical b=2 round differs from
    dense only by float32 reassociation.  Pinned tolerance: 1e-6
    relative (documented in README).  Under an attack the b>1 round
    computes a DIFFERENT (provably tighter) defended statistic by
    design, so the attack-free config is the right commutation pin."""
    d_losses, d_params = _dense("Mean", None)
    h_losses, h_params, _, m = _hier("Mean", None, bucket_size=2)
    np.testing.assert_allclose(d_losses, h_losses, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(d_params), jax.tree.leaves(h_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # 8 clients over 4 client-chips in buckets of 2 -> 4 representatives.
    assert int(m["preagg_kept"]) == 4


# ---------------------------------------------------------------------------
# ICI accounting: recorder <-> comm model, both directions
# ---------------------------------------------------------------------------


def test_ici_reconciles_with_comm_model_both_ways():
    """Every collective the traced hier program counted must appear in
    the analytic inventory with the same (kind, payload, ring), and
    vice versa; the per-chip wire totals must be EQUAL (both sides use
    the same integer ring arithmetic)."""
    _, params = _dense("Median", "ALIE")
    _, _, d = ravel_fn(params)
    for mesh_shape in (MESH_2D, (8, 1)):
        _, _, rec, m = _hier("Median", "ALIE", mesh_shape=mesh_shape)
        vols = hier_round_volumes(N_CLIENTS, d, mesh_shape,
                                  preagg="bucket", bucket_size=1)
        model = sorted((v.kind, v.payload_bytes, k)
                       for v, k in vols for _ in range(v.count))
        recorded = sorted((kind, payload, k)
                          for _, kind, payload, k in rec.ici_events)
        assert recorded == model, (mesh_shape, recorded, model)
        assert rec.ici_bytes == hier_wire_bytes(vols)
        assert int(m["preagg_kept"]) == N_CLIENTS
    # The 2-D torus gathers column-sliced representatives in two phases;
    # the flat ring ships full rows once — the 2-D wire total is strictly
    # smaller for this geometry.
    v2 = hier_wire_bytes(hier_round_volumes(N_CLIENTS, d, MESH_2D))
    v1 = hier_wire_bytes(hier_round_volumes(N_CLIENTS, d, (8, 1)))
    assert v2 < v1


# ---------------------------------------------------------------------------
# pre-agg primitives (pure, no mesh)
# ---------------------------------------------------------------------------


def test_bucket_representatives_math():
    u = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    real = jnp.array([True] * 5 + [False])
    # b=1: identity on real lanes.
    r1 = bucket_representatives(u, real, 1)
    assert np.array_equal(np.asarray(r1[:5]), np.asarray(u[:5]))
    # b=2: masked means; the boundary bucket averages only its real lane.
    r2 = bucket_representatives(u, real, 2)
    assert bucket_count(6, 2) == 3
    np.testing.assert_allclose(np.asarray(r2[0]),
                               np.asarray(u[:2].mean(axis=0)))
    np.testing.assert_allclose(np.asarray(r2[2]), np.asarray(u[4]))
    # A NaN ghost lane cannot poison its bucket.
    u_nan = u.at[5].set(jnp.nan)
    r2n = bucket_representatives(u_nan, real, 2)
    assert np.isfinite(np.asarray(r2n)).all()


def test_nnm_representatives_math():
    u = jnp.array([[0.0], [0.1], [10.0], [100.0]], jnp.float32)
    real = jnp.array([True, True, True, False])
    # b=1: identity on REAL lanes (ghost rows emit garbage at their own
    # index — the caller's static ``kept`` slice removes them).
    assert np.array_equal(np.asarray(nnm_representatives(u, real, 1))[:3],
                          np.asarray(u)[:3])
    # b=2: each row mixes with its nearest REAL neighbor; the ghost
    # (100.0) is never selected.
    r = np.asarray(nnm_representatives(u, real, 2))
    np.testing.assert_allclose(r[0], [0.05])
    np.testing.assert_allclose(r[1], [0.05])
    np.testing.assert_allclose(r[2], [5.05])


def test_hier_kept_counts_static_prefix():
    # 10 real clients on 4 chips of 3 lanes (pad 12): reals 3,3,3,1.
    assert hier_kept_counts(10, 3, 4, 1) == [3, 3, 3, 1]
    assert hier_kept_counts(10, 3, 4, 2) == [2, 2, 2, 1]
    assert hier_kept_counts(12, 3, 4, 3) == [1, 1, 1, 1]
    assert sum(hier_kept_counts(8, 2, 4, 1)) == 8


# ---------------------------------------------------------------------------
# the scaled acceptance run: 10k registered clients through the driver
# ---------------------------------------------------------------------------


def _tiny_population_dataset(n_clients, rows_per_client=4, shape=SHAPE,
                             num_classes=2, seed=0):
    from blades_tpu.data.datasets import FLDataset
    from blades_tpu.data.partition import partition_dataset

    rng = np.random.default_rng(seed)
    n = n_clients * rows_per_client
    mus = rng.normal(size=(num_classes,) + shape).astype(np.float32)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = (mus[y] + 0.5 * rng.normal(size=(n,) + shape)).astype(np.float32)
    train = partition_dataset(x, y, n_clients, iid=True, seed=seed)
    test = partition_dataset(x[: 2 * n_clients], y[: 2 * n_clients],
                             n_clients, iid=True, seed=seed + 1)
    return FLDataset(name="tinypop", train=train, test_x=x[:64],
                     test_y=y[:64], test=test, num_classes=num_classes,
                     input_shape=shape)


def _tiny_driver(n, *, seed=0, faults=None, num_malicious=0):
    cfg = (
        FedavgConfig()
        .data(dataset=_tiny_population_dataset(n, seed=seed), num_clients=n,
              seed=seed)
        .training(global_model=MLP(hidden1=8, hidden2=8, num_classes=2),
                  num_classes=2, input_shape=SHAPE, server_lr=0.5,
                  train_batch_size=4, aggregator={"type": "Median"})
        .client(lr=0.1)
        .evaluation(evaluation_interval=0)
        .resources(num_devices=8, execution="hier")
    )
    if num_malicious:
        cfg.adversary(num_malicious_clients=num_malicious,
                      adversary_config={"type": "ALIE"})
    if faults:
        cfg.fault_tolerance(faults=faults)
    return cfg.build()


# 10k-registered mesh round: shard_map compiles are the most expensive
# tier-1 class (~8 s); the hier path keeps its bit-identity grid and
# kill-and-resume tier-1, the scale acceptance rides the slow lane
# (PR 20 budget rebalance).
@pytest.mark.slow
def test_10k_registered_clients_hier_round_completes():
    """The ISSUE 18 acceptance run, scaled for the CPU tier-1 box:
    10 240 registered clients on the 8-virtual-device mesh complete a
    hierarchical round, and the stamped ici_bytes reconciles exactly
    against the analytic comm model."""
    n = 10_240
    algo = _tiny_driver(n)
    try:
        row = algo.train()
        assert np.isfinite(row["train_loss"])
        assert row["mesh_shape"] == "8x1"
        assert row["preagg_kept"] == n  # bucket_size=1 keeps every client
        _, _, d = ravel_fn(algo.state.server.params)
        vols = hier_round_volumes(n, d, (8, 1), preagg="bucket",
                                  bucket_size=1)
        assert row["ici_bytes"] == hier_wire_bytes(vols)
    finally:
        algo.stop()


def test_hier_kill_and_resume_bit_identical(tmp_path):
    """Kill-and-resume through the faults harness: checkpoint a
    hierarchical run with dropout injection mid-stream, rebuild a fresh
    driver, load, and the continued rounds must be bit-identical to the
    uninterrupted run (round keys and the fault process both derive
    from the stored round counter)."""
    a = _tiny_driver(16, faults={"dropout_rate": 0.25, "seed": 11},
                     num_malicious=4)
    try:
        # The wire total is a host int off the recorder: at ResNet width
        # it passes int32 from ~150 clients on, which an in-program
        # int32 stamp could not trace.
        a._hier_recorder.ici_bytes += 1 << 32
        assert a.train()["ici_bytes"] > 1 << 32
        path = a.save_checkpoint(str(tmp_path))
        r2a = a.train()
        r3a = a.train()
        b = _tiny_driver(16, faults={"dropout_rate": 0.25, "seed": 11},
                         num_malicious=4)
        try:
            b.load_checkpoint(path)
            r2b = b.train()
            r3b = b.train()
            assert r2a["train_loss"] == r2b["train_loss"]
            assert r3a["train_loss"] == r3b["train_loss"]
            for x, y in zip(jax.tree.leaves(a.state.server.params),
                            jax.tree.leaves(b.state.server.params)):
                assert np.array_equal(np.asarray(x), np.asarray(y))
        finally:
            b.stop()
    finally:
        a.stop()


# ---------------------------------------------------------------------------
# validate(): every mesh rejection names the exact pair + knob
# ---------------------------------------------------------------------------


def _check(match, **kw):
    cfg = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=8, seed=0)
        .training(global_model="mlp", aggregator={"type": "Median"})
    )
    for k, v in kw.items():
        setattr(cfg, k, v)
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_pod_scale_validation_messages():
    _check("mesh_shape × single-chip is an unsupported pair",
           mesh_shape=(4, 2))
    _check("must tile exactly", mesh_shape=(4, 2), num_devices=16)
    _check(r"mesh_shape must be a \(clients, d\) pair",
           mesh_shape=(4, 2, 1), num_devices=8)
    _check("pre-aggregates per chip and gathers", execution="hier")
    _check("preagg must be one of", preagg="mean")
    _check("bucket_size must be an int >= 1", bucket_size=0)
    _check("autotune × execution='hier' is an unsupported pair",
           execution="hier", num_devices=8, autotune="on")
    _check("autotune × execution='dsharded' is an unsupported pair",
           execution="dsharded", num_devices=8, autotune="on")
    _check("straggler faults is an unsupported pair",
           execution="hier", num_devices=8,
           fault_config={"dropout_rate": 0.1, "num_stragglers": 1})
    _check("identity-height pre-aggregation",
           execution="hier", num_devices=8, bucket_size=2,
           fault_config={"dropout_rate": 0.1})


def test_hier_step_rejects_unsupported_rounds():
    from blades_tpu.parallel.hier import _check_supported

    fr, _ = _tiny_round()
    with pytest.raises(ValueError, match="unknown preagg flavor"):
        _check_supported(fr, "mean", 1)
    with pytest.raises(ValueError, match="bucket_size must be >= 1"):
        _check_supported(fr, "bucket", 0)


# ---------------------------------------------------------------------------
# the full aggregator zoo (tier 2): b=1 identity for every defense
# ---------------------------------------------------------------------------


ZOO = [
    {"type": "Mean"},
    {"type": "Median"},
    {"type": "Trimmedmean", "num_byzantine": N_BYZ},
    {"type": "GeoMed"},
    {"type": "DnC", "num_byzantine": N_BYZ, "sub_dim": 8, "num_iters": 2},
    {"type": "Multikrum", "num_byzantine": N_BYZ, "k": 2},
    {"type": "Centeredclipping"},
    {"type": "Signguard"},
    {"type": "Clippedclustering"},
    {"type": "FLTrust"},
]


@pytest.mark.parametrize(
    "agg", [pytest.param(a, marks=pytest.mark.slow, id=a["type"])
            for a in ZOO])
def test_hier_bucket1_zoo_bit_identical(agg):
    """Every registered aggregator, hierarchical b=1 vs dense: exact."""
    import dataclasses

    def rounds():
        fr, data = _tiny_round(agg, "ALIE")
        if agg["type"] == "FLTrust":
            x, y = data[0], data[1]
            fr = dataclasses.replace(fr, trusted_data=(x[0], y[0]))
        return fr, data

    fr, data = rounds()
    dense = _run_dense(fr, data, 2)
    fr2, data2 = rounds()
    hier = _run_hier(fr2, data2, 2)
    _assert_bit_identical(dense, hier)

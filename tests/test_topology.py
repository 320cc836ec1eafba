"""Decentralized gossip federation (ISSUE 19): topology + gossip tests.

The headline tier-1 contract is the one :mod:`blades_tpu.topology.gossip`
pins in its docstring: on the **complete graph with Mean**, the gossip
round — per-node local training, neighborhood exchange, per-node
aggregation, doubly-stochastic mixing — is **bit-identical** to the
centralized dense ``FedRound.step`` (tolerance ZERO: every node's
replica equals the dense server params, losses and agg norms match
bitwise).  The ICI reconciliation test checks the trace-time recorder
against :mod:`blades_tpu.parallel.comm_model.gossip_round_volumes` in
both directions, event by event; partition tolerance pins the
deterministic edge-dropout realization and the loud per-node
breakdown-bound degradation; and the driver tests run the full
``execution="gossip"`` surface including kill-and-resume bit-identity.

Budget note: gossip compiles ride tier-1 deliberately (the ISSUE 19
acceptance runs the decentralized path on the CPU tier-1 box); every
federation is tiny (MLP(8, 8) on 4x4x1 inputs, d = 226) and dense/
gossip trajectories are cached per config so each program compiles
exactly once.  The full graph x aggregator x attack zoo is slow-marked
and rides tier 2.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from blades_tpu.adversaries import get_adversary, make_malicious_mask
from blades_tpu.adversaries.topology_attacks import TopologyAttackAdversary
from blades_tpu.algorithms import FedavgConfig
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.faults import FaultInjector
from blades_tpu.models.mlp import MLP
from blades_tpu.obs.schema import validate_record
from blades_tpu.parallel.comm_model import (
    gossip_round_volumes,
    gossip_wire_bytes,
)
from blades_tpu.parallel.mesh import make_mesh
from blades_tpu.topology import (
    GRAPHS,
    TopologyConfig,
    get_topology,
    gossip_evaluate,
    gossip_federation,
    gossip_step,
)
from blades_tpu.utils.tree import ravel_fn

N_CLIENTS = 8
N_BYZ = 2
ROWS = 4
SHAPE = (4, 4, 1)
TOPO_ALIE = {"type": "TopologyAttack", "base": "ALIE"}


def _tiny_round(agg="Median", attack="ALIE", n=N_CLIENTS, f=N_BYZ, seed=0,
                faults=None, health=False):
    """A raw FedRound on the tiny synthetic task (d = 226 params)."""
    task = TaskSpec(model=MLP(hidden1=8, hidden2=8, num_classes=2),
                    num_classes=2, input_shape=SHAPE, lr=0.1).build()
    server = Server.from_config(aggregator=agg, num_byzantine=f or None,
                                lr=0.5)
    adv = (get_adversary(attack, num_clients=n, num_byzantine=f)
           if attack is not None else None)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=2,
                  num_batches_per_round=1, num_clients=n, faults=faults,
                  health_check=health)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, ROWS) + SHAPE), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(n, ROWS)), jnp.int32)
    lengths = jnp.full((n,), ROWS, jnp.int32)
    mal = make_malicious_mask(n, f)
    return fr, (x, y, lengths, mal)


def _run_dense(fr, data, rounds):
    """Single-chip dense trajectory: (losses, aggns, final params)."""
    x, y, lengths, mal = data
    state = fr.init(jax.random.PRNGKey(0), N_CLIENTS)
    step = jax.jit(fr.step)
    losses, aggns = [], []
    for r in range(rounds):
        state, m = step(state, x, y, lengths, mal,
                        jax.random.fold_in(jax.random.PRNGKey(9), r))
        losses.append(float(m["train_loss"]))
        aggns.append(float(m["agg_norm"]))
    return losses, aggns, jax.tree.map(np.asarray, state.server.params)


def _mesh8():
    """The 8-virtual-device 1-D mesh (kept out of test bodies so the
    slow-markers pass only bills tests that actually COMPILE on it —
    the build-gate tests below raise before tracing)."""
    return make_mesh(8)


def _run_gossip(fr, data, rounds, graph, *, n=N_CLIENTS, **topo_kw):
    """Gossip trajectory on the 8-device mesh.

    Returns ``(losses, aggns, per-node params stack, recorder,
    last metrics)``.
    """
    x, y, lengths, mal = data
    mesh = make_mesh(8)
    topo = TopologyConfig(graph=graph, num_nodes=n, **topo_kw)
    state = fr.init(jax.random.PRNGKey(0), n)
    state, arrays = gossip_federation(mesh, state, (x, y, lengths))
    step, rec = gossip_step(fr, mesh, topo)
    losses, aggns, m = [], [], None
    for r in range(rounds):
        state, m = step(state, *arrays, mal,
                        jax.random.fold_in(jax.random.PRNGKey(9), r))
        losses.append(float(m["train_loss"]))
        aggns.append(float(m["agg_norm"]))
    return (losses, aggns, jax.tree.map(np.asarray, state.server.params),
            rec, {k: np.asarray(v) for k, v in m.items()})


_DENSE_CACHE = {}
_GOSSIP_CACHE = {}


def _dense(agg, attack, rounds=2, f=N_BYZ):
    key = (agg, str(attack), rounds, f)
    if key not in _DENSE_CACHE:
        fr, data = _tiny_round(agg, attack, f=f)
        _DENSE_CACHE[key] = _run_dense(fr, data, rounds)
    return _DENSE_CACHE[key]


def _gossip(agg, attack, graph, rounds=2, f=N_BYZ, **topo_kw):
    key = (agg, str(attack), graph, rounds, f,
           tuple(sorted(topo_kw.items())))
    if key not in _GOSSIP_CACHE:
        fr, data = _tiny_round(agg, attack, f=f)
        _GOSSIP_CACHE[key] = _run_gossip(fr, data, rounds, graph, **topo_kw)
    return _GOSSIP_CACHE[key]


# ---------------------------------------------------------------------------
# graph family unit tests (host-side numpy, no mesh)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("graph", GRAPHS)
def test_graph_adjacency_and_mixing_contracts(graph):
    """Every family: symmetric adjacency, no self loops, connected-by-
    construction mixing that is symmetric doubly-stochastic with a
    positive spectral gap."""
    topo = TopologyConfig(graph=graph, num_nodes=8, k=4, p=0.3)
    a = topo.adjacency()
    assert a.dtype == bool and a.shape == (8, 8)
    assert np.array_equal(a, a.T)
    assert not a.diagonal().any()
    assert (a.sum(axis=1) >= 1).all()  # no isolated nodes
    w = topo.mixing_matrix()
    np.testing.assert_allclose(w.sum(axis=0), 1.0, atol=1e-12)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-12)
    assert (w >= 0).all()
    np.testing.assert_allclose(w, w.T, atol=1e-15)
    assert 0.0 < topo.spectral_gap <= 1.0


def test_erdos_seeded_and_complete_gap():
    """The one random family is pure in graph_seed (two processes build
    the same graph); complete's mixing is the uniform average — the
    largest possible gap — and denser graphs mix no slower than ring."""
    a1 = TopologyConfig(graph="erdos", num_nodes=12, p=0.4,
                        graph_seed=7).adjacency()
    a2 = TopologyConfig(graph="erdos", num_nodes=12, p=0.4,
                        graph_seed=7).adjacency()
    a3 = TopologyConfig(graph="erdos", num_nodes=12, p=0.4,
                        graph_seed=8).adjacency()
    assert np.array_equal(a1, a2)
    assert not np.array_equal(a1, a3)
    gaps = {g: TopologyConfig(graph=g, num_nodes=8, k=4).spectral_gap
            for g in ("ring", "kregular", "complete")}
    assert gaps["complete"] == pytest.approx(1.0)
    assert gaps["ring"] < gaps["kregular"] <= gaps["complete"]


def test_neighbor_tables_slot_contract():
    """The bit-identity pin rests on this ordering: closed neighborhoods
    in ASCENDING global index (so complete-graph rows reproduce the
    dense matrix), pad slots pointing at the node itself, w_slot zero on
    self and pad slots."""
    topo = TopologyConfig(graph="ring", num_nodes=6)
    t = topo.neighbor_tables()
    n, k1 = t.nbr_idx.shape
    assert (n, k1) == (6, 3)
    w = topo.mixing_matrix()
    for i in range(n):
        d_i = int(t.valid[i].sum())
        real = t.nbr_idx[i, :d_i]
        assert list(real) == sorted(real)  # ascending global index
        assert i in real
        assert (t.nbr_idx[i, d_i:] == i).all()  # ghost slots = self
        assert t.nbr_idx[i, t.self_slot[i]] == i
        assert t.w_slot[i, t.self_slot[i]] == 0.0
        assert (t.w_slot[i, d_i:] == 0.0).all()
        for s in range(d_i):
            j = int(real[s])
            if j != i:
                assert t.w_slot[i, s] == pytest.approx(w[i, j], rel=1e-6)
    # Complete graph: every row is the identity permutation 0..n-1.
    tc = TopologyConfig(graph="complete", num_nodes=6).neighbor_tables()
    assert np.array_equal(tc.nbr_idx,
                          np.tile(np.arange(6, dtype=np.int32), (6, 1)))


def test_graph_validation_messages():
    with pytest.raises(ValueError, match="unknown topology graph"):
        TopologyConfig(graph="smallworld", num_nodes=8)
    with pytest.raises(ValueError, match="unknown mixing scheme"):
        TopologyConfig(graph="ring", num_nodes=8, mixing="lazy")
    with pytest.raises(ValueError, match="num_nodes >= 2"):
        TopologyConfig(graph="ring", num_nodes=1)
    with pytest.raises(ValueError, match="must be even with 2 <= k"):
        TopologyConfig(graph="kregular", num_nodes=8, k=3)
    with pytest.raises(ValueError, match="p=1.5 must be in"):
        TopologyConfig(graph="erdos", num_nodes=8, p=1.5)
    with pytest.raises(ValueError, match="torus needs a 2-D grid"):
        TopologyConfig(graph="torus", num_nodes=7)
    # get_topology resolution: name, dict, instance (pinning num_nodes).
    assert get_topology("kregular", 8).graph == "kregular"
    assert get_topology({"graph": "erdos", "p": 0.5}, 8).p == 0.5
    t = TopologyConfig(graph="ring", num_nodes=4)
    assert get_topology(t, 99) is t


# ---------------------------------------------------------------------------
# the headline pin: complete graph + Mean == centralized dense, bitwise
# ---------------------------------------------------------------------------


def test_complete_mean_gossip_bit_identical_to_dense():
    """Tolerance ZERO: with the complete graph and Mean every node's
    neighborhood matrix IS the dense matrix in dense row order, mixing
    is a no-op on consensus replicas, and the RNG discipline mirrors the
    dense split chain — so every node's replica must equal the dense
    server params bitwise, along with losses and agg norms."""
    d_losses, d_aggns, d_params = _dense("Mean", "ALIE", rounds=3)
    g_losses, g_aggns, g_params, _, m = _gossip("Mean", "ALIE", "complete",
                                                rounds=3)
    assert g_losses == d_losses
    assert g_aggns == d_aggns
    for stack, ref in zip(jax.tree.leaves(g_params),
                          jax.tree.leaves(d_params)):
        for i in range(N_CLIENTS):
            assert np.array_equal(stack[i], ref)
    # Consensus never breaks on the complete graph.
    assert float(m["consensus_dist"]) == 0.0
    assert int(m["num_partitioned_nodes"]) == 0


# ---------------------------------------------------------------------------
# ICI accounting: recorder <-> comm model, both directions
# ---------------------------------------------------------------------------


# Byte-accounting reconciliation over a full gossip compile (~6 s); the
# gossip round path itself stays tier-1 via the complete-graph + Mean
# centralized-equivalence test (PR 20 budget rebalance).
@pytest.mark.slow
def test_gossip_ici_reconciles_with_comm_model_both_ways():
    """Every collective the traced gossip program counted must appear in
    the analytic inventory with the same (kind, payload, ring), and vice
    versa; the per-chip wire totals must be EQUAL (same integer ring
    arithmetic on both sides) and match the stamped metric."""
    _, _, d_params = _dense("Mean", "ALIE")
    _, _, d = ravel_fn(d_params)
    # Fault-free round: the partition psum is absent on both sides.
    _, _, _, rec, m = _gossip("Mean", "ALIE", "complete")
    vols = gossip_round_volumes(N_CLIENTS, d, (8, 1))
    model = sorted((v.kind, v.payload_bytes, k)
                   for v, k in vols for _ in range(v.count))
    recorded = sorted((kind, payload, k)
                      for _, kind, payload, k in rec.ici_events)
    assert recorded == model, (recorded, model)
    assert rec.ici_bytes == gossip_wire_bytes(vols)
    # Fault-armed round: the partitioned-count psum joins the inventory.
    fr, data = _tiny_round("Median", "SignFlip",
                           faults=FaultInjector(seed=5, dropout_rate=0.3))
    _, _, _, rec_f, m_f = _run_gossip(fr, data, 1, "ring")
    vols_f = gossip_round_volumes(N_CLIENTS, d, (8, 1), faults=True)
    model_f = sorted((v.kind, v.payload_bytes, k)
                     for v, k in vols_f for _ in range(v.count))
    recorded_f = sorted((kind, payload, k)
                        for _, kind, payload, k in rec_f.ici_events)
    assert recorded_f == model_f, (recorded_f, model_f)
    assert rec_f.ici_bytes == gossip_wire_bytes(vols_f)
    # The exchange volume does not depend on graph density (replica
    # gathers ship the full stack; the topology selects locally).
    assert rec.ici_bytes == gossip_wire_bytes(
        gossip_round_volumes(N_CLIENTS, d, (8, 1), faults=False))


# ---------------------------------------------------------------------------
# robustness grid: graph x aggregator x attack
# ---------------------------------------------------------------------------


def _assert_cell_healthy(agg, attack, graph, f=N_BYZ, **topo_kw):
    losses, _, params, _, m = _gossip(agg, attack, graph, f=f, **topo_kw)
    assert all(np.isfinite(v) for v in losses), (graph, agg, losses)
    for leaf in jax.tree.leaves(params):
        assert np.isfinite(leaf[:N_CLIENTS]).all()
    assert float(m["consensus_dist"]) >= 0.0
    assert int(m["num_partitioned_nodes"]) == 0  # no faults armed


# Headline tier-1 subset: one cell per graph family + the Multikrum
# static-gate survivor, covering both attack flavors.  Multikrum cells
# run f=1: Krum scoring needs 2f+2 rows per neighborhood matrix, so
# f=2 on kregular's k1=5 matrices is structurally out (the f=2 ring
# rejection is pinned by the breakdown-gate test below).
GRID_HEADLINE = [
    ("Median", TOPO_ALIE, "ring", {}, N_BYZ),
    ("Mean", "SignFlip", "ring", {}, N_BYZ),
    ("Multikrum", "SignFlip", "kregular", {"k": 4}, 1),
    ("Median", TOPO_ALIE, "complete", {}, N_BYZ),
]

# The slow zoo: every remaining supported (graph, aggregator, attack)
# cell — ring excludes Multikrum (the breakdown gate rejects it, pinned
# below); kregular/complete run all three aggregators.
GRID_ZOO = [
    (agg, attack, graph, ({"k": 4} if graph == "kregular" else {}),
     (1 if agg == "Multikrum" else N_BYZ))
    for graph in ("ring", "kregular", "complete")
    for agg in ("Mean", "Median", "Multikrum")
    for attack in (TOPO_ALIE, "SignFlip")
    if not (graph == "ring" and agg == "Multikrum")
    and (agg, attack, graph) not in [(a, k, g) for a, k, g, _, _ in
                                     GRID_HEADLINE]
]


@pytest.mark.parametrize(
    "agg,attack,graph,kw,f", GRID_HEADLINE,
    ids=[f"{g}-{a}-{k if isinstance(k, str) else 'TopoALIE'}"
         for a, k, g, _, _ in GRID_HEADLINE])
def test_gossip_grid_headline(agg, attack, graph, kw, f):
    """>= 3 aggregators x 2 attacks across ring/kregular/complete: the
    per-node robust round stays finite and stamps sane telemetry."""
    _assert_cell_healthy(agg, attack, graph, f=f, **kw)


@pytest.mark.slow
@pytest.mark.parametrize(
    "agg,attack,graph,kw,f", GRID_ZOO,
    ids=[f"{g}-{a}-{k if isinstance(k, str) else 'TopoALIE'}"
         for a, k, g, _, _ in GRID_ZOO])
def test_gossip_grid_zoo(agg, attack, graph, kw, f):
    _assert_cell_healthy(agg, attack, graph, f=f, **kw)


def test_multikrum_ring_breakdown_gate():
    """Static build-time gate: Multikrum(f=2) needs f+3 = 5 neighborhood
    rows; ring's closed neighborhoods hold 3 — the pair must be rejected
    BEFORE tracing, naming the graph, the aggregator, and the fix."""
    fr, _ = _tiny_round("Multikrum", None)
    with pytest.raises(ValueError,
                       match=r"Multikrum\(num_byzantine=2\) needs "
                             r"neighborhood matrices of >= 5 rows"):
        gossip_step(fr, _mesh8(),
                    TopologyConfig(graph="ring", num_nodes=N_CLIENTS))


# ---------------------------------------------------------------------------
# topology-scoped adversaries
# ---------------------------------------------------------------------------


def test_topology_attack_receiver_mask():
    adv = get_adversary(TOPO_ALIE, num_clients=6, num_byzantine=2)
    assert isinstance(adv, TopologyAttackAdversary)
    assert adv.topology_scoped
    a = TopologyConfig(graph="ring", num_nodes=6).adjacency()
    mask = adv.receiver_mask(a)
    # Out-edge poisoning: receiver i sees forged rows from its IN-edges
    # (column view of the adjacency) — for symmetric graphs, a.T == a.
    assert mask.dtype == bool and mask.shape == (6, 6)
    assert np.array_equal(mask, a.T)
    # Eclipse focuses the forged rows on one receiver only.
    adv_e = get_adversary({**TOPO_ALIE, "eclipse_target": 3},
                          num_clients=6, num_byzantine=2)
    mask_e = adv_e.receiver_mask(a)
    assert mask_e[3].any()
    assert not np.delete(mask_e, 3, axis=0).any()


def test_topology_attack_validation():
    with pytest.raises(ValueError, match="eclipse_target"):
        get_adversary({**TOPO_ALIE, "eclipse_target": 99},
                      num_clients=6, num_byzantine=2)
    with pytest.raises(ValueError, match="TopologyAttack"):
        # Wrapping itself is a config error, not infinite recursion.
        get_adversary({"type": "TopologyAttack", "base": "TopologyAttack"},
                      num_clients=6, num_byzantine=2)
    adv = get_adversary(TOPO_ALIE, num_clients=8, num_byzantine=2)
    with pytest.raises(ValueError, match="num_clients"):
        adv.receiver_mask(np.zeros((4, 4), bool))


def test_eclipse_focuses_poison_on_target():
    """One gossip round from consensus init on the complete graph with
    an eclipse on node 5: only node 5's neighborhood matrix carries
    forged rows (receiver_mask restricts the poison-slot select), so
    every OTHER node aggregates the identical clean full matrix from
    identical mixed params — all 7 replicas bit-identical to each
    other — while the eclipsed target's replica diverges."""
    fr, data = _tiny_round("Mean", {**TOPO_ALIE, "eclipse_target": 5})
    _, _, params, _, _ = _run_gossip(fr, data, 1, "complete")
    leaves = jax.tree.leaves(params)
    others = [i for i in range(N_CLIENTS) if i != 5]
    for leaf in leaves:
        for i in others[1:]:
            assert np.array_equal(leaf[others[0]], leaf[i])
    assert any(not np.array_equal(leaf[5], leaf[others[0]])
               for leaf in leaves)


# ---------------------------------------------------------------------------
# partition tolerance: deterministic edge dropout, loud degradation
# ---------------------------------------------------------------------------


def _dropout_run(rounds=3):
    fr, data = _tiny_round("Median", "SignFlip",
                           faults=FaultInjector(seed=5, dropout_rate=0.6),
                           health=True)
    x, y, lengths, mal = data
    mesh = make_mesh(8)
    topo = TopologyConfig(graph="ring", num_nodes=N_CLIENTS)
    state = fr.init(jax.random.PRNGKey(0), N_CLIENTS)
    state, arrays = gossip_federation(mesh, state, (x, y, lengths))
    step, _ = gossip_step(fr, mesh, topo)
    parts, m = [], None
    for r in range(rounds):
        state, m = step(state, *arrays, mal,
                        jax.random.fold_in(jax.random.PRNGKey(11), r))
        parts.append(int(m["num_partitioned_nodes"]))
    return parts, jax.tree.map(np.asarray, state.server.params), m


def test_partition_tolerance_degrades_loudly_and_deterministically():
    """Edge dropout at 0.6 on a ring partitions nodes below Median's
    breakdown bound (2f+1 live rows): the round keeps running, each
    degraded node falls back to self-trust (params stay finite), the
    count lands LOUDLY in num_partitioned_nodes, and the realization is
    pure in (fault_seed, round) — a rebuilt run reproduces the counts
    and the params bitwise."""
    parts, params, m = _dropout_run()
    assert any(p > 0 for p in parts), parts
    assert all(0 <= p <= N_CLIENTS for p in parts)
    for leaf in jax.tree.leaves(params):
        assert np.isfinite(leaf[:N_CLIENTS]).all()
    assert bool(m["round_ok"])  # degraded != unhealthy
    parts2, params2, _ = _dropout_run()
    assert parts2 == parts
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        assert np.array_equal(a, b)


@pytest.mark.slow
def test_dropout_never_fires_without_faults():
    """The fault-free program contains no partition psum and stamps a
    hard zero — covered by the grid cells asserting
    num_partitioned_nodes == 0 — and a zero-rate injector on a clean
    federation keeps every edge alive and every node above its bound.
    (With attackers present the per-node breakdown check is live even
    at rate 0: adjacent ring attackers degrade their OWN 3-row
    neighborhoods, f_i=2 -> need 5 — that loudness is the feature.)"""
    fr, data = _tiny_round("Median", None, f=0,
                           faults=FaultInjector(seed=5, dropout_rate=0.0))
    parts = []
    x, y, lengths, mal = data
    mesh = make_mesh(8)
    state = fr.init(jax.random.PRNGKey(0), N_CLIENTS)
    state, arrays = gossip_federation(mesh, state, (x, y, lengths))
    step, _ = gossip_step(fr, mesh,
                          TopologyConfig(graph="ring", num_nodes=N_CLIENTS))
    for r in range(2):
        state, m = step(state, *arrays, mal,
                        jax.random.fold_in(jax.random.PRNGKey(3), r))
        parts.append(int(m["num_partitioned_nodes"]))
    assert parts == [0, 0]


# ---------------------------------------------------------------------------
# driver surface: config gates, schema row, kill-and-resume
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


def _gossip_driver(n=N_CLIENTS, *, graph="ring", agg="Median", adv=None,
                   nm=0, faults=None, seed=0, **topo_kw):
    cfg = (
        FedavgConfig()
        .data(dataset=_tiny_population_dataset(n, seed=seed), num_clients=n,
              seed=seed)
        .training(global_model=MLP(hidden1=8, hidden2=8, num_classes=2),
                  num_classes=2, input_shape=SHAPE, server_lr=0.5,
                  train_batch_size=4, aggregator={"type": agg})
        .client(lr=0.1)
        .evaluation(evaluation_interval=0)
        .resources(num_devices=8, execution="gossip")
        .topology(graph=graph, **topo_kw)
    )
    if nm:
        cfg.adversary(num_malicious_clients=nm, adversary_config=adv)
    if faults:
        cfg.fault_tolerance(faults=faults)
    return cfg.build()


def test_gossip_driver_row_stamps_and_schema():
    """The full driver round stamps the six gossip fields together
    (validate_metrics' partial-stamp contract) and the row passes the
    round-record schema."""
    algo = _gossip_driver(nm=2, adv=TOPO_ALIE)
    try:
        row = algo.train()
        validate_record(dict(row, experiment="gossip", trial="t0",
                             training_iteration=1))
        assert row["topology"] == "ring"
        assert row["graph_seed"] == 0
        assert 0.0 < row["spectral_gap"] <= 1.0
        assert row["gossip_ici_bytes"] > 0
        assert row["num_partitioned_nodes"] == 0
        assert row["consensus_dist"] >= 0.0
        ev = algo.evaluate()
        assert np.isfinite(ev["test_loss"])
    finally:
        algo.stop()


def test_gossip_kill_and_resume_bit_identical(tmp_path):
    """Kill-and-resume through the faults harness: checkpoint a gossip
    run with edge dropout mid-stream, rebuild a fresh driver, load, and
    the continued rounds must be bit-identical to the uninterrupted run
    (round keys and the edge realization both derive from the stored
    round counter; the per-node params stack rides the checkpoint
    verbatim through reshard_gossip_state)."""
    kw = dict(graph="kregular", k=4, nm=2, adv={"type": "SignFlip"},
              faults={"dropout_rate": 0.4, "seed": 11})
    a = _gossip_driver(**kw)
    try:
        a.train()
        path = a.save_checkpoint(str(tmp_path))
        r2a = a.train()
        r3a = a.train()
        b = _gossip_driver(**kw)
        try:
            b.load_checkpoint(path)
            r2b = b.train()
            r3b = b.train()
            assert r2a["train_loss"] == r2b["train_loss"]
            assert r3a["train_loss"] == r3b["train_loss"]
            assert (r3a["num_partitioned_nodes"]
                    == r3b["num_partitioned_nodes"])
            for x, y in zip(jax.tree.leaves(a.state.server.params),
                            jax.tree.leaves(b.state.server.params)):
                assert np.array_equal(np.asarray(x), np.asarray(y))
        finally:
            b.stop()
    finally:
        a.stop()


# ---------------------------------------------------------------------------
# validate(): every gossip rejection names the exact pair + knob
# ---------------------------------------------------------------------------


def _check(match, *, topology=None, adversary=None, **kw):
    cfg = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=8, seed=0)
        .training(global_model="mlp", aggregator={"type": "Median"})
    )
    if topology is not None:
        cfg.topology(**topology)
    if adversary is not None:
        cfg.adversary(num_malicious_clients=2, adversary_config=adversary)
    for k, v in kw.items():
        setattr(cfg, k, v)
    with pytest.raises(ValueError, match=match):
        cfg.validate()


def test_gossip_validation_messages():
    _check("topology_config is set but execution='dense'",
           topology={"graph": "ring"}, execution="dense")
    _check(r"execution='gossip' × update codecs", execution="gossip",
           codec_config={"name": "quant", "bits": 8})
    _check(r"execution='gossip' × defense forensics", execution="gossip",
           forensics=True)
    _check(r"execution='gossip' × 2-D mesh_shape",
           execution="gossip", mesh_shape=(4, 2))
    _check(r"execution='gossip' × straggler faults", execution="gossip",
           fault_config={"num_stragglers": 2, "staleness": 1})
    _check(r"execution='gossip' × corruption faults", execution="gossip",
           fault_config={"corrupt_rate": 0.1})
    # A bad graph knob dies at validate() time, not at trace time.
    _check("kregular degree k=3", execution="gossip",
           topology={"graph": "kregular", "k": 3})
    # Topology-scoped adversaries need the peer graph.
    _check("topology-scoped", adversary=TOPO_ALIE, execution="dense")


@pytest.mark.slow
def test_flightrec_replay_gossip_round(tmp_path):
    """tools/replay_round on a gossip dump: the peer graph rebuilds from
    topology_config, the edge-dropout realization is pure in
    (fault_seed, round), and the gossip digest fields (gossip_ici_bytes,
    num_partitioned_nodes, consensus_dist, spectral_gap, graph_seed)
    compare bit-for-bit."""
    import json

    from blades_tpu.algorithms import get_algorithm_class
    from blades_tpu.obs.flightrec import FlightRecorder
    from tools.replay_round import main as replay_main

    trial_cfg = {
        "dataset_config": {"type": "mnist", "num_clients": N_CLIENTS,
                           "seed": 7},
        "global_model": "mlp",
        "num_devices": 8,
        "execution": "gossip",
        "topology_config": {"graph": "ring"},
        "fault_config": {"dropout_rate": 0.5, "seed": 11},
        "adversary_config": {"type": "SignFlip"},
        "num_malicious_clients": 2,
    }
    _, config = get_algorithm_class("FEDAVG", return_config=True)
    config.update_from_dict(json.loads(json.dumps(trial_cfg)))
    algo = config.build()
    rec = FlightRecorder(tmp_path / "flightrec.json", capacity=8,
                         experiment="e", trial="t", algo="FEDAVG",
                         config=trial_cfg, max_rounds=3)
    try:
        rows = [algo.train() for _ in range(3)]
    finally:
        algo.stop()
    assert any(r["num_partitioned_nodes"] > 0 for r in rows)
    for r in rows:
        rec.record(json.loads(json.dumps(dict(r, trial="t"),
                                         default=float)))
    rec.dump({"kind": "exception",
              "round": rows[-1]["training_iteration"]})
    assert replay_main([str(tmp_path / "flightrec.json"), "--quiet"]) == 0


@pytest.mark.slow
def test_gossip_evaluate_reads_node0_head():
    fr, data = _tiny_round("Median", None)
    x, y, lengths, _ = data
    mesh = make_mesh(8)
    state = fr.init(jax.random.PRNGKey(0), N_CLIENTS)
    state, arrays = gossip_federation(mesh, state, (x, y, lengths))
    step, _ = gossip_step(fr, mesh,
                          TopologyConfig(graph="complete",
                                         num_nodes=N_CLIENTS))
    state, _ = step(state, *arrays, make_malicious_mask(N_CLIENTS, 0),
                    jax.random.PRNGKey(1))
    ev = gossip_evaluate(fr)(state, x, y, lengths)
    assert np.isfinite(float(ev["test_loss"]))
    assert 0.0 <= float(ev["test_acc"]) <= 1.0

"""Client lane-packing (blades_tpu/parallel/packed.py).

Covers the tentpole's acceptance criteria:

- packed (pack_factor=2) FashionCNN and MLP rounds match the unpacked
  dense path per aggregator within fp-reassociation tolerance (the MLP
  case is bit-identical on this backend — pack-axis einsum vs per-lane
  matmul lower to the same contractions; grouped convs reassociate) —
  tier-1 runs the headline aggregators, the rest ride the ``slow`` lane
  exactly like ``tests/test_comm.py``'s identity sweep;
- equivalence holds under ALIE/IPM forging (the adversary reads the
  unpacked ``(n, d)`` matrix, so detection metrics and forged rows are
  the same experiment) and under the identity codec;
- pack/unpack are EXACT pytree inverses (pure layout transforms);
- ``"auto"`` falls back LOUDLY on ineligible configs — ResNet-18's wide
  stages, ``n % P != 0``, training-hook adversaries — and a forced
  ``client_packing`` int that cannot run raises at validate();
- kill-and-resume across a packed -> unpacked layout change via the
  chaos layer's resume harness: RoundState stays in canonical unpacked
  layout, so any pack_factor restores any other and the resumed
  trajectory matches an unpacked run within tolerance;
- ``pack_factor`` / ``packed_lanes`` are schema-registered, stamped
  into metrics.jsonl rows and sweep summaries (sequential and laned).
"""

import json
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.adversaries import get_adversary
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.models import MLP
from blades_tpu.ops.aggregators import AGGREGATORS
from blades_tpu.parallel.packed import (
    ClientPacking,
    PackingUnsupported,
    pack_replicated,
    pack_stacked,
    resolve_client_packing,
    unpack_stacked,
    unpack_tree,
)

_T1_AGGREGATORS = ("Mean",)

# fp-reassociation tolerance for packed-vs-unpacked trajectories
# (documented in README "Client packing"): grouped kernels reassociate
# reductions; over the few rounds tested the drift stays below 1e-4
# relative even through an aggregator's nonlinear selection.
RTOL = 1e-4


def _tiny_round(agg_name, *, model="mlp", adversary="ALIE", codec=None,
                packing=None, forensics=False, num_batches=2):
    if model == "mlp":
        spec = MLP(hidden1=8, hidden2=8, num_classes=4)
        input_shape = (8, 8, 1)
    else:  # the reference FashionCNN on a small spatial grid
        spec, input_shape = "cnn", (12, 12, 1)
    task = TaskSpec(model=spec, input_shape=input_shape, num_classes=4,
                    lr=0.1).build()
    n, f = 6, 2
    server = Server.from_config(aggregator=agg_name, num_byzantine=f, lr=0.5)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 12) + input_shape), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(n, 12)), jnp.int32)
    ln = jnp.full((n,), 12, jnp.int32)
    mal = jnp.arange(n) < f
    adv = get_adversary({"type": adversary}, num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=num_batches, num_clients=n,
                  codec=codec, forensics=forensics,
                  packing=ClientPacking(2) if packing else None,
                  trusted_data=((x[0, :8], y[0, :8])
                                if agg_name == "FLTrust" else None))
    return fr, (x, y, ln, mal)


def _run_rounds(fr, data, rounds=2, seed=5):
    x, y, ln, mal = data
    state = fr.init(jax.random.PRNGKey(0), 6)
    step = jax.jit(fr.step)
    metrics = []
    for r in range(rounds):
        state, m = step(state, x, y, ln, mal,
                        jax.random.fold_in(jax.random.PRNGKey(seed), r))
    return state, jax.device_get(m)


def _assert_close_trees(a, b, rtol=RTOL, msg=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=rtol, err_msg=msg)


# ---------------------------------------------------------------------------
# pack/unpack: exact pytree inverses
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model,shape", [
    ("mlp", (8, 8, 1)),
    # cnn/resnet roundtrips compile the conv-model init (~7 s each);
    # tier-1 already exercises the Conv/BSN pack rules end-to-end via
    # test_packed_cnn_ipm_forensics_detection_parity.
    pytest.param("cnn", (12, 12, 1), marks=pytest.mark.slow),
    pytest.param("resnet10", (8, 8, 3), marks=pytest.mark.slow)])
def test_pack_unpack_roundtrip_exact(model, shape):
    spec = MLP(hidden1=8, hidden2=8, num_classes=4) if model == "mlp" \
        else model
    task = TaskSpec(model=spec, input_shape=shape, num_classes=4,
                    momentum=0.9).build()
    params = task.init_params(jax.random.PRNGKey(1))
    stacked = jax.tree.map(
        lambda p: jnp.stack([p + i for i in range(4)]), params)
    rt = unpack_stacked(pack_stacked(stacked, 2), 2)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(rt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # momentum opt state packs by the same path rules
    opt = jax.tree.map(lambda p: jnp.stack([p, p * 2.0]),
                       task.init_client_opt_state(params))
    rt_opt = unpack_stacked(pack_stacked(opt, 2), 2)
    for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(rt_opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # replicated global params unpack to P identical client copies
    per_client = unpack_tree(pack_replicated(params, 2), 2)
    for orig, pc in zip(jax.tree.leaves(params),
                        jax.tree.leaves(per_client)):
        np.testing.assert_array_equal(np.asarray(pc[0]), np.asarray(orig))
        np.testing.assert_array_equal(np.asarray(pc[1]), np.asarray(orig))


# ---------------------------------------------------------------------------
# packed == unpacked per aggregator (ALIE forging, dropout active)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("agg_name", [
    a if a in _T1_AGGREGATORS else pytest.param(a, marks=pytest.mark.slow)
    for a in sorted(AGGREGATORS)])
def test_packed_matches_unpacked_per_aggregator(agg_name):
    """Acceptance: the packed MLP path reproduces the unpacked dense
    round per aggregator — aggregates, metrics, and full end state —
    within the documented fp tolerance, under ALIE forging with
    train-mode dropout active (mask equality is implied: a single
    differing mask would blow the tolerance immediately)."""
    fr_u, data = _tiny_round(agg_name)
    fr_p, _ = _tiny_round(agg_name, packing=True)
    s_u, m_u = _run_rounds(fr_u, data)
    s_p, m_p = _run_rounds(fr_p, data)
    for mk in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(m_u[mk]), float(m_p[mk]),
                                   rtol=RTOL, err_msg=(agg_name, mk))
    _assert_close_trees(s_u, s_p, msg=agg_name)


# CNN compile x packing x forensics (~6 s); packed parity and forensics
# detection are each pinned tier-1 separately
# (test_packed_matches_unpacked_per_aggregator[Mean], tests/test_ledger)
# (PR 20 budget rebalance).
@pytest.mark.slow
def test_packed_cnn_ipm_forensics_detection_parity():
    """Acceptance: grouped-conv packed FashionCNN under IPM forging with
    forensics on — the aggregator's per-lane decisions (benign mask,
    detection precision/recall/FPR) are IDENTICAL, adversary behavior
    unchanged, scalar metrics within tolerance."""
    fr_u, data = _tiny_round("Multikrum", model="cnn", adversary="IPM",
                             forensics=True, num_batches=1)
    fr_p, _ = _tiny_round("Multikrum", model="cnn", adversary="IPM",
                          forensics=True, num_batches=1, packing=True)
    s_u, m_u = _run_rounds(fr_u, data)
    s_p, m_p = _run_rounds(fr_p, data)
    for mk in ("byz_precision", "byz_recall", "byz_fpr", "num_flagged"):
        assert float(m_u[mk]) == float(m_p[mk]), mk
    np.testing.assert_array_equal(np.asarray(m_u["lane_benign_mask"]),
                                  np.asarray(m_p["lane_benign_mask"]))
    np.testing.assert_allclose(float(m_u["train_loss"]),
                               float(m_p["train_loss"]), rtol=RTOL)
    _assert_close_trees(s_u, s_p)


# Packing x codec transitivity (~6 s); both halves are tier-1 on their
# own (packed parity above, identity-codec bit-identity in
# tests/test_comm.py) (PR 20 budget rebalance).
@pytest.mark.slow
def test_packed_under_identity_codec():
    """Acceptance: packing composes with the comm layer — the identity
    codec is bit-transparent on the packed path (identical RoundState
    and metrics: the codec consumes the UNPACKED (n, d) matrix, exactly
    as it does today).  Packed+codec == unpacked+codec then follows by
    transitivity from the per-aggregator parity sweep above."""
    from blades_tpu.comm import CodecConfig

    fr_p, data = _tiny_round("Median", packing=True)
    fr_pc, _ = _tiny_round("Median", packing=True,
                           codec=CodecConfig("identity"))
    s_p, m_p = _run_rounds(fr_p, data)
    s_pc, m_pc = _run_rounds(fr_pc, data)
    assert float(m_p["agg_norm"]) == float(m_pc["agg_norm"])
    for a, b in zip(jax.tree.leaves(s_p), jax.tree.leaves(s_pc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_packed_resnet_forced_equivalence():
    """BasicBlock ResNets have a packed formulation (grouped convs +
    per-channel BatchStatsNorm): forced pack_factor=2 on a tiny
    ResNet-10 round matches unpacked within tolerance.  ('auto' would
    decline — wide stages — which test_auto_fallback covers.)"""
    task = TaskSpec(model="resnet10", input_shape=(8, 8, 3),
                    num_classes=4, lr=0.1).build()
    server = Server.from_config(aggregator="Mean", lr=0.5)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 4, 8, 8, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(2, 4)), jnp.int32)
    ln = jnp.full((2,), 4, jnp.int32)
    mal = jnp.zeros((2,), bool)
    out = {}
    for packing in (None, ClientPacking(2)):
        fr = FedRound(task=task, server=server, batch_size=2,
                      num_clients=2, packing=packing)
        state = fr.init(jax.random.PRNGKey(0), 2)
        state, m = jax.jit(fr.step)(state, x, y, ln, mal,
                                    jax.random.PRNGKey(3))
        out[packing is None] = (state, m)
    (s_p, m_p), (s_u, m_u) = out[False], out[True]
    np.testing.assert_allclose(float(m_u["train_loss"]),
                               float(m_p["train_loss"]), rtol=RTOL)
    _assert_close_trees(s_u, s_p)


# ---------------------------------------------------------------------------
# explicit dropout-key discipline (models/layers.py::keyed_dropout)
# ---------------------------------------------------------------------------


def test_keyed_dropout_discipline():
    """Masks are pure functions of (key, layer index): same key -> same
    output, different keys differ, eval needs no key, train without a
    key fails loudly."""
    m = MLP(hidden1=8, hidden2=8, num_classes=4)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 16)))["params"]
    x = jnp.ones((2, 16))
    k = jax.random.PRNGKey(7)
    a = m.apply({"params": params}, x, train=True, dropout_key=k)
    b = m.apply({"params": params}, x, train=True, dropout_key=k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = m.apply({"params": params}, x, train=True,
                dropout_key=jax.random.PRNGKey(8))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    m.apply({"params": params}, x)  # eval: no key needed
    with pytest.raises(ValueError, match="dropout key"):
        m.apply({"params": params}, x, train=True)


# ---------------------------------------------------------------------------
# eligibility: auto falls back loudly, forced raises
# ---------------------------------------------------------------------------


def _auto_decision(**cfg_kw):
    from blades_tpu.algorithms.config import FedavgConfig

    cfg = FedavgConfig()
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    cfg.client_packing = "auto"
    cfg.validate()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fr = cfg.get_fed_round()
    return fr, cfg._packing_decision, [str(x.message) for x in w]


def test_auto_packs_eligible_cnn():
    fr, dec, warned = _auto_decision(dataset="fashionmnist", num_clients=8,
                                     global_model="cnn")
    assert fr.packing == ClientPacking(2)
    assert dec == {"requested": "auto", "pack_factor": 2,
                   "packed_lanes": 4, "fallback": None}
    assert not any("falling back" in m for m in warned)


@pytest.mark.parametrize("kw,reason", [
    (dict(dataset="cifar10", num_clients=8, global_model="resnet18"),
     "wide stages"),
    (dict(dataset="fashionmnist", num_clients=7, global_model="cnn"),
     "not divisible"),
    (dict(dataset="fashionmnist", num_clients=8, global_model="mlp"),
     "vreg"),
])
def test_auto_fallback_is_loud(kw, reason):
    """Acceptance: 'auto' falls back LOUDLY (warning + recorded reason)
    on ineligible configs — ResNet-18 wide stages, n % P != 0, and
    models whose widths already fill the vector lanes."""
    fr, dec, warned = _auto_decision(**kw)
    assert fr.packing is None
    assert dec["pack_factor"] == 1 and reason in dec["fallback"]
    assert any("falling back" in m and reason in m for m in warned)


def test_auto_fallback_on_training_hook_adversary():
    """Training-side attacks hook per-client local training, which the
    packed lane has no formulation for — auto declines with the reason;
    update-forging adversaries (ALIE/IPM) pack fine."""
    fr, data = _tiny_round("Mean")
    adv = get_adversary({"type": "SignFlip"}, num_clients=6, num_byzantine=2)
    import dataclasses

    fr = dataclasses.replace(fr, adversary=adv)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        fr2, dec = resolve_client_packing(fr, "auto", num_clients=6)
    assert fr2.packing is None and "hooks local training" in dec["fallback"]
    assert any("falling back" in str(x.message) for x in w)
    # forced: same condition is a hard error
    with pytest.raises(PackingUnsupported, match="hooks local training"):
        resolve_client_packing(fr, 2, num_clients=6)


# End-to-end auto-resolution run (~5 s); the resolver's decision logic
# is covered tier-1 by the resolve_client_packing unit tests above
# (PR 20 budget rebalance).
@pytest.mark.slow
def test_auto_fallback_when_auto_execution_resolves_streamed(monkeypatch):
    """'auto' packing keeps its loud-fallback contract when
    execution='auto' itself resolves to the streamed round (HBM-driven,
    invisible to resolve_client_packing): the Fedavg constructor warns,
    strips the packing, records the reason, and trains unpacked instead
    of hard-failing."""
    from blades_tpu.algorithms.config import FedavgConfig
    from blades_tpu.algorithms.fedavg import Fedavg

    monkeypatch.setattr(Fedavg, "dense_matrix_hbm_limit", classmethod(
        lambda cls: 0))
    cfg = (FedavgConfig()
           .data(dataset="fashionmnist", num_clients=8)
           .training(global_model="cnn", aggregator="Median", server_lr=1.0,
                     train_batch_size=8)
           .resources(client_packing="auto"))
    cfg.validate()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        algo = cfg.build()
    assert any("falling back" in str(x.message)
               and "streaming" in str(x.message) for x in w)
    assert algo.fed_round.packing is None
    dec = algo.packing_summary
    assert dec["pack_factor"] == 1 and "streaming" in dec["fallback"]
    assert np.isfinite(algo.train()["train_loss"])


def test_forced_packing_validation_errors():
    from blades_tpu.algorithms.config import FedavgConfig

    with pytest.raises(ValueError, match="does not divide"):
        FedavgConfig().data(num_clients=7).resources(
            client_packing=2).validate()
    with pytest.raises(ValueError, match="int must be >= 2"):
        FedavgConfig().resources(client_packing=0).validate()
    with pytest.raises(ValueError, match="num_devices>1 is an unsupported"):
        c = FedavgConfig().data(num_clients=8)
        c.num_devices = 2
        c.resources(client_packing=2).validate()
    with pytest.raises(ValueError, match="dense round"):
        c = FedavgConfig().data(num_clients=8)
        c.execution = "streamed"
        c.resources(client_packing=2).validate()


# ---------------------------------------------------------------------------
# sweep integration: rows, summaries, kill-and-resume across layouts
# ---------------------------------------------------------------------------


def _packed_experiments(client_packing, rounds=3, **cfg):
    return {
        "packed": {
            "run": "FEDAVG",
            "stop": {"training_iteration": rounds},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 6,
                                   "train_bs": 8},
                "global_model": "mlp",
                "evaluation_interval": rounds,
                "server_config": {"lr": 1.0},
                "client_packing": client_packing,
                **cfg,
            },
        }
    }


def test_packed_trial_streams_and_summarises(tmp_path):
    """pack_factor/packed_lanes appear per round in metrics.jsonl
    (schema-valid) and the sweep summary carries the packing decision."""
    from blades_tpu.obs.schema import main as schema_main
    from blades_tpu.tune import run_experiments

    [s] = run_experiments(_packed_experiments(2),
                          storage_path=str(tmp_path), verbose=0,
                          lanes=False, cost_analysis=False)
    assert "status" not in s
    assert s["packing"] == {"requested": 2, "pack_factor": 2,
                            "packed_lanes": 3, "fallback": None}
    tdir = Path(s["dir"])
    assert schema_main([str(tdir / "metrics.jsonl")]) == 0
    rows = [json.loads(l)
            for l in (tdir / "metrics.jsonl").read_text().splitlines()]
    assert len(rows) == 3
    assert all(r["pack_factor"] == 2 and r["packed_lanes"] == 3
               for r in rows)


def test_packed_kill_and_resume_to_unpacked(tmp_path):
    """Acceptance: kill a PACKED run mid-sweep (the chaos layer's
    SimulatedPreemption harness), resume it UNPACKED — RoundState is
    layout-free, so the restore just works, the round sequence has no
    duplicates/gaps, and the whole trajectory matches an end-to-end
    unpacked run within the packed-equivalence tolerance."""
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    base = run_experiments(
        _packed_experiments("off", rounds=6, evaluation_interval=6),
        storage_path=str(tmp_path / "base"), verbose=0, lanes=False,
        cost_analysis=False)
    kill = run_experiments(
        _packed_experiments(2, rounds=6, evaluation_interval=6),
        storage_path=str(tmp_path / "kill"), verbose=0, lanes=False,
        cost_analysis=False,
        checkpoint_freq=2, preempt_after=5)
    assert kill[0].get("status") == "ERROR"  # preempted, max_failures=0
    resumed = run_experiments(
        _packed_experiments("off", rounds=6, evaluation_interval=6),
        storage_path=str(tmp_path / "kill"), verbose=0, lanes=False,
        cost_analysis=False,
        checkpoint_freq=2, resume=True)
    (b,), (r,) = base, resumed
    assert "status" not in r and r["rounds"] == 6
    assert r.get("resumed") == "from round 4"
    tdir = Path(r["dir"])
    assert verify_result_rounds(tdir / "result.json") == list(range(1, 7))
    rows_b = [json.loads(l) for l in
              (Path(b["dir"]) / "result.json").read_text().splitlines()]
    rows_r = [json.loads(l) for l in
              (tdir / "result.json").read_text().splitlines()]
    for rb, rr in zip(rows_b, rows_r):
        assert rb["training_iteration"] == rr["training_iteration"]
        np.testing.assert_allclose(rb["train_loss"], rr["train_loss"],
                                   rtol=RTOL)
    np.testing.assert_allclose(rows_b[-1]["test_acc"],
                               rows_r[-1]["test_acc"], atol=1e-3)


@pytest.mark.slow
def test_laned_trials_carry_packing_stamps(tmp_path):
    """Laned trials (one vmapped program per seed group) run the packed
    local round inside each lane and stamp pack_factor/packed_lanes
    into every row; group summaries surface the packing slice."""
    from blades_tpu.tune import run_experiments

    exps = _packed_experiments(2, rounds=2, evaluation_interval=0)
    exps["packed"]["config"]["dataset_config"]["seed"] = {
        "grid_search": [1, 2]}
    summaries = run_experiments(exps, storage_path=str(tmp_path), verbose=0,
                                lanes=True, cost_analysis=False)
    assert len(summaries) == 2
    for s in summaries:
        assert s.get("lanes") == 2, s
        assert s["packing"] == {"pack_factor": 2, "packed_lanes": 3}
        rows = [json.loads(l) for l in
                (Path(s["dir"]) / "metrics.jsonl").read_text().splitlines()]
        assert rows and all(r["pack_factor"] == 2 for r in rows)

"""Failure detection + elastic recovery tests (SURVEY.md §5).

The reference's fault machinery is inherited from Ray
(FaultTolerantActorManager, Tune trial retry — ref:
fllib/core/execution/actor_manager.py:25, worker_group.py:95-127).  The
TPU-native equivalents under test here (blades_tpu/core/health.py):
lane-level detection/neutralisation inside the jitted round, round-level
aggregate guards, and checkpoint-restart trial retry in the sweep runner.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.core.health import guard_server_state, sanitize_updates


def test_sanitize_updates_zeroes_nonfinite_lanes():
    u = jnp.array([[1.0, 2.0], [jnp.nan, 3.0], [4.0, jnp.inf], [5.0, 6.0]])
    clean, healthy = sanitize_updates(u)
    assert healthy.tolist() == [True, False, False, True]
    assert jnp.isfinite(clean).all()
    # The WHOLE unhealthy lane is zeroed — its finite entries came from
    # the same diverged run and would still poison a Mean.
    assert clean[1].tolist() == [0.0, 0.0]
    assert clean[2].tolist() == [0.0, 0.0]
    assert jnp.array_equal(clean[0], u[0]) and jnp.array_equal(clean[3], u[3])


def test_guard_server_state_keeps_params_advances_round():
    server = Server.from_config(aggregator="Mean", lr=1.0)
    task = TaskSpec(model="mlp", input_shape=(28, 28, 1)).build()
    params = task.init_params(jax.random.PRNGKey(0))
    old = server.init(params, num_clients=4)
    new, _ = server.step(old, jnp.ones((4, sum(
        p.size for p in jax.tree.leaves(params)))))
    bad = guard_server_state(jnp.array(False), new, old)
    assert int(bad.round) == 1  # the round happened
    for a, b in zip(jax.tree.leaves(bad.params), jax.tree.leaves(old.params)):
        assert jnp.array_equal(a, b)  # ...but the update was discarded
    ok = guard_server_state(jnp.array(True), new, old)
    for a, b in zip(jax.tree.leaves(ok.params), jax.tree.leaves(new.params)):
        assert jnp.array_equal(a, b)


@pytest.fixture(scope="module")
def tiny_fr():
    from blades_tpu.models import MLP

    task = TaskSpec(model=MLP(hidden1=8, hidden2=8, num_classes=4),
                    input_shape=(8, 8, 1), num_classes=4, lr=0.1).build()
    server = Server.from_config(aggregator="Mean", lr=0.5)
    fr = FedRound(task=task, server=server, batch_size=4,
                  num_batches_per_round=1, health_check=True)
    rng = np.random.default_rng(0)
    n = 6
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(n, 8)), jnp.int32)
    ln = jnp.full((n,), 8, jnp.int32)
    state = fr.init(jax.random.PRNGKey(0), n)
    return fr, state, x, y, ln


def test_round_recovers_from_nan_client(tiny_fr):
    """A client with a corrupt (NaN) shard is detected, neutralised, and
    training continues — the lane-health analogue of marking an actor
    unhealthy and routing around it."""
    fr, state, x, y, ln = tiny_fr
    x = x.at[2].set(jnp.nan)  # client 2's data is corrupt
    mal = jnp.zeros(x.shape[0], bool)
    step = jax.jit(fr.step)
    new_state, m = step(state, x, y, ln, mal, jax.random.PRNGKey(1))
    assert int(m["num_unhealthy"]) == 1
    assert bool(m["round_ok"])
    for p in jax.tree.leaves(new_state.server.params):
        assert jnp.isfinite(p).all()
    # And the model actually moved (the 5 healthy lanes still aggregated).
    moved = any(
        not jnp.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(new_state.server.params),
                        jax.tree.leaves(state.server.params))
    )
    assert moved


def test_round_guard_skips_nonfinite_aggregate(tiny_fr):
    """If the aggregate itself is non-finite (here: a post-sanitize forging
    adversary emitting inf), the server update is skipped — params survive
    unchanged, the round counter still advances."""
    from blades_tpu.adversaries import get_adversary

    fr, state, x, y, ln = tiny_fr
    n = x.shape[0]
    adv = get_adversary("IPM", num_clients=n, num_byzantine=2, scale=float("inf"))
    fr_bad = FedRound(task=fr.task, server=fr.server, adversary=adv,
                      batch_size=4, num_batches_per_round=1, health_check=True)
    mal = jnp.arange(n) < 2
    step = jax.jit(fr_bad.step)
    new_state, m = step(state, x, y, ln, mal, jax.random.PRNGKey(1))
    assert not bool(m["round_ok"])
    assert int(m["round"]) == int(state.server.round) + 1
    for a, b in zip(jax.tree.leaves(new_state.server.params),
                    jax.tree.leaves(state.server.params)):
        assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# Sweep-level trial fault tolerance (Tune's max_failures).
# ---------------------------------------------------------------------------


class _FlakyConfig:
    """Minimal config for a fake trainable (the reference registers mock
    trainables for exactly this, ref: blades/algorithms/registry.py:37-48)."""

    crash_state = {"remaining": 0}  # class-level: survives rebuilds

    def update_from_dict(self, d):
        self.cfg = d
        return self

    def build(self):
        return _FlakyAlgo(self.cfg)


class _FlakyAlgo:
    def __init__(self, cfg):
        self._iteration = 0
        self._last_eval = {}
        self.crash_at = cfg.get("crash_at", -1)

    @property
    def iteration(self):
        return self._iteration

    def train(self):
        self._iteration += 1
        if (self._iteration == self.crash_at
                and _FlakyConfig.crash_state["remaining"] > 0):
            _FlakyConfig.crash_state["remaining"] -= 1
            raise RuntimeError("injected fault")
        return {"training_iteration": self._iteration, "test_acc": 0.5}

    def save_checkpoint(self, d):
        import pathlib

        p = pathlib.Path(d)
        p.mkdir(parents=True, exist_ok=True)
        (p / "it.json").write_text(json.dumps({"it": self._iteration}))
        return d

    def load_checkpoint(self, path):
        import pathlib

        self._iteration = json.loads(
            (pathlib.Path(path) / "it.json").read_text())["it"]


@pytest.fixture()
def flaky_registry():
    from blades_tpu.algorithms import registry

    registry.ALGORITHMS["FLAKY"] = lambda: (_FlakyAlgo, _FlakyConfig)
    yield
    registry.ALGORITHMS.pop("FLAKY", None)


def test_sweep_retries_failed_trial_from_checkpoint(tmp_path, flaky_registry):
    from blades_tpu.tune import run_experiments

    _FlakyConfig.crash_state["remaining"] = 1  # crash once, then heal
    experiments = {"exp": {"run": "FLAKY", "stop": {"training_iteration": 8},
                           "config": {"crash_at": 5}}}
    summaries = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0,
        checkpoint_freq=2, max_failures=2,
    )
    (s,) = summaries
    assert "status" not in s  # recovered, not failed
    assert s["rounds"] == 8
    err = tmp_path / "exp" / "exp_00000" / "error.txt"
    assert err.exists() and "injected fault" in err.read_text()


def test_sweep_marks_trial_failed_and_continues(tmp_path, flaky_registry):
    from blades_tpu.tune import run_experiments

    _FlakyConfig.crash_state["remaining"] = 10  # crashes forever
    experiments = {"exp": {"run": "FLAKY", "stop": {"training_iteration": 8},
                           "config": {"crash_at": {"grid_search": [3, -1]}}}}
    summaries = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0, max_failures=1,
    )
    assert len(summaries) == 2
    assert summaries[0].get("status") == "ERROR"
    assert "injected fault" in summaries[0]["error"]
    # The second trial (crash_at=-1, never crashes) still ran to completion.
    assert "status" not in summaries[1]
    assert summaries[1]["rounds"] == 8


@pytest.mark.slow
def test_dsharded_health_check_detects_and_recovers():
    """Cross-shard row health on the width-sharded giant-federation path:
    a NaN client lane is detected via psum over its shards, zeroed, and
    the round still updates the model (SURVEY.md §5 failure detection on
    the multi-chip production path)."""
    import dataclasses

    import jax

    from blades_tpu.adversaries import make_malicious_mask
    from blades_tpu.data import DatasetCatalog
    from blades_tpu.parallel import make_mesh
    from blades_tpu.parallel.dsharded import dsharded_step

    n = 16
    ds = DatasetCatalog.get_dataset("mnist", num_clients=n)
    task = TaskSpec(model="mlp", lr=0.1, input_shape=(28, 28, 1)).build()
    server = Server.from_config(aggregator="Mean", lr=1.0)
    fr = FedRound(task=task, server=server, batch_size=8, health_check=True)
    x = jnp.array(ds.train.x).at[5].set(jnp.nan)  # client 5's shard corrupt
    y, ln = jnp.array(ds.train.y), jnp.array(ds.train.lengths)
    mal = make_malicious_mask(n, 0)
    mesh = make_mesh()
    state = fr.init(jax.random.PRNGKey(0), n)
    step = dsharded_step(fr, mesh)
    new_state, m = step(state, x, y, ln, mal, jax.random.PRNGKey(1))
    assert int(m["num_unhealthy"]) == 1
    assert bool(m["round_ok"])
    for p in jax.tree.leaves(new_state.server.params):
        assert jnp.isfinite(p).all()
    moved = any(
        not jnp.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(new_state.server.params),
                        jax.tree.leaves(state.server.params))
    )
    assert moved


# ---------------------------------------------------------------------------
# Chaos layer: deterministic fault injection (blades_tpu/faults).
# ---------------------------------------------------------------------------


def test_fault_injector_validates_config():
    from blades_tpu.faults import FaultInjector

    with pytest.raises(ValueError, match="dropout_rate"):
        FaultInjector(dropout_rate=1.0)
    with pytest.raises(ValueError, match="corrupt_mode"):
        FaultInjector(corrupt_mode="segfault")
    with pytest.raises(ValueError, match="staleness"):
        FaultInjector(staleness=0)
    with pytest.raises(ValueError, match="dropout_schedule"):
        FaultInjector(dropout_schedule=((0, 1.5),))
    # YAML hands lists; the injector normalizes to a hashable tuple.
    inj = FaultInjector(dropout_schedule=[[10, 0.5], [0, 0.1]])
    assert inj.dropout_schedule == ((0, 0.1), (10, 0.5))
    hash(inj)  # static jit config must stay hashable


def test_fault_injector_deterministic_in_seed_and_round():
    """Realizations are pure in (seed, round): same inputs replay the SAME
    failures (the retry/resume determinism contract), different rounds and
    seeds draw different ones."""
    from blades_tpu.faults import FaultInjector

    u = jnp.ones((16, 4))
    inj = FaultInjector(seed=5, dropout_rate=0.5)
    _, _, p1, _, _ = inj.inject(u, None, jnp.int32(3))
    _, _, p2, _, _ = inj.inject(u, None, jnp.int32(3))
    _, _, p3, _, _ = inj.inject(u, None, jnp.int32(4))
    _, _, p4, _, _ = FaultInjector(seed=6, dropout_rate=0.5).inject(
        u, None, jnp.int32(3))
    assert jnp.array_equal(p1, p2)
    assert not jnp.array_equal(p1, p3) or not jnp.array_equal(p1, p4)
    assert bool(p1.any())  # graceful degradation: never an empty round


def test_fault_injector_dropout_schedule():
    from blades_tpu.faults import FaultInjector

    inj = FaultInjector(dropout_rate=0.0, dropout_schedule=((5, 0.9),))
    assert float(inj.dropout_rate_at(jnp.int32(0))) == 0.0
    assert float(inj.dropout_rate_at(jnp.int32(4))) == 0.0
    assert float(inj.dropout_rate_at(jnp.int32(5))) == pytest.approx(0.9)
    assert float(inj.dropout_rate_at(jnp.int32(99))) == pytest.approx(0.9)
    u = jnp.ones((32, 4))
    _, _, early, _, _ = inj.inject(u, None, jnp.int32(0))
    _, _, late, _, _ = inj.inject(u, None, jnp.int32(50))
    assert bool(early.all())
    assert int(late.sum()) < 32


def test_fault_injector_straggler_delivers_stale_update():
    """A straggler lane delivers the update it computed `staleness` rounds
    ago, via the ring buffer threaded through RoundState."""
    from blades_tpu.faults import FaultInjector

    n, d = 4, 3
    inj = FaultInjector(seed=1, num_stragglers=1, staleness=2)
    buf = inj.init_stale_buffer(n, d)
    assert buf.shape == (2, n, d)
    rounds = [jnp.full((n, d), float(t + 1)) for t in range(4)]
    for t, fresh in enumerate(rounds):
        out, buf, part, strag, _ = inj.inject(fresh, buf, jnp.int32(t))
        assert int(strag.sum()) == 1
        assert bool((strag & part).sum() == strag.sum())  # stragglers participate
        lane = int(jnp.argmax(strag))
        if t < 2:  # buffer still cold: stragglers deliver zeros
            assert out[lane].tolist() == [0.0] * d
        else:  # delivers the (t - staleness)'th round's update
            assert out[lane].tolist() == [float(t - 1)] * d
        others = ~strag
        assert jnp.array_equal(out[others], fresh[others])


def test_fault_injector_corruption_caught_by_sanitize():
    """Lane corruption emits exactly what sanitize_updates exists to catch
    (nan/inf); 'overflow' stays finite on arrival and is the aggregate
    guard's problem instead."""
    from blades_tpu.faults import FaultInjector

    u = jnp.ones((16, 4))
    for mode, finite_on_arrival in (("nan", False), ("inf", False),
                                    ("overflow", True)):
        inj = FaultInjector(seed=2, corrupt_rate=0.5, corrupt_mode=mode)
        out, _, part, _, corr = inj.inject(u, None, jnp.int32(0))
        assert int(corr.sum()) > 0
        assert bool((corr & part).sum() == corr.sum())  # only participants
        assert bool(jnp.isfinite(out[corr]).all()) == finite_on_arrival
        clean, healthy = sanitize_updates(out, part)
        assert jnp.isfinite(clean).all()
        if not finite_on_arrival:
            assert jnp.array_equal(~healthy, corr)


def test_sanitize_updates_participation_restricts_unhealthy_count():
    """A dropped lane cannot be unhealthy — it delivered nothing — but its
    non-finite row is still zeroed (it never enters the aggregate)."""
    u = jnp.array([[1.0, 2.0], [jnp.nan, 3.0], [jnp.inf, 0.0], [5.0, 6.0]])
    part = jnp.array([True, True, False, True])
    clean, healthy = sanitize_updates(u, part)
    assert healthy.tolist() == [True, False, True, True]
    assert jnp.isfinite(clean).all()


def test_detection_metrics_conditioned_on_participation():
    """A malicious client that dropped out was neither caught nor missed:
    with participation given, it leaves the confusion matrix entirely."""
    from blades_tpu.obs.forensics import detection_metrics

    benign_mask = jnp.array([True, True, True, True])  # nothing flagged
    malicious = jnp.array([True, False, False, False])
    part = jnp.array([False, True, True, True])  # the malicious lane dropped
    dense = detection_metrics(benign_mask, malicious)
    cond = detection_metrics(benign_mask, malicious, participation=part)
    assert float(dense["byz_recall"]) == 0.0   # missed the malicious lane
    assert float(cond["byz_recall"]) == 1.0    # ...which never reported
    # And a flagged dropped lane is not a false positive either.
    flagged_dropped = jnp.array([False, True, True, True])
    cond2 = detection_metrics(flagged_dropped, malicious, participation=part)
    assert float(cond2["byz_fpr"]) == 0.0
    assert int(cond2["num_flagged"]) == 0


# ---------------------------------------------------------------------------
# Participation-aware aggregation (ops/aggregators.py masked_call).
# ---------------------------------------------------------------------------


def _mk_aggregator(name):
    from blades_tpu.ops.aggregators import AGGREGATORS

    cls = AGGREGATORS[name]
    if name in ("Trimmedmean", "Multikrum", "DnC"):
        return cls(num_byzantine=1)
    return cls()


def _with_trusted(name, updates, mask):
    """FLTrust judges against an appended trusted row that always
    'participates' (the server's own update)."""
    if name != "FLTrust":
        return updates, mask
    return (jnp.concatenate([updates, updates.mean(0, keepdims=True)]),
            jnp.concatenate([mask, jnp.ones((1,), bool)]))


@pytest.fixture(scope="module")
def faulty_round():
    """Chaos-layer fixture: a tiny-MLP federation plus a REAL update matrix
    (one local round's output) and a FedRound factory parameterized by
    aggregator + FaultInjector — shared by the property sweep and the
    end-to-end chaos tests."""
    from blades_tpu.models import MLP

    task = TaskSpec(model=MLP(hidden1=8, hidden2=8, num_classes=4),
                    input_shape=(8, 8, 1), num_classes=4, lr=0.1).build()
    rng = np.random.default_rng(7)
    n = 8
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(n, 8)), jnp.int32)
    ln = jnp.full((n,), 8, jnp.int32)

    def make(aggregator, faults=None, **kw):
        server = Server.from_config(aggregator=aggregator, lr=0.5)
        return FedRound(task=task, server=server, batch_size=4,
                        num_clients=n, faults=faults, **kw)

    # One real update matrix for aggregator-level property tests.
    fr = make("Mean")
    state = fr.init(jax.random.PRNGKey(0), n)
    from blades_tpu.core.task import (identity_data_hook, identity_grad_hook,
                                      identity_round_begin_hook,
                                      identity_round_end_hook)
    from blades_tpu.data.sampler import sample_client_batches

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    bx, by = sample_client_batches(k1, x, y, ln, 4, 1)
    updates, *_ = fr.task.local_round_batched(
        state.server.params, state.client_opt, bx, by,
        jax.random.split(k2, n), jnp.zeros((n,), bool),
        identity_data_hook, identity_grad_hook,
        identity_round_begin_hook, identity_round_end_hook,
    )
    return {"task": task, "n": n, "x": x, "y": y, "ln": ln, "make": make,
            "updates": updates}


@pytest.mark.parametrize("name", sorted(
    __import__("blades_tpu.ops.aggregators",
               fromlist=["AGGREGATORS"]).AGGREGATORS))
def test_dropout_sweep_finite_and_shape_stable(faulty_round, name):
    """Property sweep (satellite): dropout in {0, 0.3, 0.7} x every
    registered aggregator — the participation-aware aggregate stays finite
    and shape-stable, diag never keeps a dropped lane, on a real tiny-MLP
    update matrix with the dropout realizations drawn by the FaultInjector
    itself.  ONE jitted program per aggregator (rates reuse it)."""
    from blades_tpu.faults import FaultInjector

    updates = faulty_round["updates"]
    n, d = updates.shape
    agg = _mk_aggregator(name)
    state = agg.init(d, n)
    key = jax.random.PRNGKey(11)

    @jax.jit
    def run(u, m):
        out, _ = agg.masked_call(u, m, state, key=key)
        _, _, diag = agg.masked_diagnose(u, m, state, key=key)
        return out, diag["benign_mask"]

    for rate in (0.0, 0.3, 0.7):
        inj = FaultInjector(seed=13, dropout_rate=rate)
        _, _, part, _, _ = inj.inject(updates, None, jnp.int32(1))
        if rate == 0.0:
            assert bool(part.all())
        u, m = _with_trusted(name, updates, part)
        out, benign = run(u, m)
        assert out.shape == (d,), (name, rate)
        assert jnp.isfinite(out).all(), (name, rate)
        # no aggregator may 'keep' a lane that never reported
        assert benign.shape == (n,), (name, rate)
        assert not bool((benign & ~part[:n]).any()), (name, rate)


@pytest.mark.parametrize("name", sorted(
    __import__("blades_tpu.ops.aggregators",
               fromlist=["AGGREGATORS"]).AGGREGATORS))
def test_full_participation_bit_identical_to_dense(faulty_round, name):
    """Regression (acceptance): with full participation the masked path
    dispatches to the EXACT dense trace — aggregates bit-identical for
    every registered aggregator — and the diag bundle matches diagnose().
    All four entry points share ONE jitted program so the comparison is
    compile-for-compile fair."""
    updates = faulty_round["updates"]
    n, d = updates.shape
    agg = _mk_aggregator(name)
    state = agg.init(d, n)
    key = jax.random.PRNGKey(5)
    u, ones = _with_trusted(name, updates, jnp.ones((n,), bool))

    @jax.jit
    def run(uu, mm):
        dense, _ = agg(uu, state, key=key)
        msk, _ = agg.masked_call(uu, mm, state, key=key)
        _, _, ddiag = agg.diagnose(uu, state, key=key)
        _, _, mdiag = agg.masked_diagnose(uu, mm, state, key=key)
        return dense, msk, ddiag, mdiag

    dense, msk, ddiag, mdiag = run(u, ones)
    assert jnp.array_equal(dense, msk), name
    assert jnp.array_equal(ddiag["benign_mask"], mdiag["benign_mask"]), name
    assert jnp.array_equal(ddiag["scores"], mdiag["scores"]), name


def test_noop_injector_round_params_bit_identical(faulty_round):
    """faults=None and an all-disabled FaultInjector produce bit-identical
    round outputs: the full-participation mask takes the dense aggregation
    trace via lax.cond."""
    from blades_tpu.faults import FaultInjector

    fx = faulty_round
    mal = jnp.zeros((fx["n"],), bool)
    fr0 = fx["make"]("Mean")
    fr1 = fx["make"]("Mean", faults=FaultInjector(seed=0))
    s0 = fr0.init(jax.random.PRNGKey(0), fx["n"])
    s1 = fr1.init(jax.random.PRNGKey(0), fx["n"])
    s0, m0 = jax.jit(fr0.step)(s0, fx["x"], fx["y"], fx["ln"], mal,
                               jax.random.PRNGKey(1))
    s1, m1 = jax.jit(fr1.step)(s1, fx["x"], fx["y"], fx["ln"], mal,
                               jax.random.PRNGKey(1))
    for a, b in zip(jax.tree.leaves(s0.server.params),
                    jax.tree.leaves(s1.server.params)):
        assert jnp.array_equal(a, b)
    assert int(m1["num_participating"]) == fx["n"]
    assert int(m1["num_dropped"]) == 0
    assert float(m0["train_loss"]) == float(m1["train_loss"])


@pytest.mark.parametrize("aggregator", [
    "Mean",
    {"type": "Trimmedmean", "num_byzantine": 1},
    {"type": "Multikrum", "num_byzantine": 1},
])
def test_chaos_run_20_rounds_stays_finite(faulty_round, aggregator):
    """Acceptance: 30% Bernoulli dropout + 1 straggler with staleness 2,
    20 rounds on the tiny MLP — finite params, num_participating logged
    per round, detection metrics conditioned on participation."""
    from blades_tpu.faults import FaultInjector

    fx = faulty_round
    n = fx["n"]
    inj = FaultInjector(seed=21, dropout_rate=0.3, num_stragglers=1,
                        staleness=2)
    fr = fx["make"](aggregator, faults=inj, forensics=True)
    mal = jnp.arange(n) < 1
    state = fr.init(jax.random.PRNGKey(0), n)
    assert state.stale.shape == (2, n, state.stale.shape[-1])
    step = jax.jit(fr.step)
    per_round = []
    for key in jax.random.split(jax.random.PRNGKey(2), 20):
        state, m_r = step(state, fx["x"], fx["y"], fx["ln"], mal, key)
        per_round.append(m_r)
    m = jax.tree.map(lambda *vs: jnp.stack(vs), *per_round)
    for p in jax.tree.leaves(state.server.params):
        assert jnp.isfinite(p).all()
    part = m["num_participating"]
    assert part.shape == (20,)
    assert bool((part >= 1).all()) and bool((part <= n).all())
    assert bool((part < n).any())  # dropout actually fired
    assert bool((m["num_straggled"] == 1).all())
    assert m["num_dropped"].tolist() == (n - part).tolist()
    # Detection metrics present and valid (conditioned on participation).
    for k in ("byz_precision", "byz_recall", "byz_fpr"):
        assert jnp.isfinite(m[k]).all()
        assert bool((m[k] >= 0).all()) and bool((m[k] <= 1).all())
    # Fault realizations are seed-driven: identical across aggregators.
    assert part.tolist() == faulty_round.setdefault(
        "_part_trace", part.tolist())


# ---------------------------------------------------------------------------
# Host layer: atomic checkpoints, retry backoff, preemption simulation.
# ---------------------------------------------------------------------------


def test_atomic_checkpoint_publishes_or_leaves_orphan_tmp(tmp_path):
    from blades_tpu.faults.host import atomic_checkpoint
    from blades_tpu.tune.sweep import _latest_checkpoint

    def good_save(d):
        import pathlib

        p = pathlib.Path(d)
        p.mkdir(parents=True)
        (p / "it.json").write_text('{"it": 4}')

    atomic_checkpoint(good_save, tmp_path / "ckpt_000004")
    assert (tmp_path / "ckpt_000004" / "it.json").exists()
    assert not (tmp_path / "ckpt_000004.tmp").exists()

    def killed_mid_write(d):
        import pathlib

        p = pathlib.Path(d)
        p.mkdir(parents=True)
        (p / "it.json").write_text('{"it":')  # torn payload
        raise KeyboardInterrupt("SIGKILL stand-in")

    with pytest.raises(KeyboardInterrupt):
        atomic_checkpoint(killed_mid_write, tmp_path / "ckpt_000006")
    # The kill left an orphaned .tmp, never a torn ckpt_000006 ...
    assert (tmp_path / "ckpt_000006.tmp").exists()
    assert not (tmp_path / "ckpt_000006").exists()
    # ... and restore skips AND deletes the orphan.
    latest = _latest_checkpoint(tmp_path)
    assert latest is not None and latest.name == "ckpt_000004"
    assert not (tmp_path / "ckpt_000006.tmp").exists()


def test_atomic_checkpoint_rewrites_same_round(tmp_path):
    """Re-checkpointing a round after a resume replaces the old dir."""
    from blades_tpu.faults.host import atomic_checkpoint

    def save(tag):
        def _s(d):
            import pathlib

            p = pathlib.Path(d)
            p.mkdir(parents=True)
            (p / "v.txt").write_text(tag)
        return _s

    atomic_checkpoint(save("old"), tmp_path / "ckpt_000002")
    atomic_checkpoint(save("new"), tmp_path / "ckpt_000002")
    assert (tmp_path / "ckpt_000002" / "v.txt").read_text() == "new"


def test_retry_backoff_deterministic_exponential_capped():
    from blades_tpu.faults.host import retry_backoff

    a = [retry_backoff(i, "trial:0", base=0.5, cap=30.0) for i in (1, 2, 3, 9)]
    b = [retry_backoff(i, "trial:0", base=0.5, cap=30.0) for i in (1, 2, 3, 9)]
    assert a == b  # deterministic jitter (reproducible retry timeline)
    assert a[0] < a[1] < a[2]  # exponential growth
    # jitter in [0.5, 1.5) around min(cap, base * 2^(n-1))
    assert 0.25 <= a[0] < 0.75
    assert 15.0 <= a[3] < 45.0  # capped at 30s before jitter
    # distinct trials de-synchronize
    assert retry_backoff(1, "trial:1") != retry_backoff(1, "trial:0")
    with pytest.raises(ValueError):
        retry_backoff(0, "trial:0")


def test_sweep_retries_back_off_between_restarts(tmp_path, flaky_registry,
                                                 monkeypatch):
    from blades_tpu.tune import run_experiments

    sleeps = []
    monkeypatch.setattr("time.sleep", lambda s: sleeps.append(s))
    _FlakyConfig.crash_state["remaining"] = 2
    experiments = {"exp": {"run": "FLAKY", "stop": {"training_iteration": 6},
                           "config": {"crash_at": 3}}}
    summaries = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0,
        checkpoint_freq=2, max_failures=3,
        retry_backoff_base=0.25, retry_backoff_cap=8.0,
    )
    (s,) = summaries
    assert "status" not in s and s["rounds"] == 6
    assert len(sleeps) == 2  # one backoff per restart
    assert sleeps[1] > sleeps[0]  # exponential


def test_preempt_after_kill_and_resume_in_process(tmp_path, flaky_registry):
    """Acceptance: a SimulatedPreemption landing between the result write
    and the checkpoint save is retried from the latest checkpoint with no
    duplicated or skipped rounds in result.json."""
    from blades_tpu.faults.host import SimulatedPreemption  # noqa: F401
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    _FlakyConfig.crash_state["remaining"] = 0  # never self-crashes
    experiments = {"exp": {"run": "FLAKY", "stop": {"training_iteration": 8},
                           "config": {"crash_at": -1}}}
    summaries = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0,
        checkpoint_freq=2, max_failures=1, preempt_after=5,
        retry_backoff_base=0.0,
    )
    (s,) = summaries
    assert "status" not in s and s["rounds"] == 8
    tdir = tmp_path / "exp" / "exp_00000"
    assert "SimulatedPreemption" in (tdir / "error.txt").read_text()
    # No-duplicate/no-gap round sequence despite the mid-trial kill.
    assert verify_result_rounds(tdir / "result.json") == list(range(1, 9))
    # metrics stream was truncated + re-entered consistently too.
    its = [json.loads(l)["training_iteration"]
           for l in (tdir / "metrics.jsonl").read_text().splitlines()]
    assert its == list(range(1, 9))


def test_preempt_after_resume_in_second_sweep(tmp_path, flaky_registry):
    """Kill-and-resume across sweep invocations: the preempted trial is
    marked failed (max_failures=0), then a --resume sweep restores from
    its latest checkpoint and completes the sequence exactly."""
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    _FlakyConfig.crash_state["remaining"] = 0
    experiments = {"exp": {"run": "FLAKY", "stop": {"training_iteration": 8},
                           "config": {"crash_at": -1}}}
    first = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0,
        checkpoint_freq=2, preempt_after=5,
    )
    assert first[0].get("status") == "ERROR"
    second = run_experiments(
        experiments, storage_path=str(tmp_path), verbose=0,
        checkpoint_freq=2, resume=True,
    )
    (s,) = second
    assert "status" not in s and s["rounds"] == 8
    assert s.get("resumed") == "from round 4"  # ckpt_000004, not round 5
    tdir = tmp_path / "exp" / "exp_00000"
    assert verify_result_rounds(tdir / "result.json") == list(range(1, 9))


def test_verify_result_rounds_rejects_duplicates_and_gaps(tmp_path):
    from blades_tpu.tune.sweep import verify_result_rounds

    p = tmp_path / "result.json"
    p.write_text("".join(json.dumps({"training_iteration": i}) + "\n"
                         for i in (1, 2, 2, 3)))
    with pytest.raises(ValueError, match="duplicates or gaps"):
        verify_result_rounds(p)
    p.write_text("".join(json.dumps({"training_iteration": i}) + "\n"
                         for i in (1, 2, 4)))
    with pytest.raises(ValueError, match="duplicates or gaps"):
        verify_result_rounds(p)
    p.write_text("".join(json.dumps({"training_iteration": i}) + "\n"
                         for i in (2, 4, 6)))  # a stride is a gap too
    with pytest.raises(ValueError, match="duplicates or gaps"):
        verify_result_rounds(p)
    p.write_text("".join(json.dumps({"training_iteration": i}) + "\n"
                         for i in (3, 4, 5)))
    assert verify_result_rounds(p) == [3, 4, 5]


# ---------------------------------------------------------------------------
# Obs schema: chaos-run metrics are first-class records.
# ---------------------------------------------------------------------------


def test_schema_accepts_fault_event_fields(tmp_path):
    from blades_tpu.obs.schema import validate_jsonl, validate_record

    rec = {
        "experiment": "chaos", "trial": "chaos_00000",
        "training_iteration": 3, "train_loss": 1.2, "agg_norm": 0.4,
        "update_norm_mean": 0.6, "num_participating": 6, "num_dropped": 2,
        "num_straggled": 1, "fault_seed": 21, "byz_precision": 1.0,
        "byz_recall": 0.5, "byz_fpr": 0.0, "num_flagged": 1,
    }
    assert validate_record(rec) is rec
    p = tmp_path / "metrics.jsonl"
    p.write_text(json.dumps(rec) + "\n")
    num_valid, errors = validate_jsonl(p)
    assert num_valid == 1 and not errors


def test_chaos_trial_streams_schema_valid_metrics(tmp_path):
    """End-to-end: a fault-injected FEDAVG trial through the sweep runner
    emits a metrics.jsonl the validator CLI accepts, with participation
    logged per round."""
    from blades_tpu.obs.schema import main as schema_main
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    experiments = {"chaos": {
        "run": "FEDAVG", "stop": {"training_iteration": 3},
        "config": {
            "dataset_config": {"type": "mnist", "num_clients": 6},
            "global_model": "mlp", "train_batch_size": 8,
            "evaluation_interval": 3,
            "fault_config": {"dropout_rate": 0.3, "num_stragglers": 1,
                             "staleness": 2, "seed": 5},
        },
    }}
    summaries = run_experiments(experiments, storage_path=str(tmp_path),
                                verbose=0, cost_analysis=False)
    (s,) = summaries
    assert "status" not in s
    tdir = tmp_path / "chaos" / "chaos_00000"
    assert schema_main([str(tdir / "metrics.jsonl")]) == 0
    rows = [json.loads(l)
            for l in (tdir / "metrics.jsonl").read_text().splitlines()]
    assert len(rows) == 3
    for r in rows:
        assert 1 <= r["num_participating"] <= 6
        assert r["num_participating"] + r["num_dropped"] == 6
        assert r["fault_seed"] == 5
    assert verify_result_rounds(tdir / "result.json") == [1, 2, 3]


# Dropout x Byzantine x lanes composition (~6 s); dropout imputation and
# Byzantine robustness are each pinned tier-1 separately in this file
# (PR 20 budget rebalance).
@pytest.mark.slow
def test_robustness_survives_dropout_with_byzantine_lanes():
    """Graceful degradation must not break Byzantine robustness: with 2
    poison lanes (100x) present and 20% of the benign cohort dropped,
    every robust aggregator stays at the benign scale.  Guards the
    imputation strategy — imputing dropped rows with the active-lane MEAN
    (corruptible) minted copies of the poison and captured GeoMed; the
    masked-median imputation keeps imputed rows in the benign cluster."""
    from blades_tpu.ops import get_aggregator

    key = jax.random.PRNGKey(0)
    d, nb, nm = 64, 8, 2
    benign = jax.random.normal(key, (nb, d)) * 0.1
    updates = jnp.concatenate([100.0 * jnp.ones((nm, d)), benign])
    mask = jnp.concatenate([jnp.ones((nm,), bool),  # poison lanes present
                            jax.random.uniform(key, (nb,)) > 0.3])
    assert int(mask.sum()) < nb + nm
    for name in ("Median", "Trimmedmean", "GeoMed", "Multikrum", "DnC",
                 "Signguard", "Clippedclustering", "Centeredclipping"):
        agg = get_aggregator(name, num_byzantine=nm)
        out, _ = agg.masked_call(updates, mask, agg.init(d, nb + nm), key=key)
        assert float(jnp.abs(out).max()) < 1.0, name
    # ... and the non-robust baseline still collapses (the test has teeth).
    mean = get_aggregator("Mean")
    out, _ = mean.masked_call(updates, mask, (), key=key)
    assert float(jnp.abs(out).max()) > 10.0

"""Round-pipeline perf layer tests (blades_tpu/perf + data/prefetch):

- compile-count regression: N identically-shaped sweep trials lower and
  compile the round program exactly once (the AOT executable cache);
- donation: the pre-step RoundState's buffers are invalidated after a
  donated dispatch (and stay alive with ``donate_buffers=False``);
- bit-identity: prefetch on/off reproduces the eager path exactly, per
  aggregator.
"""

import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.algorithms import FedavgConfig
from blades_tpu.ops.aggregators import AGGREGATORS
from blades_tpu.perf import cache_stats, clear_cache, fingerprint
from blades_tpu.tune import run_experiments


def tiny_config(**overrides):
    cfg = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=6, seed=3)
        .training(global_model="mlp", server_lr=1.0, train_batch_size=8,
                  aggregator={"type": "Mean"})
        .client(lr=0.1)
        .evaluation(evaluation_interval=0)
    )
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _params(algo):
    return [np.asarray(p) for p in jax.tree.leaves(algo.state.server.params)]


# ---------------------------------------------------------------------------
# AOT compile cache
# ---------------------------------------------------------------------------


def _seed_sweep(tmp_path, seeds, **kw):
    experiments = {
        "cc": {
            "run": "FEDAVG",
            "stop": {"training_iteration": 4},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 6,
                                   "train_bs": 8,
                                   "seed": {"grid_search": list(seeds)}},
                "global_model": "mlp",
                "evaluation_interval": 2,
                "server_config": {"lr": 1.0},
            },
        }
    }
    return run_experiments(experiments, storage_path=str(tmp_path),
                           verbose=0, lanes=False, **kw)


# Three full sweep trials through run_experiments (~11 s of XLA CPU
# compile); the cache-counter contract itself is asserted by the cheaper
# prefetch/driver tests below (PR 20 budget rebalance, same rule as PR 7).
@pytest.mark.slow
def test_identically_shaped_trials_compile_once(tmp_path):
    """The acceptance criterion: a sweep of >= 3 identically-shaped
    trials compiles the round program exactly once; the other trials
    are cache hits, surfaced both in the summaries and in the metrics
    stream."""
    clear_cache()
    summaries = _seed_sweep(tmp_path, seeds=(1, 2, 3))
    stats = cache_stats()
    assert stats["by_role"]["step"]["misses"] == 1, stats
    assert stats["by_role"]["step"]["hits"] >= 2, stats
    # Per-trial summary deltas: first trial owns every miss.
    assert summaries[0]["compile_cache"]["misses"] >= 1
    for s in summaries[1:]:
        assert s["compile_cache"]["misses"] == 0, s
        assert s["compile_cache"]["hits"] >= 1, s
    # The obs stream carries the counters (schema-registered fields).
    first = json.loads(
        (Path(summaries[1]["dir"]) / "metrics.jsonl").read_text()
        .splitlines()[0])
    assert first["compile_cache_misses"] == 0
    assert first["compile_cache_hits"] >= 1


@pytest.mark.slow
def test_shape_change_recompiles(tmp_path):
    """Different geometry must NOT share an executable."""
    clear_cache()
    _seed_sweep(tmp_path / "a", seeds=(1,))
    misses_6 = cache_stats()["by_role"]["step"]["misses"]
    experiments = {
        "cc8": {
            "run": "FEDAVG",
            "stop": {"training_iteration": 2},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 8,
                                   "train_bs": 8},
                "global_model": "mlp",
                "evaluation_interval": 2,
                "server_config": {"lr": 1.0},
            },
        }
    }
    run_experiments(experiments, storage_path=str(tmp_path / "b"),
                    verbose=0, lanes=False)
    assert cache_stats()["by_role"]["step"]["misses"] == misses_6 + 1


def test_fingerprint_stability():
    assert fingerprint({"a": 1, "b": [2, 3]}) == fingerprint({"b": [2, 3], "a": 1})
    assert fingerprint({"a": 1}) != fingerprint({"a": 2})


def test_fingerprint_excludes_seed_only():
    """Two configs differing only in seed share a program fingerprint;
    differing in a baked-in static (server lr) must not."""
    a = tiny_config().build()
    b = tiny_config(seed=99).build()
    c = tiny_config(server_lr=0.5).build()
    assert a._program_fingerprint() == b._program_fingerprint()
    assert a._program_fingerprint() != c._program_fingerprint()


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------


def test_donated_step_invalidates_pre_step_state():
    algo = tiny_config().build()
    leaves = jax.tree.leaves(algo.state.server.params)
    algo.train()
    assert all(l.is_deleted() for l in leaves), (
        "RoundState was not donated into the round dispatch"
    )
    # The CURRENT state is alive and usable (next round, checkpoints).
    assert all(not l.is_deleted()
               for l in jax.tree.leaves(algo.state.server.params))


def test_donation_opt_out_keeps_state_alive():
    algo = tiny_config(donate_buffers=False).build()
    leaves = jax.tree.leaves(algo.state.server.params)
    algo.train()
    assert all(not l.is_deleted() for l in leaves)


# ---------------------------------------------------------------------------
# prefetch
# ---------------------------------------------------------------------------


def test_batch_prefetcher_contract():
    from blades_tpu.data.prefetch import BatchPrefetcher

    calls = []

    def sample(key):
        calls.append(int(key))
        return ("batch", int(key))

    pf = BatchPrefetcher(sample)
    assert pf.take(0, 7) == ("batch", 7)        # cold: sync draw
    pf.stage(1, 8)
    assert pf.take(1, 8) == ("batch", 8)        # warm: staged, no redraw
    assert calls == [7, 8]
    pf.stage(2, 9)
    assert pf.take(5, 11) == ("batch", 11)      # index mismatch: redraw
    pf.stage(6, 12)
    pf.invalidate()
    assert pf.take(6, 12) == ("batch", 12)      # invalidated: redraw
    assert calls == [7, 8, 9, 11, 12, 12]


def test_prefetch_to_device_order_and_values():
    from blades_tpu.data.prefetch import prefetch_to_device

    items = [np.full((3,), i, np.float32) for i in range(5)]
    out = list(prefetch_to_device(iter(items), size=2))
    assert len(out) == 5
    for i, a in enumerate(out):
        assert isinstance(a, jax.Array)
        np.testing.assert_array_equal(np.asarray(a), items[i])


def test_prefetch_bit_identity_fedavg_driver():
    """The full driver surface: 5 Fedavg rounds with prefetch forced on
    (staged batches + prebatched program + donation + AOT cache) vs
    prefetch off — rows and params bit-equal."""
    def build(prefetch):
        cfg = tiny_config(prefetch=prefetch)
        cfg.update_from_dict({
            "num_malicious_clients": 2,
            "adversary_config": {"type": "ALIE"},
            "server_config": {"aggregator": {"type": "Median"}},
        })
        return cfg.build()

    on, off = build(True), build(False)
    assert on._prefetcher is not None and off._prefetcher is None
    rows_on = [on.train() for _ in range(5)]
    rows_off = [off.train() for _ in range(5)]
    for r_on, r_off in zip(rows_on, rows_off):
        for k in ("train_loss", "agg_norm", "update_norm_mean"):
            assert r_on[k] == r_off[k], (k, r_on[k], r_off[k])
    for p_on, p_off in zip(_params(on), _params(off)):
        np.testing.assert_array_equal(p_on, p_off)


# Tier-1 runs the headline aggregator only; the rest of the registry
# runs the identical check in the full suite (`pytest tests/`) — two
# separately compiled programs per aggregator is the irreducible cost
# (~10-14 s/case here), and the 870 s tier-1 budget on this 2-core box
# cannot absorb them (PR 7 rebalance; this box's wall-clock swings ~2x
# run to run, so tier-1 must carry real headroom under the cap).
# PR 20 rebalance: the whole grid is slow-lane now — tier-1 prefetch
# bit-identity rides test_prefetch_bit_identity_fedavg_driver instead.
_T1_AGGREGATORS = ()


@pytest.mark.parametrize("agg_name", [
    a if a in _T1_AGGREGATORS else pytest.param(a, marks=pytest.mark.slow)
    for a in sorted(AGGREGATORS)])
def test_prefetch_bit_identity_per_aggregator(agg_name):
    """5 rounds of prefetch-split execution (sample_round_batches +
    step_prebatched, the prefetch-ON program pair) vs the fused step
    (prefetch OFF): params and round metrics bit-equal.  FedRound-level
    on a deliberately tiny task so the compiles stay cheap; the
    driver-level staging/donation path is covered by
    test_prefetch_bit_identity_fedavg_driver above."""
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec

    n, f, rounds = 6, 2, 5
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=4,
                    lr=0.1).build()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 12, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(n, 12)), jnp.int32)
    ln = jnp.full((n,), 12, jnp.int32)
    mal = make_malicious_mask(n, f)
    adv = get_adversary({"type": "ALIE"}, num_clients=n, num_byzantine=f)

    server = Server.from_config(aggregator=agg_name, num_byzantine=f, lr=0.5)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  trusted_data=((x[0, :8], y[0, :8])
                                if agg_name == "FLTrust" else None))
    fused = jax.jit(fr.step)
    sample = jax.jit(fr.sample_round_batches)
    split = jax.jit(fr.step_prebatched)
    s_f = s_s = fr.init(jax.random.PRNGKey(0), n)
    key = jax.random.PRNGKey(5)
    for r in range(rounds):
        k = jax.random.fold_in(key, r)
        s_f, m_f = fused(s_f, x, y, ln, mal, k)
        bx, by = sample(x, y, ln, k)
        s_s, m_s = split(s_s, bx, by, mal, k)
        for mk in ("train_loss", "agg_norm", "update_norm_mean"):
            assert float(m_f[mk]) == float(m_s[mk]), (agg_name, r, mk)
    for a, b in zip(jax.tree.leaves(s_f.server.params),
                    jax.tree.leaves(s_s.server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=agg_name)


# ---------------------------------------------------------------------------
# persistent compilation cache wiring
# ---------------------------------------------------------------------------


def test_persistent_cache_placement(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache and then no
    directory is set in code; unset, the cache is the fixed
    ``<checkout>/.jax_cache``."""
    from blades_tpu.perf import enable_persistent_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_persistent_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        assert enable_persistent_compilation_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        # Idempotent.
        assert enable_persistent_compilation_cache() == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

"""Comm subsystem (blades_tpu/comm): compressed update codecs under
Byzantine-robust aggregation.

Covers the tentpole's acceptance criteria:

- the ``identity`` codec is bit-transparent per aggregator (aggregates,
  metrics, AND the full RoundState that checkpoints pickle) — tier-1
  runs the headline aggregators, the rest of the registry rides the
  ``slow`` lane exactly like ``tests/test_perf.py``'s identity sweep;
- stochastic uniform quantization is unbiased in expectation
  (statistical test over PRNG keys) and lands exactly on the
  ``scale * int`` wire grid;
- top-k with error feedback transmits exactly ``k`` coordinates per
  client and conserves mass (``sent + residual == pre-image``), the
  residual survives kill-and-resume bit-identically (the chaos layer's
  resume harness, extended), and the compressed run converges near the
  uncompressed baseline on the 32-client CNN smoke config (slow);
- ``comm_bytes_up`` / ``codec_bits`` / ``comm_compression_ratio`` are
  schema-registered, appear in ``metrics.jsonl`` and sweep summaries
  (sequential AND laned trials), and reconcile with
  ``parallel/comm_model.uplink_bytes``;
- the codec composes with the chaos layer (corruption lands on encoded
  payloads and is still caught by the health machinery).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.comm import CodecConfig, get_codec
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.ops.aggregators import AGGREGATORS


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_codec_config_validates():
    with pytest.raises(ValueError, match="name"):
        CodecConfig("gzip")
    with pytest.raises(ValueError, match="bits"):
        CodecConfig("quant", bits=3)
    with pytest.raises(ValueError, match="topk_ratio"):
        CodecConfig("topk", topk_ratio=0.0)
    with pytest.raises(ValueError, match="topk_ratio"):
        CodecConfig("topk", topk_ratio=1.5)
    hash(CodecConfig("topk", topk_ratio=0.1))  # static jit config


def test_get_codec_resolution():
    assert get_codec(None) is None
    c = get_codec({"type": "quant", "bits": 4})
    assert c.name == "quant" and c.bits == 4
    assert get_codec("identity").name == "identity"
    inst = CodecConfig("topk", topk_ratio=0.5)
    assert get_codec(inst) is inst
    with pytest.raises(ValueError, match="type"):
        get_codec({"bits": 8})


def test_config_builder_validates_codec_and_placement():
    from blades_tpu.algorithms import FedavgConfig

    cfg = FedavgConfig().data(dataset="mnist", num_clients=4)
    cfg.communication(codec={"type": "quant", "bits": 3})
    with pytest.raises(ValueError, match="bits"):
        cfg.validate()
    cfg2 = (FedavgConfig().data(dataset="mnist", num_clients=4)
            .communication(codec={"type": "topk"})
            .resources(execution="streamed"))
    with pytest.raises(ValueError, match="codec"):
        cfg2.validate()
    cfg3 = (FedavgConfig().data(dataset="mnist", num_clients=8)
            .communication(codec={"type": "topk"})
            .resources(num_devices=2))
    with pytest.raises(ValueError, match="codec"):
        cfg3.validate()


# ---------------------------------------------------------------------------
# codec math
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_quantization_unbiased_in_expectation(bits):
    """Acceptance: E[decode(encode(u))] == u over the rounding keys.

    With K keys the per-coordinate standard error is <= scale / (2*sqrt(K))
    (Bernoulli rounding variance <= scale^2/4); the tolerance sits at
    ~6 sigma, and the deterministic-floor control below shows the test
    has teeth at the same tolerance."""
    codec = CodecConfig("quant", bits=bits)
    u = jax.random.normal(jax.random.PRNGKey(0), (3, 257)) * 2.0
    K = 4096
    keys = jax.random.split(jax.random.PRNGKey(7), K)
    dec = jax.jit(jax.vmap(
        lambda k: codec.encode_decode(u, None, k)[0]))(keys)
    scale = np.asarray(jnp.max(jnp.abs(u), axis=1, keepdims=True)) / (
        2 ** (bits - 1) - 1)
    err = np.asarray(dec.mean(axis=0)) - np.asarray(u)
    tol = 6.0 * scale / (2.0 * np.sqrt(K))
    assert (np.abs(err) <= tol).all(), np.abs(err / scale).max()
    # Teeth: deterministic floor-rounding is biased low by ~scale/2.
    floor_dec = np.floor(np.asarray(u) / scale) * scale
    floor_err = floor_dec - np.asarray(u)
    assert (np.abs(floor_err) > tol).mean() > 0.9


def test_quantization_lands_on_wire_grid():
    """Decoded values are exactly scale * integer in [-s, s] — the codec
    simulates a real int8/int4 wire, not a lossy float blur."""
    for bits in (8, 4):
        codec = CodecConfig("quant", bits=bits)
        s = 2 ** (bits - 1) - 1
        u = jax.random.normal(jax.random.PRNGKey(3), (5, 130))
        dec = codec.encode_decode(u, None, jax.random.PRNGKey(4))[0]
        scale = np.asarray(jnp.max(jnp.abs(u), axis=1, keepdims=True)) / s
        grid = np.asarray(dec) / scale
        np.testing.assert_allclose(grid, np.round(grid), atol=1e-4)
        assert np.abs(grid).max() <= s + 1e-4
    # All-zero rows survive (no 0/0 scale blowup).
    z = codec.encode_decode(jnp.zeros((2, 16)), None, jax.random.PRNGKey(5))[0]
    assert np.asarray(z).tolist() == np.zeros((2, 16)).tolist()


def test_topk_exact_k_and_error_feedback():
    n, d = 4, 200
    codec = CodecConfig("topk", topk_ratio=0.05)  # k = 10
    k = codec.topk_k(d)
    assert k == 10
    u = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    res0 = codec.init_residual(n, d)
    assert res0.shape == (n, d) and not np.asarray(res0).any()
    sent, res1 = codec.encode_decode(u, res0, jax.random.PRNGKey(2))
    # Exactly k transmitted coordinates per client, the k largest.
    nz = np.asarray((sent != 0).sum(axis=1))
    assert nz.tolist() == [k] * n
    thr = np.sort(np.abs(np.asarray(u)), axis=1)[:, -k]
    assert (np.abs(np.asarray(u))[np.asarray(sent) != 0]
            >= np.repeat(thr, k) - 1e-7).all()
    # Error feedback conserves mass: sent + residual == pre-image.
    np.testing.assert_allclose(np.asarray(sent + res1), np.asarray(u),
                               rtol=1e-6)
    # The residual is re-injected: a coordinate too small to transmit
    # accumulates until it wins a later round's selection.
    tiny = jnp.zeros((1, d)).at[0, 0].set(0.3)
    big = jnp.zeros((1, d)).at[0, 1:k + 1].set(1.0)  # exactly k winners
    r = codec.init_residual(1, d)
    sent1, r = codec.encode_decode(tiny + big, r, jax.random.PRNGKey(0))
    assert float(sent1[0, 0]) == 0.0 and float(r[0, 0]) == pytest.approx(0.3)
    # Feed zero fresh updates: the carried 0.3 beats the zeros and ships.
    sent2, r = codec.encode_decode(jnp.zeros((1, d)), r, jax.random.PRNGKey(0))
    assert float(sent2[0, 0]) == pytest.approx(0.3)
    assert float(r[0, 0]) == pytest.approx(0.0)
    # Without error feedback there is no residual state at all.
    nof = CodecConfig("topk", topk_ratio=0.05, error_feedback=False)
    assert not nof.needs_residual and nof.init_residual(n, d) is None
    sent_nof, res_nof = nof.encode_decode(u, None, jax.random.PRNGKey(2))
    assert res_nof is None
    assert np.asarray((sent_nof != 0).sum(axis=1)).tolist() == [k] * n


# ---------------------------------------------------------------------------
# byte accounting: metric <-> analytic model reconciliation
# ---------------------------------------------------------------------------


def test_payload_bytes_reconciles_with_comm_model():
    """The codec's payload_bytes and comm_model.uplink_bytes are two
    INDEPENDENT arithmetics of the same wire — they must agree for every
    codec, and the compressed d-sharded what-if must shrink the swap."""
    from blades_tpu.parallel.comm_model import (dsharded_round_volumes,
                                                uplink_bytes)

    n, d = 32, 136_074
    for codec in (CodecConfig("identity"),
                  CodecConfig("quant", bits=8),
                  CodecConfig("quant", bits=4),
                  CodecConfig("topk", topk_ratio=0.01),
                  CodecConfig("topk", topk_ratio=0.5, error_feedback=False)):
        assert codec.payload_bytes(n, d) == uplink_bytes(n, d, codec), codec
    assert uplink_bytes(n, d) == n * d * 4
    # int8 quant ~4x down, topk-1% ~50x down vs the dense f32 wire.
    dense = uplink_bytes(n, d)
    assert dense / uplink_bytes(n, d, CodecConfig("quant", bits=8)) > 3.9
    assert dense / uplink_bytes(n, d, CodecConfig("topk", topk_ratio=0.01)) > 40
    # The analytic ICI model covers compressed rounds: the axis swap
    # carries the codec payload, every other collective is unchanged.
    base = dsharded_round_volumes(1000, d, 8, update_bytes=4)
    comp = dsharded_round_volumes(1000, d, 8, update_bytes=4,
                                  codec=CodecConfig("quant", bits=8))
    swap_b = next(v for v in base if v.label == "update_matrix_swap")
    swap_c = next(v for v in comp if v.label == "update_matrix_swap")
    assert swap_b.payload_bytes / swap_c.payload_bytes > 3.9
    rest_b = sorted((v.label, v.payload_bytes) for v in base
                    if v.label != "update_matrix_swap")
    rest_c = sorted((v.label, v.payload_bytes) for v in comp
                    if v.label != "update_matrix_swap")
    assert rest_b == rest_c


def test_round_metrics_fields_schema_valid():
    from blades_tpu.obs.schema import validate_record

    m = CodecConfig("quant", bits=4).round_metrics(32, 100_000)
    assert m["comm_bytes_up"] == 32 * (50_000 + 4)
    assert m["codec_bits"] == 4
    assert m["comm_compression_ratio"] == pytest.approx(8.0, rel=1e-3)
    rec = {"experiment": "e", "trial": "t", "training_iteration": 1, **m,
           "elided_lanes": 4}
    assert validate_record(rec) is rec


# ---------------------------------------------------------------------------
# identity codec: bit-transparent per aggregator
# ---------------------------------------------------------------------------

# Tier-1 runs ONE headline aggregator (PR 7 budget rebalance: each case
# compiles two MLP round programs, ~8 s here); the rest of the registry
# runs the identical check in the full suite (`pytest tests/`).
_T1_AGGREGATORS = ("Mean",)


def _tiny_round(agg_name, codec=None, faults=None, **kw):
    from blades_tpu.models import MLP

    task = TaskSpec(model=MLP(hidden1=8, hidden2=8, num_classes=4),
                    input_shape=(8, 8, 1), num_classes=4, lr=0.1).build()
    n, f = 6, 2
    server = Server.from_config(aggregator=agg_name, num_byzantine=f, lr=0.5)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 12, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(n, 12)), jnp.int32)
    ln = jnp.full((n,), 12, jnp.int32)
    mal = jnp.arange(n) < f
    from blades_tpu.adversaries import get_adversary

    adv = get_adversary({"type": "ALIE"}, num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_clients=n, codec=codec, faults=faults,
                  trusted_data=((x[0, :8], y[0, :8])
                                if agg_name == "FLTrust" else None), **kw)
    return fr, (x, y, ln, mal)


@pytest.mark.parametrize("agg_name", [
    a if a in _T1_AGGREGATORS else pytest.param(a, marks=pytest.mark.slow)
    for a in sorted(AGGREGATORS)])
def test_identity_codec_bit_identical_per_aggregator(agg_name):
    """Acceptance: the identity codec reproduces the codec-free round
    bit-for-bit — aggregates, metrics, and the full RoundState that
    checkpoints pickle — for every registered aggregator."""
    fr_off, data = _tiny_round(agg_name, codec=None)
    fr_id, _ = _tiny_round(agg_name, codec=CodecConfig("identity"))
    x, y, ln, mal = data
    s_off = fr_off.init(jax.random.PRNGKey(0), 6)
    s_id = fr_id.init(jax.random.PRNGKey(0), 6)
    # Identity carries no residual: pytrees (and thus checkpoints,
    # sharding specs, donation layouts) are structurally unchanged.
    assert s_id.residual is None and s_id.stale is None
    step_off, step_id = jax.jit(fr_off.step), jax.jit(fr_id.step)
    key = jax.random.PRNGKey(5)
    for r in range(3):
        k = jax.random.fold_in(key, r)
        s_off, m_off = step_off(s_off, x, y, ln, mal, k)
        s_id, m_id = step_id(s_id, x, y, ln, mal, k)
        for mk in ("train_loss", "agg_norm", "update_norm_mean"):
            assert float(m_off[mk]) == float(m_id[mk]), (agg_name, r, mk)
    for a, b in zip(jax.tree.leaves(s_off), jax.tree.leaves(s_id)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=agg_name)


def test_compressing_codec_changes_the_geometry():
    """The inverse control: a real codec must actually alter what the
    aggregator sees (otherwise the identity test proves nothing)."""
    fr_off, data = _tiny_round("Median")
    fr_q, _ = _tiny_round("Median", codec=CodecConfig("quant", bits=4))
    x, y, ln, mal = data
    s_off = fr_off.init(jax.random.PRNGKey(0), 6)
    s_q = fr_q.init(jax.random.PRNGKey(0), 6)
    k = jax.random.PRNGKey(5)
    _, m_off = jax.jit(fr_off.step)(s_off, x, y, ln, mal, k)
    _, m_q = jax.jit(fr_q.step)(s_q, x, y, ln, mal, k)
    assert float(m_off["agg_norm"]) != float(m_q["agg_norm"])
    assert np.isfinite(float(m_q["train_loss"]))


def test_codec_composes_with_fault_injection():
    """Chaos x comm: lane corruption lands on ENCODED payloads (the
    codec runs first) and the health machinery still catches and
    neutralises it; the straggler ring replays post-codec rows."""
    from blades_tpu.faults import FaultInjector

    inj = FaultInjector(seed=3, dropout_rate=0.2, corrupt_rate=0.4,
                        corrupt_mode="nan", num_stragglers=1, staleness=1)
    fr, data = _tiny_round("Median", codec=CodecConfig("topk", topk_ratio=0.1),
                           faults=inj, health_check=True)
    x, y, ln, mal = data
    state = fr.init(jax.random.PRNGKey(0), 6)
    assert state.residual is not None and state.stale is not None
    step = jax.jit(fr.step)
    unhealthy = []
    for key in jax.random.split(jax.random.PRNGKey(2), 6):
        state, m = step(state, x, y, ln, mal, key)
        assert int(m["num_unhealthy"]) >= 0
        assert int(m["num_participating"]) <= 6
        unhealthy.append(int(m["num_unhealthy"]))
    for p in jax.tree.leaves(state.server.params):
        assert jnp.isfinite(p).all()
    assert jnp.isfinite(state.residual).all()
    assert any(u > 0 for u in unhealthy)  # corruption actually fired


# ---------------------------------------------------------------------------
# sweep integration: metrics stream, summaries, laned trials
# ---------------------------------------------------------------------------


def _codec_experiments(codec, rounds=3, **cfg):
    return {
        "comm": {
            "run": "FEDAVG",
            "stop": {"training_iteration": rounds},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 6,
                                   "train_bs": 8},
                "global_model": "mlp",
                "evaluation_interval": rounds,
                "server_config": {"lr": 1.0},
                "codec_config": codec,
                **cfg,
            },
        }
    }


def test_compressed_trial_streams_and_summarises_comm_metrics(tmp_path):
    """Acceptance: comm_bytes_up appears per round in metrics.jsonl
    (schema-valid), in the sweep summary, and reconciles with the
    analytic uplink model for a compressed config."""
    from blades_tpu.obs.schema import main as schema_main
    from blades_tpu.parallel.comm_model import uplink_bytes
    from blades_tpu.tune import run_experiments

    codec = {"type": "quant", "bits": 8}
    [s] = run_experiments(_codec_experiments(codec),
                          storage_path=str(tmp_path), verbose=0,
                          lanes=False, cost_analysis=False)
    assert "status" not in s
    d = 136_074  # mnist MLP width (784-128-256-10 + biases)
    want = uplink_bytes(6, d, get_codec(codec))
    assert s["comm"] == {"comm_bytes_up": want, "codec_bits": 8,
                         "comm_compression_ratio":
                             round(6 * d * 4 / want, 4),
                         # Aggregation-domain provenance (ISSUE 11):
                         # stamped whenever a codec is configured so
                         # f32/wire A/B rows are separable.
                         "agg_domain": "f32", "agg_domain_bits": 32}
    tdir = Path(s["dir"])
    assert schema_main([str(tdir / "metrics.jsonl")]) == 0
    rows = [json.loads(l)
            for l in (tdir / "metrics.jsonl").read_text().splitlines()]
    assert len(rows) == 3
    for r in rows:
        assert r["comm_bytes_up"] == want
        assert r["codec_bits"] == 8
        assert r["comm_compression_ratio"] > 3.9


@pytest.mark.slow
def test_laned_trials_carry_comm_metrics(tmp_path):
    """Laned trials (one vmapped program per seed group) stamp the same
    comm fields into every lane's rows — the codec is static shared
    config, so a seed grid lanes exactly as before."""
    from blades_tpu.tune import run_experiments

    exps = _codec_experiments({"type": "topk", "topk_ratio": 0.02},
                              rounds=2, evaluation_interval=0)
    exps["comm"]["config"]["dataset_config"]["seed"] = {
        "grid_search": [1, 2]}
    summaries = run_experiments(exps, storage_path=str(tmp_path), verbose=0,
                                lanes=True, cost_analysis=False)
    assert len(summaries) == 2
    for s in summaries:
        assert s.get("lanes") == 2, s  # actually ran as a lane group
        assert s["comm"]["codec_bits"] == 32
        rows = [json.loads(l) for l in
                (Path(s["dir"]) / "metrics.jsonl").read_text().splitlines()]
        assert rows and all(r["comm_bytes_up"] == s["comm"]["comm_bytes_up"]
                            for r in rows)


# ---------------------------------------------------------------------------
# error-feedback residual across kill-and-resume (satellite)
# ---------------------------------------------------------------------------


def _rows_no_timing(tdir):
    rows = []
    for ln in (Path(tdir) / "result.json").read_text().splitlines():
        r = json.loads(ln)
        r.pop("timers", None)
        r.pop("compile_cache_hits", None)
        r.pop("compile_cache_misses", None)
        rows.append(r)
    return rows


def test_error_feedback_residual_survives_kill_and_resume(tmp_path):
    """Satellite: checkpoint mid-sweep with the top-k codec on, get
    killed (SimulatedPreemption between the result write and the
    checkpoint save), resume from an OLDER checkpoint — the re-run
    rounds must replay the interrupted trajectory bit-identically,
    which only holds if the checkpoint carries the EF residual and
    load_checkpoint restores it (extends tests/test_faults.py's resume
    harness to the comm subsystem)."""
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    # Eval on the FINAL round only: the repeat-last-eval keys rows carry
    # between evals are driver-session state a rebuilt (post-kill) driver
    # does not replay — a cosmetic resume artifact predating the comm
    # subsystem; the trajectory itself (losses, norms, final eval) is
    # what the residual restore must reproduce exactly.
    codec = {"type": "topk", "topk_ratio": 0.02, "error_feedback": True}
    base = run_experiments(
        _codec_experiments(codec, rounds=6, evaluation_interval=6),
        storage_path=str(tmp_path / "base"), verbose=0, lanes=False,
        cost_analysis=False)
    kill = run_experiments(
        _codec_experiments(codec, rounds=6, evaluation_interval=6),
        storage_path=str(tmp_path / "kill"), verbose=0, lanes=False,
        cost_analysis=False,
        checkpoint_freq=2, max_failures=1, preempt_after=5,
        retry_backoff_base=0.0)
    (b,), (k,) = base, kill
    assert "status" not in b and "status" not in k
    # The kill really happened and restore came from round 4's checkpoint.
    assert "SimulatedPreemption" in (
        Path(k["dir"]) / "error.txt").read_text()
    assert verify_result_rounds(Path(k["dir"]) / "result.json") == \
        list(range(1, 7))
    # Bit-identical trajectory: every row (losses, norms, eval) equal.
    assert _rows_no_timing(b["dir"]) == _rows_no_timing(k["dir"])


@pytest.mark.slow
def test_load_checkpoint_cold_starts_missing_residual(tmp_path):
    """A checkpoint from a codec-free run resumed under top-k+EF starts
    the residual cold (zeros), exactly like a fresh init — the stale-
    ring-buffer convention.  Slow lane: two fresh Fedavg builds for a
    migration edge path; the residual-restore contract itself is tier-1
    via the kill-and-resume bit-identity test above."""
    from blades_tpu.algorithms import FedavgConfig

    def cfg(codec):
        c = (FedavgConfig().data(dataset="mnist", num_clients=6, seed=3)
             .training(global_model="mlp", server_lr=1.0, train_batch_size=8)
             .client(lr=0.1).evaluation(evaluation_interval=0))
        if codec:
            c.communication(codec=codec)
        return c.build()

    plain = cfg(None)
    plain.train()
    path = plain.save_checkpoint(str(tmp_path / "ck"))
    ef = cfg({"type": "topk", "topk_ratio": 0.05})
    ef.load_checkpoint(path)
    assert ef.state.residual is not None
    assert not np.asarray(ef.state.residual).any()
    ef.train()  # and the compressed round runs from the restored state
    assert np.asarray(ef.state.residual).any()


# ---------------------------------------------------------------------------
# convergence: top-k + EF near the uncompressed baseline (acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_topk_error_feedback_converges_cnn_smoke():
    """Acceptance: top-k (1%) + error feedback on the 32-client CNN
    smoke config reaches within tolerance of the uncompressed baseline
    in a <= 20-round run — error feedback re-injects the 99% it never
    shipped, so the compressed trajectory tracks the dense one."""
    from blades_tpu.algorithms import FedavgConfig

    def run(codec):
        cfg = (FedavgConfig()
               .data(dataset="mnist", num_clients=32, seed=1)
               .training(global_model="cnn", server_lr=1.0,
                         train_batch_size=32)
               .client(lr=0.1)
               .evaluation(evaluation_interval=20))
        if codec:
            cfg.communication(codec=codec)
        algo = cfg.build()
        row = {}
        for _ in range(20):
            row = algo.train()
        return row

    base = run(None)
    comp = run({"type": "topk", "topk_ratio": 0.01, "error_feedback": True})
    assert np.isfinite(comp["train_loss"])
    assert comp["comm_compression_ratio"] > 40
    # Within tolerance of the uncompressed baseline after 20 rounds.
    assert comp["test_acc"] >= base["test_acc"] - 0.10, (base, comp)
    assert comp["train_loss"] <= base["train_loss"] + 0.5, (base, comp)

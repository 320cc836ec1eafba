"""The sequence task and its model (models/mla_moe.py) at a size a CPU
holds: hidden 64, 4 heads, ranks 32/16, head dims 16+8/16, 8 experts of 32
with top-2, vocabulary 256, rows of 32 tokens.  (The comparison with the
plain reference lives in tests/perfbench/test_pb_mla_moe_lm.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from blades_tpu.core.task import TaskSpec
from blades_tpu.data.datasets import build_packed_tokens, pack_documents
from blades_tpu.models.catalog import ModelCatalog
from blades_tpu.models.mla_moe import ExpertShare, MlaMoeConfig

SMALL = dict(
    type="mla_moe_lm", vocab_size=256, hidden_size=64, num_hidden_layers=3,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    first_expert=0, experts_held=4, num_experts_per_tok=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_nextn_predict_layers=1, attn_block=8)


def _task(**kw):
    return TaskSpec(model=dict(SMALL, **kw), num_classes=256,
                    input_shape=(32,), lr=0.1).build()


def _docs(rng, lengths):
    return [rng.integers(1, 256, n).astype(np.int32) for n in lengths]


def test_shares_add_up_to_the_uncut_layer():
    """8 experts held 4 + 4: the two shares' routed parts, plus the shared
    expert counted once, equal the uncut layer's output."""
    kw = {k: v for k, v in SMALL.items() if k != "type"}
    whole = ExpertShare(MlaMoeConfig.from_dict(dict(kw, experts_held=8)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    full = whole.apply({"params": params}, x)
    # The shared expert alone: a share that holds experts nobody selects.
    silent = jax.tree.map(lambda a: a, params)
    silent = dict(silent, router_bias=jnp.zeros_like(params["router_bias"]))
    none_held = dict(silent, **{k: jnp.zeros_like(params[k])
                                for k in ("experts_gate", "experts_up",
                                          "experts_down")})
    shared = whole.apply({"params": none_held}, x)
    routed = []
    for first in (0, 4):
        share = ExpertShare(MlaMoeConfig.from_dict(
            dict(kw, first_expert=first, experts_held=4)))
        p = dict(params, **{k: params[k][first:first + 4]
                            for k in ("experts_gate", "experts_up",
                                      "experts_down")})
        out, state = share.apply({"params": p}, x, mutable=["stats"])
        routed.append(out - shared)
        assert state["stats"]["expert_tokens"][0].shape == (4,)
    np.testing.assert_allclose(routed[0] + routed[1] + shared, full,
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(routed[0]).max()) > 0      # both shares do work
    assert float(jnp.abs(routed[1]).max()) > 0


@pytest.mark.parametrize("mtp", [0, 1])
def test_a_packed_row_gives_each_document_what_it_gives_alone(mtp):
    task = _task(num_nextn_predict_layers=mtp)
    params = task.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    a, b = _docs(rng, [15, 15])

    def summed(docs, seq_len):
        x, y = pack_documents(docs, seq_len)
        assert x.shape[0] == 1

        def total(p):
            # One plane at a time, each times its own count of targets.
            planes = task.sequence_planes(task.cast_to_compute(p),
                                          jnp.asarray(x))
            out = 0.0
            for depth, (lg, w) in enumerate(
                    zip(planes, task.model.loss_weights)):
                t = task.plane_targets(jnp.asarray(y), depth)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    lg, jnp.maximum(t, 0))
                out = out + w * (ce * (t >= 0)).sum()
            return out

        return jax.jit(jax.value_and_grad(total))(params)

    both, g_both = summed([a, b], 32)
    la, ga = summed([a], 16)
    lb, gb = summed([b], 16)
    np.testing.assert_allclose(both, la + lb, rtol=1e-5)
    for gp, g1, g2 in zip(*map(jax.tree.leaves, (g_both, ga, gb))):
        np.testing.assert_allclose(gp, g1 + g2, rtol=2e-4, atol=1e-6)


def test_targets_stop_at_a_document_boundary():
    x, y = pack_documents([np.array([5, 6]), np.array([7])], 8)
    assert x.tolist() == [[0, 5, 6, 0, 7, 0, 0, 0]]
    assert y.tolist() == [[5, 6, -1, 7, -1, -1, -1, -1]]
    t2 = _task().plane_targets(jnp.asarray(y), 1)
    assert t2.tolist() == [[6, -1, -1, -1, -1, -1, -1, -1]]


def test_vmap_over_three_clients_equals_three_single_calls():
    task = _task()
    params = task.init_params(jax.random.PRNGKey(0))
    ds = build_packed_tokens(num_clients=3, seed=3, seq_len=32,
                             vocab_size=256, train_rows=4, test_rows=1,
                             doc_median=10)
    bx = jnp.asarray(ds.train.x[:, :2].reshape(3, 1, 2, 32))   # 1 step of 2
    by = jnp.asarray(ds.train.y[:, :2].reshape(3, 1, 2, 32))
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    mal = jnp.zeros((3,), bool)
    opt = jax.tree.map(lambda a: jnp.broadcast_to(a, (3,) + a.shape),
                       task.init_client_opt_state(params))
    upd, _, loss, stats = jax.jit(task.local_round_batched)(
        params, opt, bx, by, keys, mal)
    assert stats["expert_tokens"].shape == (3, 3, 4)   # lanes, layers, held
    single = jax.jit(task.local_round)
    for i in range(3):
        u1, _, l1, s1 = single(
            params, task.init_client_opt_state(params), bx[i], by[i],
            keys[i], mal[i])
        np.testing.assert_allclose(upd[i], u1, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(loss[i], l1, rtol=1e-6)
        np.testing.assert_array_equal(stats["expert_tokens"][i],
                                      s1["expert_tokens"])


def test_the_router_stays_float32_under_bf16_compute():
    task = TaskSpec(model=SMALL, num_classes=256, input_shape=(32,),
                    compute_dtype="bfloat16").build()
    cast = jax.eval_shape(lambda k: task.cast_to_compute(
        task.init_params(k)), jax.random.PRNGKey(0))
    moe = cast["layer_1"]["moe"]
    assert moe["router_kernel"].dtype == jnp.float32
    assert moe["router_bias"].dtype == jnp.float32
    assert moe["experts_gate"].dtype == jnp.bfloat16
    assert cast["embed"].dtype == jnp.bfloat16


def test_router_bias_gets_no_gradient_and_evaluate_counts_tokens():
    task = _task()
    params = task.init_params(jax.random.PRNGKey(0))
    ds = build_packed_tokens(num_clients=1, seed=1, seq_len=32,
                             vocab_size=256, train_rows=2, test_rows=2,
                             doc_median=10)
    x, y = jnp.asarray(ds.train.x[0]), jnp.asarray(ds.train.y[0])
    g = jax.jit(jax.grad(task.loss_fn))(params, x, y)
    assert float(jnp.abs(g["layer_1"]["moe"]["router_bias"]).max()) == 0.0
    assert float(jnp.abs(g["layer_1"]["moe"]["router_kernel"]).max()) > 0
    ev = jax.jit(task.evaluate)(params, x, y, jnp.array([True, False]))
    assert float(ev["count"]) == float((ds.train.y[0][0] >= 0).sum())
    main_only = _task(num_nextn_predict_layers=0)
    p0 = {k: v for k, v in params.items() if not k.startswith("mtp_")}
    np.testing.assert_allclose(
        ev["ce_sum"] / ev["count"],
        jax.jit(main_only.loss_fn)(p0, x[:1], y[:1]), rtol=1e-5)


def test_the_model_reduces_its_own_stats_to_the_rows_counters():
    """(lanes, layers, held) tokens, (lanes, layers) routed pairs and
    fused attention calls -> the five counters; a model that sows nothing
    has none."""
    task = _task()
    stats = {"expert_tokens": jnp.asarray(
        [[[6, 0, 2, 0]], [[0, 0, 8, 0]]], jnp.int32),
        "routed_pairs": jnp.full((2, 1), 64, jnp.int32),
        "attn_fused": jnp.asarray([[1, 1], [1, 0]], jnp.int32)}
    got = jax.jit(task.round_counters)(stats)
    assert int(got["attn_fused_calls"]) == 3
    assert int(got["expert_tokens_max"]) == 8
    assert float(got["expert_tokens_mean"]) == 2.0
    assert float(got["routed_here_share"]) == 16 / 128
    assert int(got["zero_expert_blocks"]) == 5
    image = TaskSpec(model="mlp", num_classes=10,
                     input_shape=(28, 28, 1)).build()
    assert image.round_counters({}) == {}


def test_a_dict_spec_resolves_and_an_unknown_key_is_refused():
    model = ModelCatalog.get_model(SMALL, num_classes=256)
    assert model.cfg.hidden_size == 64 and model.cfg.vocab_size == 256
    with pytest.raises(KeyError):
        ModelCatalog.get_model(dict(SMALL, hiden_size=64))
    with pytest.raises(ValueError):
        ModelCatalog.get_model(dict(SMALL, first_expert=6))


def test_an_image_tasks_local_round_is_the_parents_bit_for_bit():
    """The stats plumbing leaves an image task's step as it was: the same
    bits as the step written the way the parent wrote it (a plain
    ``value_and_grad`` of ``loss_fn``, no aux, no stats in the scan).  (A
    whole round against the parent's checkout, by hash: CHANGES.md, PR 29.)"""
    task = TaskSpec(model="mlp", num_classes=10, input_shape=(28, 28, 1),
                    lr=0.1, momentum=0.9).build()
    params = task.init_params(jax.random.PRNGKey(0))
    opt = task.init_client_opt_state(params)
    bx = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 28, 28, 1))
    by = jax.random.randint(jax.random.PRNGKey(2), (3, 4), 0, 10)
    key = jax.random.PRNGKey(3)

    @jax.jit
    def parents(params, opt, bx, by, key):
        def step(carry, inp):
            p, o = carry
            x, y, k = inp
            loss, g = jax.value_and_grad(task.loss_fn)(p, x, y, k)
            u, o = task.client_optimizer().update(g, o, p)
            return (optax.apply_updates(p, u), o), loss

        (p, o), losses = jax.lax.scan(
            step, (params, opt), (bx, by, jax.random.split(key, 3)))
        flat = jnp.concatenate([(a - b).ravel() for a, b in zip(
            jax.tree.leaves(p), jax.tree.leaves(params))])
        return flat, losses.mean()

    upd, _, loss, stats = jax.jit(task.local_round)(
        params, opt, bx, by, key, jnp.asarray(False))
    assert stats == {}
    want_upd, want_loss = parents(params, opt, bx, by, key)
    np.testing.assert_array_equal(upd, want_upd)
    np.testing.assert_array_equal(loss, want_loss)
    assert task.sequence is False
    assert task.loss_and_stats(params, bx[0], by[0], key)[1] == {}

"""The looped (weight-tied) language model (models/looped_lm.py) and the
sequence loss's per-position plane weighting (core/task.py) at a size a CPU
holds: hidden 64, 4 heads of 16, MLP 96, 2 layers run 3 times, vocabulary
256, rows of 32 tokens, float32.  (The comparison with the plain reference
lives in tests/perfbench/test_pb_looped_lm.py.)"""

import functools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from blades_tpu.core.task import TaskSpec
from blades_tpu.data.datasets import build_packed_tokens
from blades_tpu.models.catalog import ModelCatalog
from blades_tpu.models.layers import (RMSNorm, attention_scores_computed,
                                      packed_positions)
from blades_tpu.models.looped_lm import DecoderLayer, LoopedConfig, LoopedLM
from blades_tpu.obs import device_phases

SMALL = dict(
    type="looped_lm", vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, total_ut_steps=3, attn_block=8)


def _task(spec):
    return TaskSpec(model=spec, num_classes=256, input_shape=(32,)).build()


# One task for the tests that share SMALL.
TASK = _task(SMALL)


@functools.lru_cache(maxsize=None)
def _loss_and_grad():
    """SMALL's gradient step, compiled once: called by one test, its
    program text read by another."""
    params = TASK.init_params(jax.random.PRNGKey(0))
    x, y = _row()
    return jax.jit(jax.value_and_grad(TASK.loss_fn)).lower(
        params, x, y).compile()


def _row():
    """One client's two packed rows of 32 tokens and their targets."""
    ds = build_packed_tokens(num_clients=1, seed=1, seq_len=32,
                             vocab_size=256, train_rows=2, test_rows=1,
                             doc_median=10)
    return jnp.asarray(ds.train.x[0]), jnp.asarray(ds.train.y[0])


def _mean_ce(logits, y):
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), jnp.maximum(y, 0))
    valid = (y >= 0).astype(jnp.float32)
    return ce, valid, (ce * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def _stack(cfg, params, h, segment, position, layers):
    for name in layers:
        h = DecoderLayer(cfg).apply({"params": params[name]}, h, segment,
                                    position)
    return RMSNorm(cfg.rms_norm_eps).apply(
        {"params": params["final_norm"]}, h)


def _close(a, b, rtol, atol, what=""):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                   err_msg=what + jax.tree_util.keystr(path))


def test_one_step_without_the_entropy_is_a_plain_stack():
    """T = 1 and beta = 0: the exit distribution is all on the one step,
    the gate is read by nothing, and the loss is the plain stack's mean
    next-token cross-entropy."""
    spec = dict(SMALL, total_ut_steps=1, entropy_beta=0.0)
    task = _task(spec)
    cfg = task.model.cfg
    params = task.init_params(jax.random.PRNGKey(0))
    x, y = _row()

    def plain(p):
        segment, position = packed_positions(x, 0)
        h = _stack(cfg, p, p["embed"][x], segment, position,
                   [f"layer_{i}" for i in range(cfg.num_hidden_layers)])
        return _mean_ce(h @ p["head"], y)[2]

    l_plain, g_plain = jax.jit(jax.value_and_grad(plain))(params)
    l_loop, g_loop = jax.jit(jax.value_and_grad(task.loss_fn))(params,
                                                               x, y)
    # one program against another of the same operations: only the order
    # XLA fuses them in differs
    np.testing.assert_allclose(l_loop, l_plain, rtol=1e-6)
    gate = g_loop.pop("early_exit_gate")
    assert all(float(jnp.abs(g).max()) == 0 for g in jax.tree.leaves(gate))
    g_plain.pop("early_exit_gate")
    _close(g_loop, g_plain, rtol=1e-5, atol=1e-8)


def test_the_steps_equal_an_unrolled_stack_of_tied_copies():
    """T steps of L layers against T x L layers written out, copy t x L + i
    holding layer i's weights, with the objective written out: the loss
    and each exit agree, and each layer's gradient is the sum of its
    copies'."""
    task = TASK
    cfg = task.model.cfg
    steps, depth = cfg.total_ut_steps, cfg.num_hidden_layers
    params = task.init_params(jax.random.PRNGKey(1))
    x, y = _row()
    copies = {f"copy_{t * depth + i}": params[f"layer_{i}"]
              for t in range(steps) for i in range(depth)}
    rest = {k: v for k, v in params.items() if not k.startswith("layer_")}

    def unrolled(copies, rest):
        p = dict(rest, **copies)
        segment, position = packed_positions(x, 0)
        h = p["embed"][x]
        ces, lams = [], []
        for t in range(steps):
            h = _stack(cfg, p, h, segment, position,
                       [f"copy_{t * depth + i}" for i in range(depth)])
            ce, valid, _ = _mean_ce(h @ p["head"], y)
            ces.append(ce)
            g = p["early_exit_gate"]
            lams.append(jax.nn.sigmoid(jnp.dot(
                h, g["kernel"], precision="highest")[..., 0] + g["bias"][0]))
        stay, weights = 1.0, []
        for lam in lams[:-1]:
            weights.append(lam * stay)
            stay = stay * (1.0 - lam)
        w = jnp.stack(weights + [stay])
        per_position = (w * jnp.stack(ces)).sum(0) + cfg.entropy_beta * (
            w * jnp.log(w)).sum(0)
        return (per_position * valid).sum() / valid.sum()

    l_unrolled, (g_copies, g_rest) = jax.jit(jax.value_and_grad(
        unrolled, argnums=(0, 1)))(copies, rest)
    l_loop, g_loop = _loss_and_grad()(params, x, y)
    np.testing.assert_allclose(l_loop, l_unrolled, rtol=1e-6)
    for i in range(depth):
        summed = jax.tree.map(lambda *g: sum(g), *[
            g_copies[f"copy_{t * depth + i}"] for t in range(steps)])
        # float32 sums of three copies in another order: a gradient
        # entry near zero keeps an absolute gap of a few 1e-8
        _close(g_loop[f"layer_{i}"], summed, rtol=1e-4, atol=1e-7,
               what=f"layer_{i}")
    _close({k: g_loop[k] for k in g_rest}, g_rest, rtol=1e-4, atol=1e-7)
    # the parameters hold the stack once
    assert sorted(k for k in params if k.startswith("layer_")) == \
        ["layer_0", "layer_1"]


def _parents_sequence_loss(task, params, x, y):
    """``Task.sequence_loss`` as it was before the per-position weighting."""
    params = task.cast_to_compute(params)
    planes, sown = task.sequence_planes(params, x, stats=True)
    weights = getattr(task.model, "loss_weights", (1.0,) * len(planes))
    loss = 0.0
    for depth, (logits, w) in enumerate(zip(planes, weights)):
        t = task.plane_targets(y, depth)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.maximum(t, 0))
        valid = (t >= 0).astype(jnp.float32)
        loss = loss + w * (ce * valid).sum() / jnp.maximum(
            valid.sum(), 1.0)
    return jnp.clip(loss, 0.0, task.spec.loss_clamp), sown


@pytest.mark.parametrize("spec", [
    dict(type="mla_moe_lm", vocab_size=256, hidden_size=64,
         num_hidden_layers=1, first_k_dense_replace=0, intermediate_size=128,
         moe_intermediate_size=32, n_routed_experts=8, first_expert=0,
         experts_held=4, num_experts_per_tok=2, num_attention_heads=4,
         q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
         qk_rope_head_dim=8, v_head_dim=16, num_nextn_predict_layers=1,
         attn_block=16),
    dict(type="gqa_moe_lm", vocab_size=256, hidden_size=64,
         num_hidden_layers=1, layer_types=["sliding_attention"],
         sliding_window=8, num_attention_heads=4, num_key_value_heads=2,
         head_dim=16, num_experts=8, first_expert=0, experts_held=4,
         num_experts_per_tok=2, moe_intermediate_size=32, attn_block=16),
], ids=["mla_moe_lm_mtp", "gqa_moe_lm"])
def test_the_other_language_models_loss_and_gradients_are_the_parents(spec):
    """The loss and its gradient trace to the parent's program (equal
    jaxprs compile to equal programs, so the numbers are the parent's to
    the bit), under bf16 compute as the cells run them."""
    task = TaskSpec(model=spec, num_classes=256, input_shape=(32,),
                    compute_dtype="bfloat16").build()
    params = jax.eval_shape(task.init_params, jax.random.PRNGKey(2))
    x, y = _row()

    def program(loss):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p: loss(p, x, y), has_aux=True))(params))

    assert program(task.sequence_loss) == program(
        lambda p, x, y: _parents_sequence_loss(task, p, x, y))


def test_every_exit_operation_is_under_blades_exit_and_the_mlps_under_mlp():
    """The gradient step's compiled program: every instruction that makes
    or reads a (rows, S, vocab) array (the exits' logits, their
    cross-entropy and its backward) wears ``blades/exit``, every one of
    the MLP's (rows, S, 96) activations ``blades/mlp``, and the attention's
    scores ``blades/attn_kernel``."""
    text = _loss_and_grad().as_text()
    _, table = device_phases.phases_of_text(text)
    shape = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
    seen = {"exit": 0, "mlp": 0}
    for line in text.splitlines():
        m = shape.match(line)
        if not m or m.group(1) not in table or m.group(3) == "parameter":
            continue       # a fused computation's parameters wear nothing
        dims = m.group(2)
        if dims in ("2,32,256", "64,256"):
            assert table[m.group(1)] == "exit", line
            seen["exit"] += 1
        elif dims in ("2,32,96", "64,96"):
            assert table[m.group(1)] == "mlp", line
            seen["mlp"] += 1
    assert seen["exit"] > 3 and seen["mlp"] > 3, seen
    assert {"exit", "mlp", "attn", "attn_kernel", "embed"} <= set(
        table.values())


def test_the_counters_the_answer_plane_and_a_dict_spec():
    task = TASK
    assert isinstance(task.model, LoopedLM)
    assert ModelCatalog.get_model(dict(SMALL)).cfg == task.model.cfg
    params = task.init_params(jax.random.PRNGKey(0))
    x, y = _row()
    _, stats = jax.eval_shape(task.sequence_loss, params, x, y)
    assert set(stats) == {"attn_fused", "attn_scores", "exits",
                          "exit_last_mass", "exit_positions"}
    # (the weighting is the loss's); one program for the planes, what the
    # model sowed and evaluate
    (planes, sown), ev = jax.jit(lambda p: (
        task.sequence_planes(p, x, stats=True),
        task.evaluate(p, x, y, jnp.ones((2,), bool))))(params)
    lanes = jax.tree.map(lambda a: jnp.stack([a, a]),
                         {k: sown[k] for k in stats})
    got = task.round_counters(lanes)
    assert int(got["loop_steps"]) == 3
    assert int(got["attn_fused_calls"]) == 0       # 2 x 3 passes, XLA's
    # 2 lanes x 2 rows x 4 heads x 6 passes of XLA's query blocks
    assert float(got["attn_scores_computed"]) == 2 * 2 * 4 * 6 * \
        attention_scores_computed(32, SMALL["attn_block"])
    np.testing.assert_allclose(
        float(got["exit_last_step_mass"]),
        float(sown["plane_weights"][0].mean()), rtol=1e-6)
    # evaluate scores the model's answer: the last step's exit
    valid = (y >= 0).astype(jnp.float32)
    ce, _, _ = _mean_ce(planes[0], y)
    np.testing.assert_allclose(ev["ce_sum"], (ce * valid).sum(), rtol=1e-5)
    assert float(ev["count"]) == float(valid.sum())
    with pytest.raises(KeyError):
        ModelCatalog.get_model(dict(SMALL, n_routed_experts=8))
    with pytest.raises(ValueError):
        LoopedConfig.from_dict({"num_key_value_heads": 4})
    # under bf16 compute the gate stays float32
    bf = TaskSpec(model=SMALL, num_classes=256, input_shape=(32,),
                  compute_dtype="bfloat16").build()
    cast = bf.cast_to_compute(params)
    assert cast["early_exit_gate"]["kernel"].dtype == jnp.float32
    assert cast["layer_0"]["mlp"]["up"]["kernel"].dtype == jnp.bfloat16
    assert isinstance(task.model, nn.Module)

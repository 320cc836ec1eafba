"""The fused attention kernel (ops/attention.py: the JAX package's splash
attention behind ``packed_causal_attention``) in Pallas's interpreter
against the XLA query blocks, the rule that picks it, and the counters
the two language models stamp for it.  float32, sizes a CPU holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.core.task import TaskSpec
from blades_tpu.data.datasets import build_packed_tokens
from blades_tpu.models import layers
from blades_tpu.ops import attention

# (S, query heads, key heads, key width, value width, window, tiles,
# document starts): rows of 384 in tiles of 128 so that a window empties
# a whole tile; documents start inside a tile unless the case says so.
INSIDE = (37, 130, 300)
T128 = (128,) * 6
CASES = {
    "equal_heads": (384, 2, 2, 128, 128, None, T128, INSIDE),
    "grouped_heads": (384, 4, 2, 128, 128, None, T128, INSIDE),
    "grouped_heads_window": (384, 4, 2, 128, 128, 100, T128, INSIDE),
    "window_of_one_tile": (384, 2, 1, 128, 128, 128, T128, INSIDE),
    "keys_192_values_128": (384, 2, 2, 192, 128, None, T128, INSIDE),
    "keys_192_window": (384, 2, 2, 192, 128, 100, T128, INSIDE),
    "documents_at_tile_edges": (384, 2, 1, 128, 128, None, T128,
                                (128, 256)),
    "one_document": (384, 2, 1, 128, 128, 150, T128, ()),
    "unequal_tiles": (512, 2, 1, 128, 128, 100,
                      (256, 128, 128, 128, 256, 128), INSIDE),
    "the_modules_tiles": (1024, 2, 1, 128, 128, 300, attention.BLOCKS,
                          (100, 700)),
    "shorter_than_a_tile": (256, 2, 2, 128, 128, None, attention.BLOCKS,
                            (90,)),
}
# the dq kernel of its own (``fused_bwd=False``), where it differs most
SPLIT = ("grouped_heads_window", "keys_192_values_128", "the_modules_tiles")


def _operands(s, heads, kv_heads, dk, dv, docs, lanes=2, rows=2):
    key = jax.random.split(jax.random.PRNGKey(s + heads), 4)
    q = jax.random.normal(key[0], (lanes, rows, s, heads, dk))
    k = jax.random.normal(key[1], (lanes, rows, s, kv_heads, dk))
    v = jax.random.normal(key[2], (lanes, rows, s, kv_heads, dv))
    ct = jax.random.normal(key[3], (lanes, rows, s, heads, dv))
    start = np.zeros((lanes, rows, s), np.int32)
    start[..., list(docs)] = 1
    start[1, 1, 5] = 1              # one row's documents are its own
    return q, k, v, ct, jnp.cumsum(jnp.asarray(start), axis=-1)


@pytest.mark.parametrize("case", sorted(CASES) + [c + "/split"
                                                  for c in SPLIT])
def test_the_kernel_equals_the_xla_blocks_forward_and_backward(case):
    """Under the models' two ``vmap``s (lanes, then the rows inside the
    call): the output and the three cotangents, to float32 rounding."""
    case, _, split_bwd = case.partition("/")
    s, heads, kv_heads, dk, dv, window, tiles, docs = CASES[case]
    q, k, v, ct, seg = _operands(s, heads, kv_heads, dk, dv, docs)
    scale = dk ** -0.5

    def blocks(q, k, v, seg):
        return layers.packed_causal_attention(q, k, v, seg, scale, 128,
                                              window, impl="jnp")

    def kernel(q, k, v, seg):
        return attention.fused_causal_attention(
            q, k, v, seg, scale, window, blocks=tiles,
            fused_bwd=not split_bwd, interpret=True)

    def both(form):
        out, vjp = jax.vjp(
            lambda q, k, v: jax.vmap(form)(q, k, v, seg), q, k, v)
        return (out,) + vjp(ct)

    for got, want in zip(jax.jit(lambda: both(kernel))(),
                         jax.jit(lambda: both(blocks))()):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_default_call_is_the_xla_blocks_on_the_cpu_to_the_bit():
    """``impl=None`` here is ``"jnp"``: the call the models make traces
    what it traced."""
    q, k, v, _, seg = _operands(128, 4, 2, 128, 128, (40,))
    args = (q[0], k[0], v[0], seg[0], 0.25, 32, 48)
    np.testing.assert_array_equal(
        layers.packed_causal_attention(*args),
        layers.packed_causal_attention(*args, impl="jnp"))
    assert attention.default_impl(128, 128, 128) == "jnp"


# -- the rule -------------------------------------------------------------

RULE = {
    "mellum2s_rows": ((8192, 128, 128), True),
    "joyais_rows": ((4096, 192, 128), True),
    "a_sequence_of_one_tile": ((1024, 128, 128), True),
    "shorter_than_a_tile": ((256, 128, 128), True),
    "whole_forward_tiles_but_no_whole_backward_tile": (
        (1536, 128, 128), False),
    "no_whole_tiles": ((8192 + 256, 128, 128), False),
    "no_whole_lane_tile": ((64, 128, 128), False),
    "narrow_keys": ((8192, 64, 128), False),
    "narrow_values": ((8192, 128, 64), False),
    "keys_of_no_whole_half_tile": ((8192, 160 + 8, 128), False),
    "the_cpu_tests_model": ((32, 16, 16), False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_reads_the_backend_and_the_shapes(case, monkeypatch):
    shape, on_tpu = RULE[case]
    assert not attention.kernel_applicable(*shape)          # the CPU
    assert attention.default_impl(*shape) == "jnp"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.kernel_applicable(*shape) == on_tpu
    assert attention.default_impl(*shape) == ("kernel" if on_tpu else "jnp")


def test_a_sequence_the_tiles_do_not_divide_is_refused_by_name():
    q, k, v, _, seg = _operands(192, 2, 2, 128, 128, ())
    with pytest.raises(ValueError, match="whole number"):
        attention.fused_causal_attention(q[0], k[0], v[0], seg[0], 1.0,
                                         interpret=True)


# -- what the kernel scores -----------------------------------------------


def _live_tiles(s, window, bq, bkv):
    """Tiles holding at least one (query, key) pair the mask allows."""
    live = 0
    for q0 in range(0, s, bq):
        for k0 in range(0, s, bkv):
            first_key = max(k0, 0 if window is None else q0 - window + 1)
            live += first_key <= min(k0 + bkv - 1, q0 + bq - 1) and (
                window is None or k0 + bkv - 1 >= q0 - window + 1)
    return live


@pytest.mark.parametrize("s,window", [(8192, None), (8192, 1024),
                                      (4096, None), (1024, 300),
                                      (2048, 512), (256, None)])
def test_scores_computed_is_the_kernels_own_block_list(s, window):
    bq, bkv = (min(b, s) for b in attention.BLOCKS[:2])
    got = attention.scores_computed(s, window)
    assert got == _live_tiles(s, window, bq, bkv) * bq * bkv
    required = sum(min(i + 1, window or s) for i in range(s))
    # every allowed pair lies in a live tile; beside the XLA blocks of 512
    # (whose first key is rounded to 128, not to a tile) the kernel scores
    # no more where the window is whole tiles, as in both cells
    assert required <= got
    if not (window or 0) % 512:
        assert got <= layers.attention_scores_computed(s, 512, window)


def test_narrower_tiles_score_fewer_positions():
    wide = attention.scores_computed(8192, 1024, (512,) * 6)
    narrow = attention.scores_computed(8192, 1024, (128,) * 6)
    assert sum(min(i + 1, 1024) for i in range(8192)) < narrow < wide


# -- the models' counters --------------------------------------------------

GQA = dict(
    type="gqa_moe_lm", vocab_size=256, hidden_size=64, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=40, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, first_expert=0, experts_held=4,
    num_experts_per_tok=2, moe_intermediate_size=32, attn_block=32)
MLA = dict(
    type="mla_moe_lm", vocab_size=256, hidden_size=64, num_hidden_layers=3,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
    first_expert=0, experts_held=4, num_experts_per_tok=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_nextn_predict_layers=1, attn_block=32)


def _a_round(spec, lanes=2, s=128):
    """``(updates, losses, counters)`` of one local round of ``lanes``
    clients, one row of ``s`` tokens each."""
    task = TaskSpec(model=spec, num_classes=256, input_shape=(s,),
                    lr=0.1).build()
    params = task.init_params(jax.random.PRNGKey(0))
    ds = build_packed_tokens(num_clients=lanes, seed=3, seq_len=s,
                             vocab_size=256, train_rows=2, test_rows=1,
                             doc_median=40)
    bx = jnp.asarray(ds.train.x[:, :1].reshape(lanes, 1, 1, s))
    by = jnp.asarray(ds.train.y[:, :1].reshape(lanes, 1, 1, s))
    opt = jax.tree.map(lambda a: jnp.broadcast_to(a, (lanes,) + a.shape),
                       task.init_client_opt_state(params))
    upd, _, loss, stats = jax.jit(task.local_round_batched)(
        params, opt, bx, by, jax.random.split(jax.random.PRNGKey(5), lanes),
        jnp.zeros((lanes,), bool))
    return upd, loss, jax.device_get(jax.jit(task.round_counters)(stats))


@pytest.mark.parametrize("name,spec,layers_", [("gqa", GQA, 4),
                                               ("mla", MLA, 4)])
def test_a_round_under_the_kernel_equals_one_under_the_blocks_and_counts(
        name, spec, layers_, monkeypatch):
    """The whole local round, each model: the XLA blocks (the CPU's path,
    ``attn_fused_calls`` 0) against the kernel in the interpreter
    (``attn_fused_calls`` = layers x lanes, MTP's layer among them), the
    updates and losses to float32 rounding; ``attn_scores_computed`` from
    the path that ran."""
    from blades_tpu.obs.schema import validate_record

    upd, loss, counters = _a_round(spec)
    assert int(counters["attn_fused_calls"]) == 0
    monkeypatch.setattr(attention, "default_impl",
                        lambda s, dk, dv: "interpret")
    upd_k, loss_k, counters_k = _a_round(spec)
    assert int(counters_k["attn_fused_calls"]) == layers_ * 2
    np.testing.assert_allclose(loss_k, loss, rtol=1e-5)
    np.testing.assert_allclose(upd_k, upd, rtol=2e-3, atol=2e-6)
    validate_record({"experiment": "e", "trial": "t", "train_loss": 1.0,
                     "training_iteration": 1,
                     **{k: v.item() for k, v in counters_k.items()}})
    if name == "gqa":
        per_head = {impl: sum(fn(128, *a, w) for w in (40, 40, 40, None))
                    for impl, fn, a in (
                        ("jnp", layers.attention_scores_computed, (32,)),
                        ("kernel", attention.scores_computed, ()))}
        assert float(counters["attn_scores_computed"]) == \
            2 * 4 * per_head["jnp"]
        assert float(counters_k["attn_scores_computed"]) == \
            2 * 4 * per_head["kernel"]


def test_the_tool_times_both_forms_and_compares_them(tmp_path, monkeypatch):
    """``tools/chip_kernels.py --attention`` at sizes the interpreter
    holds: every tiling agrees with the XLA blocks and the record carries
    both forms' times and what each scores."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import chip_kernels

    monkeypatch.chdir(tmp_path)
    rc = chip_kernels.attention_forms(
        ["128", "128/split", "128/pad"], impl="interpret", times=1,
        shapes={"window": (256, 4, 2, 128, 128, 100),
                "wide_keys": (256, 2, 2, 192, 128, None)})
    assert rc == 0
    with open(tmp_path / "chiprun_out" / "chip_attention.json") as fh:
        window, wide = json.load(fh)
    assert window["kernel_128"]["ok"] and wide["kernel_128/pad"]["ok"]
    assert "kernel_128/pad" not in window       # nothing to pad at 128
    assert window["kernel_128"]["scored_over_required"] \
        < window["xla_blocks"]["scored_over_required"]
    assert window["xla_blocks"]["fwd_bwd_ms"] > 0

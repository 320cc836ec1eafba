"""The grouped-query / window / routed-experts model (models/gqa_moe.py),
its attention (models/layers.py) and the grouped product
(ops/grouped.py) at a size a CPU holds: hidden 64, 4 query / 2 key heads of
16, window 8, layers window, window, window, full, 8 experts of 32 with
top-2, vocabulary 256, rows of 32 tokens.  (The comparison with the plain
reference lives in tests/perfbench/test_pb_gqa_moe_lm.py.)"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from blades_tpu.core.task import TaskSpec
from blades_tpu.data.datasets import build_packed_tokens, pack_documents
from blades_tpu.models import gqa_moe, layers, mla_moe
from blades_tpu.models.catalog import ModelCatalog
from blades_tpu.models.gqa_moe import (
    GqaMoeConfig,
    GroupedQueryAttention,
    RoutedExperts,
)
from blades_tpu.ops import grouped

SMALL = dict(
    type="gqa_moe_lm", vocab_size=256, hidden_size=64, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, first_expert=0, experts_held=4,
    num_experts_per_tok=2, moe_intermediate_size=32, attn_block=8)


def _task(**kw):
    return TaskSpec(model=dict(SMALL, **kw), num_classes=256,
                    input_shape=(32,), lr=0.1).build()


def _row():
    """One client's two packed rows of 32 tokens and their targets."""
    ds = build_packed_tokens(num_clients=1, seed=1, seq_len=32,
                             vocab_size=256, train_rows=2, test_rows=1,
                             doc_median=10)
    return jnp.asarray(ds.train.x[0]), jnp.asarray(ds.train.y[0])


# -- the grouped product ------------------------------------------------------

LOADS = {"even": [64, 64, 64, 64], "one_expert": [0, 0, 256, 0],
         "an_expert_without_a_pair": [100, 0, 120, 30],
         "no_pair_at_all": [0, 0, 0, 0]}


def _dense_form(lhs, rhs, sizes):
    """Every group's product on every row, kept where the row is the
    group's."""
    ends = np.cumsum(sizes)
    row = jnp.arange(lhs.shape[0])
    out = 0.0
    for g in range(len(sizes)):
        mine = (row >= ends[g] - sizes[g]) & (row < ends[g])
        out = out + jnp.where(mine[:, None], lhs @ rhs[g], 0)
    return out


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_grouped_product_equals_the_dense_form(load, impl):
    sizes = LOADS[load]
    rows, k, n = 256, 64, 32
    lhs = jax.random.normal(jax.random.PRNGKey(0), (rows, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n))
    gs = jnp.asarray(sizes, jnp.int32)
    used = (jnp.arange(rows) < sum(sizes))[:, None]

    def mine(lhs, rhs):
        out = grouped.grouped_matmul(lhs, rhs, gs, tile=128, impl=impl)
        return jnp.where(used, out, 0)      # rows past the groups: unread

    want, vjp_want = jax.vjp(lambda a, b: _dense_form(a, b, sizes), lhs, rhs)
    got, vjp_got = jax.vjp(mine, lhs, rhs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    ct = jax.random.normal(jax.random.PRNGKey(2), (rows, n))
    for a, b in zip(vjp_got(ct), vjp_want(ct)):
        a = jnp.where(used, a, 0) if a.shape == lhs.shape else a
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # the rows the kernel works on: every tile a group touches
    tiles = {"even": 4, "one_expert": 2, "an_expert_without_a_pair": 4,
             "no_pair_at_all": 0}[load]
    assert int(grouped.rows_computed(gs, 128)) == tiles * 128


@pytest.mark.parametrize("impl", ["jnp", "interpret"])
def test_routed_ffn_equals_the_dense_form_under_the_routing_weights(impl):
    tokens, top_k, h, f, held = 64, 2, 64, 32, 4
    key = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(key[0], (tokens, h))
    expert = jax.random.randint(key[1], (tokens, top_k), -2, held + 2)
    here = (expert >= 0) & (expert < held)
    weight = jax.random.uniform(key[2], (tokens, top_k))
    gate, up = (0.1 * jax.random.normal(k, (held, h, f)) for k in key[3:5])
    down = 0.1 * jax.random.normal(key[5], (held, f, h))

    def dense(x, weight, gate, up, down):
        w = (weight[..., None] * (expert[..., None] == jnp.arange(held))
             ).sum(1)                                        # (T, held)
        a = jax.nn.silu(jnp.einsum("th,ehf->tef", x, gate)) \
            * jnp.einsum("th,ehf->tef", x, up)
        return jnp.einsum("tef,efh->th", a * w[..., None], down)

    def routed(x, weight, gate, up, down):
        return grouped.routed_ffn(x, expert, here, weight, gate, up, down,
                                  impl=impl)[0]

    args = (x, weight, gate, up, down)
    want, vjp_want = jax.vjp(dense, *args)
    got, vjp_got = jax.vjp(routed, *args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    ct = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    for a, b in zip(vjp_got(ct), vjp_want(ct)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    _, sizes, rows, moved = grouped.routed_ffn(
        *args[:1], expert, here, *args[1:], impl=impl)
    assert sizes.tolist() == [int(((expert == e) & here).sum())
                              for e in range(held)]
    assert int(rows) >= int(moved) >= int(sizes.sum())


def test_shares_add_up_to_the_uncut_layer():
    """8 experts held 4 + 4: the two shares' outputs sum to the uncut
    layer's (no shared expert: nothing is counted twice)."""
    kw = {k: v for k, v in SMALL.items() if k != "type"}
    whole = RoutedExperts(GqaMoeConfig.from_dict(dict(kw, experts_held=8)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    full = whole.apply({"params": params}, x)
    parts = []
    for first in (0, 4):
        share = RoutedExperts(GqaMoeConfig.from_dict(
            dict(kw, first_expert=first, experts_held=4)))
        p = dict(params, **{k: params[k][first:first + 4]
                            for k in ("experts_gate", "experts_up",
                                      "experts_down")})
        out, state = share.apply({"params": p}, x, mutable=["stats"])
        parts.append(out)
        assert state["stats"]["expert_tokens"][0].shape == (4,)
    np.testing.assert_allclose(parts[0] + parts[1], full, rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.abs(parts[0]).max()) > 0 < float(
        jnp.abs(parts[1]).max())


# -- attention ----------------------------------------------------------------


def _full_mask_attention(q, k, v, segment, scale, window=None):
    """Every query against every key under the explicit mask."""
    s = q.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    ok = (segment[:, :, None] == segment[:, None, :]) & (j <= i)
    if window is not None:
        ok = ok & (i - j < window)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(ok[:, None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


_attn = jax.jit(layers.packed_causal_attention, static_argnums=(4, 5, 6))
_masked = jax.jit(_full_mask_attention, static_argnums=(4, 5))


def _qkv(s=64, heads=4, kv_heads=2, dim=16, docs=(0, 20, 45)):
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (2, s, heads, dim))
    k = jax.random.normal(key[1], (2, s, kv_heads, dim))
    v = jax.random.normal(key[2], (2, s, kv_heads, dim))
    start = np.zeros((2, s), np.int32)
    start[:, list(docs)] = 1
    return q, k, v, jnp.cumsum(jnp.asarray(start), axis=1)


@pytest.mark.parametrize("block", [8, 16, 64])
def test_a_window_layer_equals_full_attention_under_the_window_mask(block):
    q, k, v, seg = _qkv(kv_heads=4)
    for window in (8, 24, None):
        got = _attn(q, k, v, seg, 0.25, block,
                                             window)
        want = _masked(q, k, v, seg, 0.25, window)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the blocks skip keys wholly outside the window ...
    assert layers.attention_scores_computed(64, 8, 8) == 8 * 8 + 7 * 8 * 16
    assert layers.attention_scores_computed(64, 8, None) == 8 * 8 * 36
    assert layers.attention_key_start(4096, 512, 1024) == 3072
    # ... and gradients flow through the rematerialised blocks alike
    g = jax.grad(lambda q: _attn(
        q, k, v, seg, 0.25, block, 8).sum())(q)
    w = jax.grad(lambda q: _masked(
        q, k, v, seg, 0.25, 8).sum())(q)
    np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_documents_shorter_than_the_window_read_as_in_a_full_layer():
    q, k, v, seg = _qkv(docs=(0, 10, 20, 30, 40, 50, 60))   # all <= 10 long
    windowed = _attn(q, k, v, seg, 0.25, 16, 12)
    full = _attn(q, k, v, seg, 0.25, 16, None)
    np.testing.assert_allclose(windowed, full, rtol=1e-6, atol=1e-6)


def test_grouped_heads_equal_attention_with_k_and_v_repeated():
    q, k, v, seg = _qkv(heads=8, kv_heads=2)
    got = _attn(q, k, v, seg, 0.25, 16, 24)
    rep = _attn(
        q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2), seg, 0.25,
        16, 24)
    np.testing.assert_allclose(got, rep, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError):
        layers.packed_causal_attention(q[:, :, :3], k, v, seg, 0.25, 16)


def test_yarn_frequencies_are_the_formulas_at_head_dim_128():
    """theta 5e5, factor 16 over 8192: cd(32) = 18.08, cd(1) = 34.98, so
    pairs 0..18 turn as published, pairs 35..63 at a sixteenth, and pair
    ``i`` between is blended by ``(i - 18) / 17``."""
    cfg = GqaMoeConfig()
    rope = cfg.rotary("full_attention")
    inv = rope["inv_freq"]
    extra = 500000.0 ** (-np.arange(64) / 64.0)
    assert inv.shape == (64,) and inv.dtype == np.float32
    np.testing.assert_allclose(inv[:19], extra[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], extra[35:] / 16, rtol=1e-6)
    for i in (19, 26, 34):
        ramp = (i - 18) / 17
        np.testing.assert_allclose(
            inv[i], extra[i] / 16 * ramp + extra[i] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(
        inv[[0, 18, 19, 34, 35, 63]],
        [1.0, 2.4955409e-02, 1.9208016e-02, 1.1040869e-04, 4.7781061e-05,
         1.5344629e-07], rtol=1e-5)
    assert rope["attention_factor"] == 1.2772588722239782
    np.testing.assert_allclose(rope["attention_factor"],
                               0.1 * np.log(16) + 1, rtol=1e-12)
    plain = cfg.rotary("sliding_attention")
    assert plain["inv_freq"] is None and plain["attention_factor"] == 1.0
    # a turned pair keeps its length times the factor
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 128))
    pos = jnp.arange(4)[None]
    y = layers.rotary_interleaved(x, pos, rope["theta"], inv,
                                  rope["attention_factor"])
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1),
        rope["attention_factor"] * jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(
        layers.rotary_interleaved(x, pos, 5e5, extra.astype(np.float32)),
        layers.rotary_interleaved(x, pos, 5e5), rtol=1e-5, atol=1e-6)


# -- the model on the task ------------------------------------------------------


def test_a_packed_row_gives_each_document_what_it_gives_alone():
    task = _task()
    params = task.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    a, b = (rng.integers(1, 256, 15).astype(np.int32) for _ in range(2))

    def summed(docs, seq_len):
        x, y = pack_documents(docs, seq_len)
        assert x.shape[0] == 1

        def total(p):
            (logits,) = task.sequence_planes(task.cast_to_compute(p),
                                             jnp.asarray(x))
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.maximum(jnp.asarray(y), 0))
            return (ce * (y >= 0)).sum()

        return jax.jit(jax.value_and_grad(total))(params)

    both, g_both = summed([a, b], 32)
    la, ga = summed([a], 16)
    lb, gb = summed([b], 16)
    np.testing.assert_allclose(both, la + lb, rtol=1e-5)
    for gp, g1, g2 in zip(*map(jax.tree.leaves, (g_both, ga, gb))):
        np.testing.assert_allclose(gp, g1 + g2, rtol=2e-4, atol=1e-6)


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
    assert stats["expert_tokens"].shape == (3, 4, 4)   # lanes, layers, held
    assert stats["attn_scores"].shape == (3, 4)
    single = jax.jit(task.local_round)
    for i in range(3):
        u1, _, l1, s1 = single(
            params, task.init_client_opt_state(params), bx[i], by[i],
            keys[i], mal[i])
        np.testing.assert_allclose(upd[i], u1, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(loss[i], l1, rtol=1e-6)
        np.testing.assert_array_equal(stats["expert_tokens"][i],
                                      s1["expert_tokens"])


def test_the_counters_are_valid_rows_and_count_what_they_say():
    from blades_tpu.obs.schema import validate_record

    task = _task()
    stats = {"expert_tokens": jnp.asarray(
        [[[6, 0, 2, 0]], [[0, 0, 8, 0]]], jnp.int32),
        "routed_pairs": jnp.full((2, 1), 64, jnp.int32),
        "expert_rows": jnp.asarray([[16], [8]], jnp.int32),
        "dispatch_rows": jnp.asarray([[16], [16]], jnp.int32),
        "attn_scores": jnp.asarray([[1000], [1000]], jnp.float32),
        "attn_fused": jnp.asarray([[1], [0]], jnp.int32)}
    got = jax.device_get(jax.jit(task.round_counters)(stats))
    assert int(got["attn_fused_calls"]) == 1
    assert int(got["expert_tokens_max"]) == 8
    assert float(got["routed_here_share"]) == 16 / 128
    assert int(got["zero_expert_blocks"]) == 5
    assert int(got["expert_pairs_here"]) == 16
    assert int(got["expert_rows_computed"]) == 24
    assert int(got["dispatch_rows_moved"]) == 32
    assert float(got["attn_scores_computed"]) == 2000.0
    row = {"experiment": "e", "trial": "t", "training_iteration": 1,
           "train_loss": 1.0}
    row.update({k: v.item() for k, v in got.items()})
    validate_record(row)
    with pytest.raises(Exception):
        validate_record(dict(row, expert_rows_computed=0.5))
    with pytest.raises(Exception):
        validate_record(dict(row, dispatch_rows_moved=0.5))
    # what the attention layers sow is their blocks' count, from shapes
    _, sown = jax.jit(lambda p, x: task.sequence_planes(p, x, stats=True))(
        task.init_params(jax.random.PRNGKey(0)),
        jnp.zeros((2, 32), jnp.int32))
    per_head = [layers.attention_scores_computed(32, 8, w)
                for w in (8, 8, 8, None)]
    assert sown["attn_scores"].tolist() == [2 * 4 * p for p in per_head]
    assert per_head[0] < per_head[3]


@pytest.mark.parametrize("kind,window", [("sliding_attention", 1024),
                                         ("full_attention", None)])
def test_two_rows_of_8192_count_their_scores_past_int32(kind, window):
    """2 rows x 32 heads x 35 651 584 positions of a full layer = 2.28e9:
    the count is a float32, as ``round_counters`` sums it and the schema
    reads it.  Shapes only: nothing but the sown constant is computed."""
    cfg = GqaMoeConfig.from_dict(dict(
        {k: v for k, v in SMALL.items() if k != "type"},
        num_attention_heads=32, num_key_value_heads=4, head_dim=8,
        sliding_window=1024, attn_block=512))
    x = jnp.zeros((2, 8192, 64))
    segment, position = layers.packed_positions(
        jnp.zeros((2, 8192), jnp.int32), 0)
    attn = GroupedQueryAttention(cfg, kind)
    params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), x, segment,
                            position)["params"]
    sown = jax.jit(lambda p: attn.apply(
        {"params": p}, x, segment, position,
        mutable=["stats"])[1]["stats"]["attn_scores"][0])(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params))
    want = 2 * 32 * layers.attention_scores_computed(8192, 512, window)
    assert (want > 2 ** 31) == (window is None)
    assert sown.dtype == jnp.float32
    np.testing.assert_allclose(sown, want, rtol=1e-7)


def _remat_layers(monkeypatch):
    """Every decoder layer of the model under ``nn.remat``: the backward
    pass then computes each layer's forward pass again."""
    monkeypatch.setattr(gqa_moe, "DecoderLayer",
                        nn.remat(gqa_moe.DecoderLayer))


@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_layers_without_remat_give_the_rematerialised_models_numbers(
        monkeypatch, compute_dtype):
    """Keeping the residuals changes what the backward pass reads, not what
    it computes: in float32 logits, loss and gradients are those of the
    model with each layer under ``nn.remat``, to the bit.  In bf16 the
    forward pass is the same to the bit, but the backward pass then reads
    the forward pass's own bf16 values where the remat read a
    recomputation that XLA fuses (and so rounds) differently; a leaf's
    gradient moves by a few bf16 roundings of its largest value."""
    task = TaskSpec(model=SMALL, num_classes=256, input_shape=(32,), lr=0.1,
                    compute_dtype=compute_dtype).build()
    params = task.init_params(jax.random.PRNGKey(0))
    x, y = _row()

    def run():
        # Fresh functions each time: a trace cached from the other model
        # would compare the model with itself.
        planes = jax.jit(lambda p: task.sequence_planes(
            task.cast_to_compute(p), x))(params)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: task.loss_fn(p, x, y)))(params)
        return planes, loss, grads

    mine = run()
    _remat_layers(monkeypatch)
    theirs = run()
    for a, b in zip(jax.tree.leaves(mine[:2]), jax.tree.leaves(theirs[:2])):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(mine[2]), jax.tree.leaves(theirs[2])):
        if compute_dtype is None:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=0.04 * float(jnp.abs(b).max()))


def _inner_jaxprs(eqn):
    for param in eqn.params.values():
        for sub in param if isinstance(param, (list, tuple)) else [param]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _primitives(jaxpr):
    return {name for eqn in jaxpr.eqns for name in
            {eqn.primitive.name}.union(*map(_primitives, _inner_jaxprs(eqn)))}


def _checkpoints(jaxpr, kind, under=0):
    """``(how many checkpoints enclose it, the primitives it encloses)``
    for every equation of primitive ``kind`` in ``jaxpr`` and in the
    jaxprs inside it."""
    found = []
    for eqn in jaxpr.eqns:
        here = eqn.primitive is kind
        if here:
            found.append((under, _primitives(eqn.params["jaxpr"])))
        for sub in _inner_jaxprs(eqn):
            found += _checkpoints(sub, kind, under + here)
    return found


def test_the_gradient_holds_no_checkpoint_around_a_layer(monkeypatch):
    """The gradient's checkpoints are the XLA attention path's query blocks,
    one a block a layer, none inside another and none around the routed
    layer; a layer-wide remat put back (here by hand) fails each count."""
    task = _task()
    params = task.init_params(jax.random.PRNGKey(0))
    x, y = _row()
    blocks = SMALL["num_hidden_layers"] * 32 // SMALL["attn_block"]
    kind = jax.make_jaxpr(jax.checkpoint(jnp.sin))(1.0).eqns[0].primitive

    def checkpoints():
        return _checkpoints(jax.make_jaxpr(jax.grad(
            lambda p: task.loss_fn(p, x, y)))(params).jaxpr, kind)

    mine = checkpoints()
    assert len(mine) == blocks
    assert all(under == 0 and "custom_vjp_call" not in inside
               and "dot_general" in inside for under, inside in mine)
    _remat_layers(monkeypatch)
    theirs = checkpoints()
    assert len(theirs) > blocks
    assert any(under for under, _ in theirs)
    assert any("custom_vjp_call" in inside for _, inside in theirs)


def test_the_router_stays_float32_and_a_dict_spec_resolves():
    task = TaskSpec(model=SMALL, num_classes=256, input_shape=(32,),
                    compute_dtype="bfloat16").build()
    cast = jax.eval_shape(lambda k: task.cast_to_compute(
        task.init_params(k)), jax.random.PRNGKey(0))
    moe = cast["layer_1"]["moe"]
    assert moe["router_kernel"].dtype == jnp.float32
    assert moe["experts_gate"].dtype == jnp.bfloat16
    assert set(moe) == {"router_kernel", "experts_gate", "experts_up",
                        "experts_down"}             # no bias, no shared
    model = ModelCatalog.get_model(SMALL, num_classes=256)
    assert model.cfg.layer_types[3] == "full_attention"
    assert model.cfg.vocab_size == 256
    with pytest.raises(KeyError):
        ModelCatalog.get_model(dict(SMALL, hiden_size=64))
    with pytest.raises(ValueError):
        ModelCatalog.get_model(dict(SMALL, first_expert=6))
    with pytest.raises(ValueError):
        ModelCatalog.get_model(dict(SMALL, layer_types=["full_attention"]))
    # the published defaults: one period of four, seven times over
    assert GqaMoeConfig().layer_types[:4] == (
        "sliding_attention",) * 3 + ("full_attention",)
    assert len(GqaMoeConfig().layer_types) == 28


# -- the attention it shares with the MLA model -------------------------------


def _parents_packed_causal_attention(q, k, v, segment, scale, block=512):
    """``packed_causal_attention`` as the parent commit (PR 32) wrote it:
    equal heads, no window."""
    s = q.shape[1]
    block = min(block, s)

    @jax.checkpoint
    def one_block(qi, kj, vj, seg_q, seg_k, q0):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                        preferred_element_type=jnp.float32) * scale
        qpos = q0 + jnp.arange(qi.shape[1])
        ok = (seg_q[:, :, None] == seg_k[:, None, :]) \
            & (jnp.arange(kj.shape[1])[None, :] <= qpos[:, None])
        sc = jnp.where(ok[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(vj.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vj)

    out = []
    for q0 in range(0, s, block):
        end = q0 + block
        out.append(one_block(q[:, q0:end], k[:, :end], v[:, :end],
                             segment[:, q0:end], segment[:, :end], q0))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def test_the_mla_models_logits_and_gradients_are_the_parents_bit_for_bit(
        monkeypatch):
    spec = dict(
        type="mla_moe_lm", vocab_size=256, hidden_size=64,
        num_hidden_layers=3, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, first_expert=0, experts_held=4,
        num_experts_per_tok=2, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_nextn_predict_layers=1, attn_block=8)
    task = TaskSpec(model=spec, num_classes=256, input_shape=(32,)).build()
    params = task.init_params(jax.random.PRNGKey(0))
    x, y = _row()

    def run():
        planes = jax.jit(task.sequence_planes)(params, x)
        return planes, jax.jit(jax.grad(task.loss_fn))(params, x, y)

    mine = run()
    monkeypatch.setattr(mla_moe, "packed_causal_attention",
                        _parents_packed_causal_attention)
    parents = run()
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(parents)):
        np.testing.assert_array_equal(a, b)

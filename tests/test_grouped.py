"""The routed layer's Pallas row movers (ops/grouped.py: the spread and the
collect) in Pallas's interpreter against XLA's gathers, the ``jnp`` form
they replace on the chip: 256 tokens of width 128 in bf16, top-8 over 8
held experts, under the loads of ``tools/chip_kernels.py::GROUPED_LOADS``
scaled from 8192 tokens to 256, and none held at all."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.ops import grouped

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.chip_kernels import GROUPED_LOADS  # noqa: E402

TOKENS, TOP_K, H, HELD = 256, 8, 128, 8
PAIRS = TOKENS * TOP_K
LOADS = dict({name: [s * TOKENS // 8192 for s in sizes]
              for name, sizes in GROUPED_LOADS.items()},
             no_pair_at_all=[0] * HELD)
bf16, f32 = jnp.bfloat16, jnp.float32


def _routing(sizes, seed=7):
    """``(expert, held)`` ``(T, K)``: held expert ``e`` selected by
    ``sizes[e]`` tokens drawn at random, in slot ``e`` (as the tool draws
    them); the other slots select experts held elsewhere."""
    rng = np.random.default_rng(seed)
    expert = np.full((TOKENS, TOP_K), -1, np.int32)
    for e, n in enumerate(sizes):
        expert[rng.choice(TOKENS, n, replace=False), e] = e
    return jnp.asarray(expert), jnp.asarray(expert >= 0)


def _setup(sizes, tile=256):
    expert, held = _routing(sizes)
    order, pos, gs = grouped.sort_pairs(expert.reshape(PAIRS),
                                        held.reshape(PAIRS), HELD)
    cfg = grouped._Tiles(tile, math.gcd(TOKENS, grouped._CHUNK),
                         math.gcd(TOKENS, grouped._TOKEN_TILE), True)
    mine = held[..., None] & (expert[..., None] == jnp.arange(HELD))
    route = grouped._route(mine, order, pos, gs, cfg)
    return expert, held, order, pos.reshape(TOKENS, TOP_K), gs, cfg, mine, \
        route


def _rows(seed, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, H), bf16)


def _close(got, want):
    """Equal up to the order of a float32 sum of three or more held pairs'
    rows before its one rounding to bf16: a bf16 step of the largest
    value."""
    want = np.asarray(want, np.float64)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0,
                               atol=2 ** -7 * np.abs(want).max())


def _combine_xla(ys, w, pos, held):
    """The ``jnp`` form's combine, as ``routed_ffn`` writes it."""
    got = jnp.where(held[..., None], ys[pos], 0)
    return (w.astype(bf16)[..., None] * got).sum(1, dtype=f32).astype(bf16)


@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_movers_give_the_xla_gathers_values(load):
    expert, held, order, pos, gs, cfg, mine, route = _setup(LOADS[load])
    n = int(gs.sum())
    tile = cfg.tile
    x, ys, a, b = _rows(1, TOKENS), _rows(2, PAIRS), _rows(3, PAIRS), \
        _rows(4, PAIRS)
    w = jax.random.uniform(jax.random.PRNGKey(5), (TOKENS, TOP_K))
    g = _rows(6, TOKENS)
    wg = jnp.where(mine, w.astype(bf16)[..., None].astype(f32), 0).sum(1).T
    # dispatch: the rows in use to the bit, the last tile's rest zero
    xs = grouped._spread(x, route, cfg)
    np.testing.assert_array_equal(xs[:n], x[order[:n] // TOP_K])
    assert not jnp.any(xs[n:-(-n // tile) * tile])
    # its cotangent: the two projections' cotangents summed as the jnp form
    # sums them (in bf16) and gathered back
    want = grouped._gather_bwd((pos, held), a + b)[0]
    _close(grouped._collect([a, b], route, cfg), want)
    # combine and its cotangents
    want, vjp = jax.vjp(lambda ys, w: _combine_xla(ys, w, pos, held), ys, w)
    got = grouped._combine(ys, wg, route, cfg)
    _close(got, want)
    # each product rounded to bf16 as the jnp form rounds it: a sum of two
    # is the same either way round
    two = held.sum(1) <= 2
    np.testing.assert_array_equal(got[two], want[two])
    g_ys, g_w = vjp(g)
    m_ys, m_wg = grouped._combine_bwd(cfg, (ys, wg, route), g)[:2]
    np.testing.assert_array_equal(m_ys[:n], g_ys[:n])
    assert not jnp.any(m_ys[n:-(-n // tile) * tile])
    # the weight's cotangent: the jnp form's products and their sum are
    # bf16 (a few percent of an element off); the movers' is the float32
    # dot, held against float64
    g64 = np.asarray(g, np.float64)
    ys64 = np.asarray(ys, np.float64)
    p = np.asarray(pos)
    exact = np.where(np.asarray(held),
                     np.einsum("th,tkh->tk", g64, ys64[p]), 0.0)
    m_w = np.where(np.asarray(held), np.asarray(m_wg)[
        np.clip(np.asarray(expert), 0, HELD - 1),
        np.arange(TOKENS)[:, None]], 0.0)
    scale = max(np.abs(exact).max(), 1.0)
    np.testing.assert_allclose(m_w, exact, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(g_w, np.float64), exact, rtol=0,
                               atol=2 ** -5 * scale)


def test_at_one_held_pair_a_token_the_combine_is_the_jnp_forms_to_the_bit():
    sizes = LOADS["even"]
    expert, held, order, pos, gs, cfg, mine, route = _setup(sizes)
    assert int(held.sum(1).max()) >= 1
    one = held.sum(1) <= 1
    ys = _rows(2, PAIRS)
    w = jax.random.uniform(jax.random.PRNGKey(5), (TOKENS, TOP_K))
    wg = jnp.where(mine, w.astype(bf16)[..., None].astype(f32), 0).sum(1).T
    want = _combine_xla(ys, w, pos, held)
    got = grouped._combine(ys, wg, route, cfg)
    assert int(one.sum()) > TOKENS // 2
    np.testing.assert_array_equal(got[one], want[one])


def test_a_row_past_the_held_pairs_is_never_read():
    """The buffers the grouped product writes hold unspecified rows past
    the held pairs: NaN there changes nothing the collect writes."""
    expert, held, order, pos, gs, cfg, mine, route = _setup(
        LOADS["an_expert_without_a_pair"])
    n = int(gs.sum())
    ys = _rows(2, PAIRS)
    poisoned = ys.at[n:].set(jnp.nan)
    for w in (None, jax.random.uniform(jax.random.PRNGKey(5), (HELD, TOKENS))):
        np.testing.assert_array_equal(
            grouped._collect([poisoned], route, cfg, w),
            grouped._collect([ys], route, cfg, w))


@pytest.mark.parametrize("tile", [128, 256])
def test_dispatch_rows_moved_is_the_row_tiles_in_use(tile, monkeypatch):
    """What the spread writes: every buffer tile up to the last held pair,
    once, whichever experts share it (``rows_computed`` of one group of all
    the held pairs); the grouped product computes a shared tile once an
    expert.  XLA's gathers write the whole buffer."""
    expert, held = _routing(LOADS["skewed"])
    x = _rows(1, TOKENS)
    w = jax.random.uniform(jax.random.PRNGKey(5), (TOKENS, TOP_K))
    gate, up = (0.1 * jax.random.normal(jax.random.PRNGKey(k), (HELD, H, 32))
                .astype(bf16) for k in (7, 8))
    down = (0.1 * jax.random.normal(jax.random.PRNGKey(9), (HELD, 32, H))
            ).astype(bf16)
    monkeypatch.setattr(grouped, "ROW_TILE", tile)
    for impl in ("interpret", "jnp"):
        _, gs, computed, moved = grouped.routed_ffn(
            x, expert, held, w, gate, up, down, impl=impl)
        n = int(gs.sum())
        if impl == "jnp":
            assert int(moved) == PAIRS
            continue
        assert int(moved) == int(grouped.rows_computed(
            gs.sum(keepdims=True), tile)) == -(-n // tile) * tile
        assert n <= int(moved) <= int(computed)


def test_the_tied_cotangents_pass_through_unchanged():
    """The layer input and the expert weights, tied so that their
    cotangents leave the layer's backward pass together: the identity
    forward and backward."""
    args = (_rows(1, TOKENS), _weights()[0], jnp.arange(6.0).reshape(2, 3))
    out, vjp = jax.vjp(grouped._cotangents_together, *args)
    for a, b in zip(out, args):
        np.testing.assert_array_equal(a, b)
    cts = (_rows(2, TOKENS), 2 * _weights()[1], jnp.ones((2, 3)))
    for a, b in zip(vjp(cts), cts):
        np.testing.assert_array_equal(a, b)


def _layer(expert, held, impl):
    def fn(x, w, gate, up, down):
        return grouped.routed_ffn(x, expert, held, w, gate, up, down,
                                  impl=impl)[0]
    return fn


def _weights(f=32):
    k = jax.random.split(jax.random.PRNGKey(11), 3)
    gate, up = (0.1 * jax.random.normal(kk, (HELD, H, f)).astype(bf16)
                for kk in k[:2])
    down = (0.1 * jax.random.normal(k[2], (HELD, f, H))).astype(bf16)
    return gate, up, down


def test_vmap_over_three_lanes_equals_three_single_calls():
    """Three lanes whose held pairs differ in number (the dynamic grid and
    the scalar tables batch as an explicit loop over the lanes): forward
    and gradients equal each lane's own call."""
    loads = [LOADS["even"], LOADS["skewed"], LOADS["no_pair_at_all"]]
    routes = [_routing(s, seed=i) for i, s in enumerate(loads)]
    expert = jnp.stack([r[0] for r in routes])
    held = jnp.stack([r[1] for r in routes])
    assert len({int(h.sum()) for h in held}) == 3
    x = jnp.stack([_rows(i, TOKENS) for i in range(3)])
    w = jax.random.uniform(jax.random.PRNGKey(5), (3, TOKENS, TOP_K))
    gate, up, down = _weights()

    def loss(x, w, gate, up, down, expert, held):
        y = _layer(expert, held, "interpret")(x, w, gate, up, down)
        return (y.astype(f32) ** 2).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    lanes = jax.jit(jax.vmap(lambda *a: (loss(*a), grad(*a)),
                             in_axes=(0, 0, None, None, None, 0, 0)))(
        x, w, gate, up, down, expert, held)
    for i in range(3):
        one = jax.jit(lambda *a: (loss(*a), grad(*a)))(
            x[i], w[i], gate, up, down, expert[i], held[i])
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda v: v[i], lanes))):
            np.testing.assert_array_equal(a, b)


def test_the_layer_in_bf16_equals_the_dense_form():
    """The routed layer in bf16 (the chip's type) with the movers, against
    every held expert on every token under the routing weights in float32:
    forward and every gradient within bf16's roundings."""
    expert, held = _routing(LOADS["skewed"])
    x = _rows(1, TOKENS)
    w = jax.random.uniform(jax.random.PRNGKey(5), (TOKENS, TOP_K))
    weights = _weights()

    def dense(x, w, gate, up, down):
        x, gate, up, down = (a.astype(f32) for a in (x, gate, up, down))
        wt = (jnp.where(held, w, 0)[..., None]
              * (expert[..., None] == jnp.arange(HELD))).sum(1)
        a = jax.nn.silu(jnp.einsum("th,ehf->tef", x, gate)) \
            * jnp.einsum("th,ehf->tef", x, up)
        return jnp.einsum("tef,efh->th", a * wt[..., None], down)

    args = (x, w) + weights
    want, vjp_want = jax.vjp(dense, *args)
    got, vjp_got = jax.vjp(_layer(expert, held, "interpret"), *args)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got.astype(f32), want, rtol=0,
                               atol=2 ** -6 * scale)
    ct = _rows(6, TOKENS)
    for a, b in zip(vjp_got(ct), vjp_want(ct.astype(f32))):
        a = np.asarray(a, np.float64)
        np.testing.assert_allclose(a, np.asarray(b, np.float64), rtol=0,
                                   atol=2 ** -5 * float(np.abs(b).max()))

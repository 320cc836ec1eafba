"""The grouped-query attention + routed-experts language-model family:
everything the benchmark knows of the model (what a family provides:
``families/cifar_resnet.py``).  Plain ``jax`` and ``numpy``: nothing of the
program under test, and nothing the program has made.

The model is the decoder Mellum2-12B-A2.5B-Instruct's ``config.json`` sets,
every equation from the configuration's keys:

- block: ``x += attn(rms(x))``, ``x += moe(rms(x))``, ``rms_norm_eps``, no
  biases (``attention_bias`` false).  Every layer is ``sparse``
  (``mlp_layer_types``): ``intermediate_size`` (7168) is read by no layer;
- attention: ``q = x W_q`` -> ``num_attention_heads`` x ``head_dim``; ``k = x
  W_k``, ``v = x W_v`` -> ``num_key_value_heads`` x ``head_dim``; query head
  ``h`` reads key head ``h // (heads // kv_heads)``, written here the plain
  way: k and v repeated to the query heads; rotary over ``head_dim`` on q and
  k; scores ``q . k / sqrt(head_dim)``, softmax in float32; ``o = (P v)
  W_o``.  Query ``i`` sees key ``j`` iff they are of one document, ``j <= i``
  and, in a ``sliding_attention`` layer (``layer_types``), ``i - j <
  sliding_window``.  Computed a block of queries at a time against EVERY key
  under that whole mask, each block rematerialised in the backward pass, so
  that no (heads, S, S) array is kept; one compiled body for all blocks;
- rotary by layer type (``rope_parameters``).  ``default``: ``inv_freq_i =
  theta ** (-2i / head_dim)``.  ``yarn``, static (applied at every length):
  ``extra_i = theta ** (-2i / d)``, ``inter_i = extra_i / factor``; ``cd(r) =
  d ln(original_max_position_embeddings / (2 pi r)) / (2 ln theta)``; ``low =
  floor(cd(beta_fast))``, ``high = ceil(cd(beta_slow))``, clipped to ``[0, d -
  1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i =
  inter_i ramp_i + extra_i (1 - ramp_i)``; cos and sin both times
  ``attention_factor``.  Pairs are interleaved ``(x[2i], x[2i+1])`` (the
  published code turns halves: a fixed permutation of q's and k's features
  alike; ``assumed``);
- router: ``p = softmax(x W_g)`` over all ``router_outputs`` experts in
  float32, top-``num_experts_per_tok``, weights ``p_i / sum_topk p``
  (``norm_topk_prob``); no bias, no scaling, no shared expert, no auxiliary
  loss;
- expert share: the sum over the selected experts HELD HERE (``first_expert``
  ... ``+ num_experts``) of ``w_i down_i(silu(gate_i x) * up_i x)``, written
  the plain way: every held expert on every token, times its routing weight,
  0 where the token did not select it.  What the absent experts would add is
  left out, as in the program; no token is dropped;
- a document starts at each token ``bos_id`` (0): positions restart there and
  attention does not cross it.  Targets ``y`` are next tokens, ``-1`` where
  the next token is another document's; the loss is the mean cross-entropy
  over the positions with a target, in float32, over the vocabulary slice.

The router's contraction is float32 at ``HIGHEST`` and is NOT rounded in the
fp8 control (``families/mla_moe_lm.py`` says why).

The weights (``init_params``) are normal(0, 0.02) but for the routers, which
are made balanced over the deployment's chips (``_router_kernel``): every
seed's run then holds the same work, where normal routers gave a (lane,
layer) anything from no pair to 2.3 x S of them by the seed.

Cost conventions (a sample is one row of S tokens): 2 operations a
multiply-add, backward twice the forward; every matrix a token's products
touch, the routed experts at their expectation ``num_experts_per_tok * held /
router_outputs`` a token, the embedding lookup nothing; attention's two
contractions over the causal positions a layer type may see (``sum_i min(i +
1, window)`` under a window), documents not counted; norms, rotary, softmax,
the router's top-k, the loss and the optimizer count nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pb.arith import HIGHEST, operand
from pb.costs import ITEMSIZE, trained_lanes

BOS = 0
INIT_STD = 0.02
# The router's two parts (``_router_kernel``): a slot's direction, shared by
# one expert of every chip, and each expert's own.  The first is 25 times
# the other matrices' so that a token's best slot stays its best through the
# window's SGD steps (on the chip at 0.06 one seed in five lost up to 14% of
# its pairs by the ninth round; on the CPU at 0.5 a (lane, layer) stays
# within 2% through six steps); the second only breaks the ties inside a slot.
ROUTER_SLOT_STD = 0.5
ROUTER_OWN_STD = 0.0002


# -- the layer list, from the configuration's file --------------------------


def _layer_types(cfg):
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _attn_shapes(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": {"kernel": (h, heads * d)}, "k": {"kernel": (h, kv * d)},
            "v": {"kernel": (h, kv * d)}, "o": {"kernel": (heads * d, h)}}


def layer_shapes(cfg: dict) -> dict:
    """The parameter tree under the program's module names
    (``blades_tpu/models/gqa_moe.py``; stated under ``assumed``)."""
    h, v, f = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    tree = {"embed": (v, h), "head": (h, v), "final_norm": {"scale": (h,)}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": (h,)}, "attn": _attn_shapes(cfg),
            "mlp_norm": {"scale": (h,)},
            "moe": {"router_kernel": (h, cfg["router_outputs"]),
                    "experts_gate": (held, h, f), "experts_up": (held, h, f),
                    "experts_down": (held, f, h)}}
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def num_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        layer_shapes(cfg), is_leaf=_is_shape))


def _router_kernel(key, shape, slots: int):
    """A router whose load is balanced over the deployment's chips, from the
    seed.  Expert ``e`` lies on chip ``e // slots`` at slot ``e % slots``
    (``slots`` experts a chip: the share held here is one chip's).  Column
    ``e`` is its SLOT's direction, one draw of normal(0, ``ROUTER_SLOT_STD``)
    shared by that slot's expert on every chip, plus a part of its own,
    normal(0, ``ROUTER_OWN_STD``).  A token's top-k is then the experts of
    its best slots, one on every chip: each chip is sent ``tokens x top-k /
    chips`` pairs whatever the seed (the state a device-balanced router
    keeps), while WHICH of a chip's experts a token selects follows the
    token, so the load over the held experts keeps the corpus's skew."""
    if shape[1] % slots:
        raise ValueError(f"{shape[1]} router outputs are no whole number of "
                         f"chips of {slots} experts")
    ks, ko = jax.random.split(key)
    slot = np.float32(ROUTER_SLOT_STD) * jax.random.normal(
        ks, (shape[0], slots), jnp.float32)
    own = np.float32(ROUTER_OWN_STD) * jax.random.normal(
        ko, shape, jnp.float32)
    return jnp.tile(slot, (1, shape[1] // slots)) + own


def init_params(cfg: dict, seed: int):
    """The weights every side starts from, made on the device in one jitted
    call from the seed: normal(0, 0.02) matrices and embedding (assumed: the
    source gives no ``initializer_range``), unit norm scales, and the
    routers of :func:`_router_kernel`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        layer_shapes(cfg), is_leaf=_is_shape)

    def leaf(key, path, shape):
        if path[-1].key == "scale":
            return jnp.ones(shape, jnp.float32)
        if path[-1].key == "router_kernel":
            return _router_kernel(key, shape, cfg["num_experts"])
        return np.float32(INIT_STD) * jax.random.normal(key, shape,
                                                         jnp.float32)

    @jax.jit
    def make(key):
        return [leaf(jax.random.fold_in(key, i), path, shape)
                for i, (path, shape) in enumerate(flat)]

    return jax.tree.unflatten(treedef, make(jax.random.PRNGKey(seed)))


# -- the model ----------------------------------------------------------------


def _remat(cfg):
    """Recompute a layer's (and an attention block's) forward pass in the
    backward pass, so that the reference fits beside the update matrix: the
    same arithmetic, stored or recomputed.  ``"remat": false`` in the
    configuration turns it off (the cost test counts a plain step)."""
    return jax.checkpoint if cfg.get("remat", True) else (lambda f: f)


def _rms(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"]


def _positions(tokens):
    """``(segment, position)`` of every token of packed rows ``(B, S)``."""
    start = tokens == BOS
    idx = jnp.arange(tokens.shape[-1], dtype=jnp.int32)
    segment = jnp.cumsum(start.astype(jnp.int32), axis=-1)
    last = lax.cummax(jnp.where(start, idx, 0), axis=1)
    return segment, idx - last


def inv_freq(cfg: dict, layer_type: str):
    """``(inv_freq (head_dim // 2,) float32, attention_factor)`` of a layer
    type's rotary, from ``rope_parameters`` (the module docstring's
    equations, in float64 and rounded once)."""
    rope, d = cfg["rope_parameters"][layer_type], cfg["head_dim"]
    theta = float(rope["rope_theta"])
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope["rope_type"] == "default":
        return extra.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    inter = extra / rope["factor"]

    def cd(turns):
        return d * math.log(rope["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(cd(rope["beta_fast"])), 0)
    high = min(math.ceil(cd(rope["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ((inter * ramp + extra * (1 - ramp)).astype(np.float32),
            float(rope["attention_factor"]))


def _rotary(x, position, inv, factor):
    """Interleaved pairs ``(x[2i], x[2i+1])`` of the last axis turned by
    ``position * inv[i]``, cos and sin times ``factor``; ``x`` ``(B, S,
    heads, dim)``."""
    dim = x.shape[-1]
    ang = position.astype(jnp.float32)[:, :, None, None] * inv
    pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang) * np.float32(factor), \
        jnp.sin(ang) * np.float32(factor)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(cfg, p, x, segment, position, layer_type, q_):
    b, s, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    window = cfg["sliding_window"] if layer_type == "sliding_attention" \
        else None

    def lin(x, name):
        return jnp.dot(q_(x), q_(p[name]["kernel"]), precision=HIGHEST)

    inv, factor = inv_freq(cfg, layer_type)
    q = _rotary(lin(x, "q").reshape(b, s, heads, d), position, inv, factor)
    k = _rotary(lin(x, "k").reshape(b, s, kv, d), position, inv, factor)
    v = lin(x, "v").reshape(b, s, kv, d)
    # Grouped heads the plain way: each key head written out for its group.
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    scale = np.float32(d ** -0.5)
    step = min(cfg.get("attn_block", 512), s)
    key_index = jnp.arange(s)

    @_remat(cfg)
    def block(args):
        """One block of queries against every key, masked: the same compiled
        body for every block (``lax.map``), so the program stays small."""
        qi, seg_q, q0 = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_(qi), q_(k),
                        precision=HIGHEST) * scale
        qpos = (q0 + jnp.arange(step))[:, None]
        ok = (seg_q[:, :, None] == segment[:, None, :]) \
            & (key_index[None, :] <= qpos)
        if window is not None:
            ok = ok & (qpos - key_index[None, :] < window)
        pr = jax.nn.softmax(jnp.where(ok[:, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q_(pr), q_(v),
                          precision=HIGHEST)

    def blocks(a):
        """``(B, S, ...)`` -> ``(S / step, B, step, ...)``."""
        return jnp.swapaxes(a.reshape((b, s // step, step) + a.shape[2:]),
                            0, 1)

    out = lax.map(block, (blocks(q), blocks(segment),
                          jnp.arange(0, s, step)))
    return lin(jnp.swapaxes(out, 0, 1).reshape(b, s, heads * d), "o")


def routing(cfg, p, x):
    """``(weights (B, S, held), idx (B, S, top-k))``: each held expert's
    routing weight a token, 0 where the token did not select it."""
    held = cfg["num_experts"]
    pr = jax.nn.softmax(jnp.dot(x, p["router_kernel"], precision=HIGHEST),
                        axis=-1)
    w, idx = lax.top_k(pr, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    here = idx[..., None] == (cfg["first_expert"] + jnp.arange(held))
    return (w[..., None] * here).sum(-2), idx


def _experts(cfg, p, x, q_):
    """The held experts' part of the routed result: every held expert on
    every token, weighted by its routing weight."""
    w_held, _ = routing(cfg, p, x)
    a = jax.nn.silu(jnp.einsum("bsh,ehf->bsef", q_(x), q_(p["experts_gate"]),
                               precision=HIGHEST)) \
        * jnp.einsum("bsh,ehf->bsef", q_(x), q_(p["experts_up"]),
                     precision=HIGHEST)
    return jnp.einsum("bsef,efh->bsh", q_(a * w_held[..., None]),
                      q_(p["experts_down"]), precision=HIGHEST)


def _layer(cfg, p, x, segment, position, layer_type, q_):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, p["attn"], _rms(x, p["attn_norm"], eps),
                       segment, position, layer_type, q_)
    return x + _experts(cfg, p["moe"], _rms(x, p["mlp_norm"], eps), q_)


def forward(cfg: dict, params, tokens, quant=None):
    """Float32 ``(B, S, vocab)`` logits."""
    q_ = operand(quant)
    segment, position = _positions(tokens)
    x = params["embed"][tokens]
    for i, layer_type in enumerate(_layer_types(cfg)):
        layer = _remat(cfg)(lambda p, x, t=layer_type: _layer(
            cfg, p, x, segment, position, t, q_))
        x = layer(params[f"layer_{i}"], x)
    hidden = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    return jnp.dot(q_(hidden), q_(params["head"]), precision=HIGHEST)


def loss_fn(cfg: dict, params, x, y, quant=None):
    """``x`` ``(batch, S)`` tokens, ``y`` ``(batch, S)`` next-token targets
    (``-1``: none).  Mean cross-entropy over the positions with a target."""
    logp = jax.nn.log_softmax(forward(cfg, params, x, quant))
    ce = -jnp.take_along_axis(logp, jnp.maximum(y, 0)[..., None],
                              axis=-1)[..., 0]
    valid = (y >= 0).astype(jnp.float32)
    return jnp.clip((ce * valid).sum() / jnp.maximum(valid.sum(), 1.0),
                    0.0, 1e6)


# -- the required work, from the configuration's shapes alone -----------------


def routed_experts_per_token(cfg: dict) -> float:
    """Held experts a token selects in expectation (an even router)."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_outputs"])


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token's matrix products touch: every layer's attention
    projections and router, its routed experts at their expectation, and the
    head; the embedding is a lookup and counts nothing."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    attn = sum(int(np.prod(v["kernel"])) for v in _attn_shapes(cfg).values())
    layer = (attn + h * cfg["router_outputs"]
             + routed_experts_per_token(cfg) * 3 * h * f)
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def attention_positions(cfg: dict, layer_type: str) -> int:
    """(query, key) positions of one row a head of this layer type may see:
    ``sum_i min(i + 1, window)``, the causal half without a window."""
    s = cfg["input_shape"][0]
    if layer_type == "sliding_attention":
        w = min(cfg["sliding_window"], s)
        return w * (w + 1) // 2 + (s - w) * w
    return s * (s + 1) // 2


def attention_flops_per_sample(cfg: dict) -> int:
    """Forward + backward of both attention contractions over the positions
    each layer may see: ``3 x 2 contractions x 2 x heads x head_dim`` a
    position."""
    per_position = 3 * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_position * sum(attention_positions(cfg, t)
                              for t in _layer_types(cfg))


def train_flops_per_sample(cfg: dict, fed: dict) -> int:
    """A sample is one packed row of ``S = input_shape[0]`` tokens."""
    s = cfg["input_shape"][0]
    return int(round(s * 6 * matmul_params_per_token(cfg)
                     + attention_flops_per_sample(cfg)))


def train_activation_bytes_per_sample(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of one row's local step beside the parameters: the
    tokens read once; per layer the residual stream, q, k and v, the
    attention output and the routed experts' hidden activations written once
    (forward) and read once (backward) in the compute type; the float32
    logits written and read once."""
    act = ITEMSIZE[cfg["compute_dtype"]]
    s, h, d = cfg["input_shape"][0], cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_token = cfg["num_hidden_layers"] * (
        2 * h + d * (2 * heads + 2 * kv)
        + 2 * routed_experts_per_token(cfg) * cfg["moe_intermediate_size"])
    return int(s * (4 + 2 * act * per_token + 2 * 4 * cfg["vocab_size"]))


def _expert_calls(cfg: dict, fed: dict) -> int:
    """(layer, lane, step, row) blocks a round: each is one call of the
    grouped product a projection and a pass."""
    return (cfg["num_hidden_layers"] * trained_lanes(fed)
            * fed["local_steps"] * fed["batch_size"])


def grouped_matmul_work(cfg: dict, fed: dict, pairs: float) -> tuple:
    """``(operations, bytes)`` of the grouped products over ``pairs``
    routed (token, expert) pairs a round, whatever implements them: three
    projections (gate, up: h -> f; down: f -> h), each forward, for its
    left operand's cotangent and for its weights' (3 x 3 products of 2 x
    pairs x h x f); each product reads its rows, writes its result's rows
    and reads (or writes) the held experts' weights once a call."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    act = ITEMSIZE[cfg["compute_dtype"]]
    weights = _expert_calls(cfg, fed) * cfg["num_experts"] * h * f
    return (9 * 2 * pairs * h * f,
            9 * act * (pairs * (h + f) + weights))


def expected_pairs(cfg: dict, fed: dict) -> float:
    return (routed_experts_per_token(cfg) * cfg["input_shape"][0]
            * _expert_calls(cfg, fed))


def required_attention_scores(cfg: dict, fed: dict) -> int:
    """(query, key, head) positions a round's attention must score."""
    return (cfg["num_attention_heads"] * trained_lanes(fed)
            * fed["local_steps"] * fed["batch_size"]
            * sum(attention_positions(cfg, t) for t in _layer_types(cfg)))


# A kernel's work by name (``pb/costs.py::work``): at the router's
# expectation here; ``COUNTED_WORKS`` takes the round's own count
# (``readers/counted_roofline.py``), which under the corpus's skew differs
# from the expectation by tens of percent.
WORKS = {"grouped_matmul": lambda cfg, fed: grouped_matmul_work(
    cfg, fed, expected_pairs(cfg, fed))}
COUNTED_WORKS = {"grouped_matmul": grouped_matmul_work}
REQUIRED = {"attention_scores": required_attention_scores}

"""The MLA + expert-share + MTP language-model family: everything the
benchmark knows of the model (what a family provides:
``families/cifar_resnet.py``).  Plain ``jax`` and ``numpy``: nothing of the
program under test, and nothing the program has made.

The model is the DeepSeek-V3 family's decoder as JoyAI-LLM-Flash's
``config.json`` sets it, every equation from the configuration's keys:

- block: ``x += attn(rms(x))``, ``x += mlp(rms(x))``, ``rms_norm_eps``, no
  biases;
- latent attention: ``c_q = rms(x W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``
  -> heads x (``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``[c_kv; k_r] =
  x W_kva`` (``kv_lora_rank`` + rope), ``c_kv = rms(c_kv)``; ``[k_nope; v] =
  c_kv W_kvb`` -> heads x (nope + ``v_head_dim``); ``k_r`` is one rotary key
  for all heads; rotary over interleaved pairs (``rope_interleave``),
  ``rope_theta``, no scaling; scores ``(q_nope . k_nope + q_r . k_r) /
  sqrt(nope + rope)``, causal within a document, softmax in float32; ``o =
  (P v) W_o``.  k and v are materialised from the latent (training: no
  absorbed form, no cache).  Computed a block of queries at a time against
  every key under the mask, each block rematerialised in the backward pass,
  so that no (heads, S, S) array is kept; one compiled body for all blocks
  (at HIGHEST the program with a body a block is 661 MB of code, with
  one body 382 MB: ``PERF.md`` section 6, PR 29);
- layers ``< first_k_dense_replace``: SwiGLU of ``intermediate_size``;
- expert layers: ``s = sigmoid(x W_g)`` over all ``router_outputs`` routed
  experts, top-``num_experts_per_tok`` of ``s + b`` (``noaux_tc``, ``n_group``
  = ``topk_group`` = 1: no group limit), weights ``s_i / sum_topk s``
  (``norm_topk_prob``) ``* routed_scaling_factor``; the output is the sum
  over the selected experts HELD HERE (``first_expert`` ...
  ``+ n_routed_experts``) of ``w_i down_i(silu(gate_i x) * up_i x)`` plus
  the shared expert's.  What the absent experts would add is left out, as
  in the program; no token is dropped.  ``b`` gets no gradient;
- a document starts at each token ``bos_id`` (0): positions restart there
  and attention does not cross it.  Targets ``y`` are next tokens, ``-1``
  where the next token is another document's; the loss is the mean
  cross-entropy over the positions with a target, in float32;
- MTP (``num_nextn_predict_layers``; DeepSeek-V3 section 2.2): ``h' = W_eh
  [rms(h_i); rms(Emb(t_{i+1}))]`` -> one expert layer -> its norm -> the
  shared head, predicting ``t_{i+2}``; loss = main + ``mtp_loss_weight`` x
  MTP.

The router's contraction is float32 at ``HIGHEST`` and is NOT rounded in the
fp8 control: the control is the gentle one (the top-k of near-tied scores
flips under any rounding of the scores, and a limit that catches fp8 in the
other contractions catches that too).

Cost conventions (a sample is one row of S tokens): 6 operations for every
parameter a token's matrix products touch (forward 2 a multiply-add,
backward twice that), the routed experts at their expectation
``num_experts_per_tok * held / router_outputs`` a token, the embedding
lookup nothing; attention's two contractions over the causal half, ``3 * 2
* (S / 2) * heads * (qk + v)`` a token a layer; norms, rotary, softmax, the
router's top-k, the loss and the optimizer count nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pb.arith import HIGHEST, operand
from pb.costs import ITEMSIZE

BOS = 0
INIT_STD = 0.02


# -- the layer list, from the configuration's file --------------------------


def _attn_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"q_a": {"kernel": (h, ql)}, "q_norm": {"scale": (ql,)},
            "q_b": {"kernel": (ql, heads * (nope + rope))},
            "kv_a": {"kernel": (h, kvl + rope)}, "kv_norm": {"scale": (kvl,)},
            "kv_b": {"kernel": (kvl, heads * (nope + cfg["v_head_dim"]))},
            "o": {"kernel": (heads * cfg["v_head_dim"], h)}}


def _swiglu_shapes(h, f):
    return {"gate": {"kernel": (h, f)}, "up": {"kernel": (h, f)},
            "down": {"kernel": (f, h)}}


def _layer_shapes(cfg, dense):
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    out = {"attn_norm": {"scale": (h,)}, "attn": _attn_shapes(cfg),
           "mlp_norm": {"scale": (h,)}}
    if dense:
        out["mlp"] = _swiglu_shapes(h, cfg["intermediate_size"])
        return out
    held = cfg["n_routed_experts"]
    moe = {"router_kernel": (h, cfg["router_outputs"]),
           "router_bias": (cfg["router_outputs"],),
           "experts_gate": (held, h, f), "experts_up": (held, h, f),
           "experts_down": (held, f, h)}
    for i in range(cfg["n_shared_experts"]):
        moe[f"shared_{i}"] = _swiglu_shapes(h, f)
    out["moe"] = moe
    return out


def layer_shapes(cfg: dict) -> dict:
    """The parameter tree under the program's module names
    (``blades_tpu/models/mla_moe.py``; stated under ``assumed``)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    tree = {"embed": (v, h), "head": (h, v), "final_norm": {"scale": (h,)}}
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layer_{i}"] = _layer_shapes(
            cfg, i < cfg["first_k_dense_replace"])
    for k in range(cfg["num_nextn_predict_layers"]):
        tree[f"mtp_{k}_h_norm"] = {"scale": (h,)}
        tree[f"mtp_{k}_e_norm"] = {"scale": (h,)}
        tree[f"mtp_{k}_eh_proj"] = {"kernel": (2 * h, h)}
        tree[f"mtp_{k}_layer"] = _layer_shapes(cfg, False)
        tree[f"mtp_{k}_norm"] = {"scale": (h,)}
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def num_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        layer_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: dict, seed: int):
    """The weights every side starts from, made on the device in one jitted
    call from the seed: normal(0, 0.02) matrices and embedding (assumed: the
    source gives no ``initializer_range``), unit norm scales, zero ``b``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        layer_shapes(cfg), is_leaf=_is_shape)

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            leaf = path[-1].key
            if leaf == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            elif leaf == "router_bias":
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                out.append(np.float32(INIT_STD) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
        return out

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


def _rotary(x, position, theta):
    """Interleaved pairs ``(x[2i], x[2i+1])`` of the last axis turned by
    ``position * theta ** (-2i / dim)``; ``x`` ``(B, S, ..., dim)``."""
    dim = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ang = position.astype(jnp.float32)[..., None] * inv
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (dim // 2,))
    pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(cfg, p, x, segment, position, q_):
    b, s, _ = x.shape
    heads, nope, rope = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"])
    kvl, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]

    def lin(x, name):
        return jnp.dot(q_(x), q_(p[name]["kernel"]), precision=HIGHEST)

    c_q = _rms(lin(x, "q_a"), p["q_norm"], eps)
    q = lin(c_q, "q_b").reshape(b, s, heads, nope + rope)
    kv = lin(x, "kv_a")
    c_kv = _rms(kv[..., :kvl], p["kv_norm"], eps)
    k_r = _rotary(kv[..., kvl:], position, cfg["rope_theta"])
    kvb = lin(c_kv, "kv_b").reshape(b, s, heads, nope + cfg["v_head_dim"])
    q = jnp.concatenate(
        [q[..., :nope], _rotary(q[..., nope:], position, cfg["rope_theta"])],
        axis=-1)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_r[:, :, None, :], (b, s, heads, rope))], axis=-1)
    v = kvb[..., nope:]
    scale = np.float32((nope + rope) ** -0.5)

    step = min(cfg.get("attn_block", 512), s)
    key_index = jnp.arange(s)

    @_remat(cfg)
    def block(args):
        """One block of queries against every key, masked: the same compiled
        body for every block (``lax.map``), so the program stays small."""
        qi, seg_q, q0 = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_(qi), q_(k),
                        precision=HIGHEST) * scale
        ok = (seg_q[:, :, None] == segment[:, None, :]) & (
            key_index[None, :] <= (q0 + jnp.arange(step))[:, None])
        pr = jax.nn.softmax(jnp.where(ok[:, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q_(pr), q_(v),
                          precision=HIGHEST)

    def blocks(a):
        """``(B, S, ...)`` -> ``(S / step, B, step, ...)``."""
        return jnp.swapaxes(a.reshape((b, s // step, step) + a.shape[2:]),
                            0, 1)

    out = lax.map(block, (blocks(q), blocks(segment),
                          jnp.arange(0, s, step)))
    o = jnp.swapaxes(out, 0, 1).reshape(b, s, heads * cfg["v_head_dim"])
    return lin(o, "o")


def _swiglu(p, x, q_):
    def lin(x, name):
        return jnp.dot(q_(x), q_(p[name]["kernel"]), precision=HIGHEST)

    return lin(jax.nn.silu(lin(x, "gate")) * lin(x, "up"), "down")


def _experts(cfg, p, x, q_):
    """The held experts' part of the routed result, and the shared
    expert's.  Every held expert is computed on every token and weighted by
    its routing weight, 0 where the token did not select it."""
    held = cfg["n_routed_experts"]
    s = jax.nn.sigmoid(jnp.dot(x, p["router_kernel"], precision=HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(p["router_bias"]),
                       cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * np.float32(cfg["routed_scaling_factor"])
    here = idx[..., None] == (cfg["first_expert"] + jnp.arange(held))
    w_held = (w[..., None] * here).sum(-2)                    # (B, S, held)
    a = jax.nn.silu(jnp.einsum("bsh,ehf->bsef", q_(x), q_(p["experts_gate"]),
                               precision=HIGHEST)) \
        * jnp.einsum("bsh,ehf->bsef", q_(x), q_(p["experts_up"]),
                     precision=HIGHEST)
    y = jnp.einsum("bsef,efh->bsh", q_(a * w_held[..., None]),
                   q_(p["experts_down"]), precision=HIGHEST)
    for i in range(cfg["n_shared_experts"]):
        y = y + _swiglu(p[f"shared_{i}"], x, q_)
    return y


def _layer(cfg, p, x, segment, position, q_):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, p["attn"], _rms(x, p["attn_norm"], eps),
                       segment, position, q_)
    h = _rms(x, p["mlp_norm"], eps)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], h, q_)
    return x + _experts(cfg, p["moe"], h, q_)


def forward(cfg: dict, params, tokens, quant=None):
    """One float32 ``(B, S, vocab)`` logits plane per prediction depth."""
    q_ = operand(quant)
    eps = cfg["rms_norm_eps"]
    segment, position = _positions(tokens)
    layer = _remat(cfg)(lambda p, x: _layer(cfg, p, x, segment, position,
                                            q_))
    x = params["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = layer(params[f"layer_{i}"], x)

    def logits(h):
        return jnp.dot(q_(h), q_(params["head"]), precision=HIGHEST)

    planes = [logits(_rms(x, params["final_norm"], eps))]
    for k in range(cfg["num_nextn_predict_layers"]):
        ahead = params["embed"][jnp.roll(tokens, -(k + 1), axis=1)]
        joined = jnp.concatenate(
            [_rms(x, params[f"mtp_{k}_h_norm"], eps),
             _rms(ahead, params[f"mtp_{k}_e_norm"], eps)], axis=-1)
        x = jnp.dot(q_(joined), q_(params[f"mtp_{k}_eh_proj"]["kernel"]),
                    precision=HIGHEST)
        x = layer(params[f"mtp_{k}_layer"], x)
        planes.append(logits(_rms(x, params[f"mtp_{k}_norm"], eps)))
    return planes


def _plane_targets(y, depth):
    t = y
    for _ in range(depth):
        ahead = jnp.concatenate([t[..., 1:], jnp.full_like(t[..., :1], -1)],
                                axis=-1)
        t = jnp.where(y >= 0, ahead, -1)
    return t


def loss_fn(cfg: dict, params, x, y, quant=None):
    """``x`` ``(batch, S)`` tokens, ``y`` ``(batch, S)`` next-token targets
    (``-1``: none).  Mean cross-entropy over the positions with a target,
    the MTP planes' added at ``mtp_loss_weight``."""
    loss = 0.0
    for depth, logits in enumerate(forward(cfg, params, x, quant)):
        t = _plane_targets(y, depth)
        logp = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(logp, jnp.maximum(t, 0)[..., None],
                                  axis=-1)[..., 0]
        valid = (t >= 0).astype(jnp.float32)
        weight = 1.0 if depth == 0 else np.float32(cfg["mtp_loss_weight"])
        loss = loss + weight * (ce * valid).sum() / jnp.maximum(
            valid.sum(), 1.0)
    return jnp.clip(loss, 0.0, 1e6)


# -- the required work, from the configuration's shapes alone -----------------


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token's matrix products touch: every matrix of every
    layer and the head once a prediction depth, the routed experts at
    their expectation; the embedding is a lookup and counts nothing."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    attn = sum(int(np.prod(v["kernel"])) for v in _attn_shapes(cfg).values()
               if "kernel" in v)
    dense = attn + 3 * h * cfg["intermediate_size"]
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["router_outputs"])
    expert = (attn + h * cfg["router_outputs"]
              + (cfg["n_shared_experts"] + routed) * 3 * h * f)
    n_dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    head = h * cfg["vocab_size"]
    mtp = cfg["num_nextn_predict_layers"]
    return (n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * expert
            + head + mtp * (2 * h * h + expert + head))


def attention_flops_per_token(cfg: dict) -> float:
    """Forward + backward of both attention contractions over the causal
    half, all layers (the MTP module's included)."""
    s = cfg["input_shape"][0]
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (3 * 2 * (s / 2) * cfg["num_attention_heads"]
            * (qk + cfg["v_head_dim"]) * layers)


def train_flops_per_sample(cfg: dict, fed: dict) -> int:
    """A sample is one packed row of ``S = input_shape[0]`` tokens."""
    s = cfg["input_shape"][0]
    return int(round(s * (6 * matmul_params_per_token(cfg)
                          + attention_flops_per_token(cfg))))


def train_activation_bytes_per_sample(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of one row's local step beside the parameters: the
    tokens read once; per layer the residual stream, q, k and v, the
    attention output and the MLP's hidden activations written once
    (forward) and read once (backward) in the compute type; the float32
    logits written and read once a prediction depth."""
    act = ITEMSIZE[cfg["compute_dtype"]]
    s, h = cfg["input_shape"][0], cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 2 * h + heads * (2 * qk + 2 * cfg["v_head_dim"])
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["router_outputs"])
    expert = attn + 2 * (cfg["n_shared_experts"] + routed) \
        * cfg["moe_intermediate_size"]
    dense = attn + 2 * cfg["intermediate_size"]
    n_dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    mtp = cfg["num_nextn_predict_layers"]
    per_token = (n_dense * dense
                 + (cfg["num_hidden_layers"] - n_dense + mtp) * expert)
    return int(s * (4 + 2 * act * per_token
                    + 2 * 4 * cfg["vocab_size"] * (1 + mtp)))


# No kernel of its own yet: attention, the router and the experts are plain
# XLA in the program, so its rooflines are the shared "train" and "finish".
WORKS = {}

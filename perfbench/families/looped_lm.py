"""The looped (weight-tied) language-model family: everything the benchmark
knows of the model (what a family provides: ``families/cifar_resnet.py``).
Plain ``jax`` and ``numpy``: nothing of the program under test, and nothing
the program has made.

The model is the decoder Ouro-2.6B's ``config.json`` sets (Zhu et al. 2025,
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741),
every equation from the configuration's keys:

- layer, sandwich norms (``input_layernorm`` N1, ``input_layernorm_2`` N2,
  ``post_attention_layernorm`` N3, ``post_attention_layernorm_2`` N4, each
  an RMSNorm of its own, ``rms_norm_eps``)::

      a  = h + N2(Attn(N1(h)))
      h' = a + N4(MLP(N3(a)))

- ``Attn``: ``q, k, v = x W_q, x W_k, x W_v``, ``num_attention_heads`` (=
  ``num_key_value_heads``) x ``head_dim``, no bias (no key gives one);
  rotary ``rope_theta`` over all ``head_dim`` features on q and k, pairs
  interleaved ``(x[2i], x[2i+1])`` (the published code turns halves: a fixed
  permutation of q's and k's features alike; ``assumed``); scores ``q . k /
  sqrt(head_dim)``, causal within a document, no window
  (``use_sliding_window`` false), softmax in float32; ``o = (P v) W_o``.
  Computed a block of queries at a time against EVERY key under the mask,
  each block rematerialised in the backward pass; one compiled body for all
  blocks;
- ``MLP(x) = W_down(silu(x W_gate) * x W_up)``, width ``intermediate_size``;
- recurrence, T = ``total_ut_steps``: ``h^0 = Embed(tokens)``; for t = 1..T
  ``h^t = N_f(Stack(h^{t-1}))``, ``Stack`` the ``num_hidden_layers`` held
  layers with the same weights at every step and ``N_f`` the final norm;
- exit t: ``z^t = h^t W_head`` over the vocabulary slice; ``lambda^t =
  sigmoid(h^t . w_g + b_g)``; ``p(t) = lambda^t prod_{j<t} (1 - lambda^j)``
  for t < T, ``p(T) = prod_{j<T} (1 - lambda^j)``, written as the products;
- a document starts at each token ``bos_id`` (0): positions restart there
  and attention does not cross it.  Targets ``y`` are next tokens, ``-1``
  where the next token is another document's; the loss is the mean over the
  positions with a target of ``sum_t p(t) CE^t - entropy_beta * H(p)``,
  ``H(p) = -sum_t p(t) log p(t)``, in float32 (the paper's
  entropy-regularised objective; ``entropy_beta`` 0.1 is ``assumed``).
  Training runs every step: ``early_exit_threshold`` is read at inference.

Every contraction, the gate's too, is float32 at ``HIGHEST`` and rounded in
the fp8 control.  :func:`last_step_loss_fn` is the second control: the last
step's cross-entropy alone, the exit weighting and the entropy left out.

The weights (``init_params``) are normal(0, 0.02), the gate's weights and
bias too; norm scales are 1.

Cost conventions (a sample is one row of S tokens): 2 operations a
multiply-add, backward twice the forward.  The work counts LAYER PASSES, not
parameters: each of the T steps runs every held layer, so a token's products
touch every layer's matrices T times, and each of the T exits touches the
head and the gate; the embedding lookup counts nothing; attention's two
contractions over the causal positions, a layer pass each; norms, rotary,
softmax, the loss and the optimizer count nothing.  What a program
recomputes is no required work and counts nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pb.arith import HIGHEST, operand
from pb.costs import ITEMSIZE, trained_lanes

BOS = 0
INIT_STD = 0.02
EXIT_BLOCK = 1024   # positions an exit's logits are computed for at once
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


# -- the parameter tree, from the configuration's file ------------------------


def _layer_matrices(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return {"self_attn": {"q": {"kernel": (h, width)},
                          "k": {"kernel": (h, width)},
                          "v": {"kernel": (h, width)},
                          "o": {"kernel": (width, h)}},
            "mlp": {"gate": {"kernel": (h, f)}, "up": {"kernel": (h, f)},
                    "down": {"kernel": (f, h)}}}


def layer_shapes(cfg: dict) -> dict:
    """The parameter tree under the program's module names
    (``blades_tpu/models/looped_lm.py``; stated under ``assumed``): the
    held stack once, whatever the number of steps."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    tree = {"embed": (v, h), "head": (h, v), "final_norm": {"scale": (h,)},
            "early_exit_gate": {"kernel": (h, 1), "bias": (1,)}}
    for i in range(cfg["num_hidden_layers"]):
        layer = _layer_matrices(cfg)
        layer.update({n: {"scale": (h,)} for n in NORMS})
        tree[f"layer_{i}"] = layer
    return tree


def _is_shape(x):
    return isinstance(x, tuple)


def num_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        layer_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: dict, seed: int):
    """The weights every side starts from, made on the device in one jitted
    call from the seed: normal(0, 0.02) for every matrix, the embedding and
    the gate's bias (assumed: the source gives no ``initializer_range``),
    unit norm scales."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        layer_shapes(cfg), is_leaf=_is_shape)

    def leaf(key, path, shape):
        if path[-1].key == "scale":
            return jnp.ones(shape, jnp.float32)
        return np.float32(INIT_STD) * jax.random.normal(key, shape,
                                                         jnp.float32)

    @jax.jit
    def make(key):
        return [leaf(jax.random.fold_in(key, i), path, shape)
                for i, (path, shape) in enumerate(flat)]

    return jax.tree.unflatten(treedef, make(jax.random.PRNGKey(seed)))


# -- the model ----------------------------------------------------------------


def _remat(cfg):
    """Recompute a step's, a layer pass's and an exit block's forward in the
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
    ``position * theta ** (-2i / dim)``; ``x`` ``(B, S, heads, dim)``."""
    dim = x.shape[-1]
    inv = (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(
        np.float32)
    ang = position.astype(jnp.float32)[:, :, None, None] * inv
    pairs = x.reshape(x.shape[:-1] + (dim // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(cfg, p, x, segment, position, q_):
    b, s, _ = x.shape
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]

    def lin(x, name):
        return jnp.dot(q_(x), q_(p[name]["kernel"]), precision=HIGHEST)

    theta = float(cfg["rope_theta"])
    q = _rotary(lin(x, "q").reshape(b, s, heads, d), position, theta)
    k = _rotary(lin(x, "k").reshape(b, s, heads, d), position, theta)
    v = lin(x, "v").reshape(b, s, heads, d)
    scale = np.float32(d ** -0.5)
    step = min(cfg.get("attn_block", 512), s)
    key_index = jnp.arange(s)

    @_remat(cfg)
    def block(args):
        """One block of queries against every key, masked: the same
        compiled body for every block (``lax.map``)."""
        qi, seg_q, q0 = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_(qi), q_(k),
                        precision=HIGHEST) * scale
        qpos = (q0 + jnp.arange(step))[:, None]
        ok = (seg_q[:, :, None] == segment[:, None, :]) \
            & (key_index[None, :] <= qpos)
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


def _mlp(p, x, q_):
    def lin(x, name):
        return jnp.dot(q_(x), q_(p[name]["kernel"]), precision=HIGHEST)

    return lin(jax.nn.silu(lin(x, "gate")) * lin(x, "up"), "down")


def _layer(cfg, p, h, segment, position, q_):
    eps = cfg["rms_norm_eps"]
    a = h + _rms(_attention(cfg, p["self_attn"],
                            _rms(h, p["input_layernorm"], eps),
                            segment, position, q_),
                 p["input_layernorm_2"], eps)
    return a + _rms(_mlp(p["mlp"], _rms(a, p["post_attention_layernorm"],
                                        eps), q_),
                    p["post_attention_layernorm_2"], eps)


def _exit(params, h, q_):
    """Step t's logits ``(B, S, vocab)`` and gate ``(B, S)`` from its exit
    state."""
    z = jnp.dot(q_(h), q_(params["head"]), precision=HIGHEST)
    g = params["early_exit_gate"]
    lam = jax.nn.sigmoid(jnp.dot(q_(h), q_(g["kernel"]),
                                 precision=HIGHEST)[..., 0] + g["bias"][0])
    return z, lam


def _steps(cfg, params, tokens, quant, each):
    """Run the T steps; ``each(h^t, q_)`` of every step's exit state,
    stacked in step order.  One compiled body serves every step
    (``lax.scan``: the weights are the same at every step)."""
    q_ = operand(quant)
    segment, position = _positions(tokens)

    @_remat(cfg)
    def step(h, _):
        for i in range(cfg["num_hidden_layers"]):
            h = _remat(cfg)(lambda p, h: _layer(
                cfg, p, h, segment, position, q_))(params[f"layer_{i}"], h)
        h = _rms(h, params["final_norm"], cfg["rms_norm_eps"])
        return h, each(h, q_)

    return lax.scan(step, params["embed"][tokens], None,
                    length=cfg["total_ut_steps"])[1]


def forward(cfg: dict, params, tokens, quant=None):
    """``(z (T, B, S, vocab) float32, p (T, B, S))``: every step's logits
    and the exit distribution."""
    z, lam = _steps(cfg, params, tokens, quant,
                    lambda h, q_: _exit(params, h, q_))
    return z, exit_distribution(lam)


def exit_distribution(lams):
    """``p (T, B, S)`` from the gates ``lambda`` ``(T, B, S)``:
    ``p(t) = lambda^t prod_{j<t} (1 - lambda^j)``, ``p(T) = prod_{j<T} (1 -
    lambda^j)`` (the last gate is not read)."""
    stay = jnp.ones_like(lams[0])
    p = []
    for lam in lams[:-1]:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def _cross_entropy(z, y):
    logp = jax.nn.log_softmax(z)
    return -jnp.take_along_axis(logp, jnp.maximum(y, 0)[..., None],
                                axis=-1)[..., 0]


def loss_fn(cfg: dict, params, x, y, quant=None):
    """``x`` ``(batch, S)`` tokens, ``y`` ``(batch, S)`` next-token targets
    (``-1``: none).  The mean over the positions with a target of ``sum_t
    p(t) CE^t - entropy_beta H(p)``.  An exit's logits live only inside
    the cross-entropy of a block of ``EXIT_BLOCK`` positions, each
    block rematerialised: one compiled body for every block
    (``lax.map``)."""
    b, s = x.shape
    step = min(EXIT_BLOCK, s)

    def exit_loss(h, q_):
        @_remat(cfg)
        def one(args):
            hi, yi = args
            z, lam = _exit(params, hi, q_)
            return _cross_entropy(z, yi), lam

        def blocks(a):
            return jnp.swapaxes(a.reshape((b, s // step, step) + a.shape[2:]),
                                0, 1)

        ce, lam = lax.map(one, (blocks(h), blocks(y)))
        return (jnp.swapaxes(ce, 0, 1).reshape(b, s),
                jnp.swapaxes(lam, 0, 1).reshape(b, s))

    ce, lam = _steps(cfg, params, x, quant, exit_loss)
    p = exit_distribution(lam)
    per_position = (p * ce).sum(0) + np.float32(cfg["entropy_beta"]) * (
        p * jnp.log(p)).sum(0)
    valid = (y >= 0).astype(jnp.float32)
    return jnp.clip((per_position * valid).sum()
                    / jnp.maximum(valid.sum(), 1.0), 0.0, 1e6)


def last_step_loss_fn(cfg: dict, params, x, y, quant=None):
    """The control that leaves the mechanism out: the last step's mean
    cross-entropy alone, no exit weighting and no entropy."""
    h = _steps(cfg, params, x, quant, lambda h, q_: h)[-1]
    z, _ = _exit(params, h, operand(quant))
    valid = (y >= 0).astype(jnp.float32)
    return jnp.clip((_cross_entropy(z, y) * valid).sum()
                    / jnp.maximum(valid.sum(), 1.0), 0.0, 1e6)


# -- the required work, from the configuration's shapes alone -----------------


def layer_passes(cfg: dict) -> int:
    """Layer passes a token makes a forward pass: every step runs every held
    layer."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def matmul_params_per_token(cfg: dict) -> int:
    """Matrix parameters a token's products touch a forward pass: each held
    layer's T times, the head's and the gate's once an exit; the embedding
    is a lookup and counts nothing."""
    layer = sum(int(np.prod(s)) for s in jax.tree.leaves(
        _layer_matrices(cfg), is_leaf=_is_shape))
    exit_ = cfg["hidden_size"] * (cfg["vocab_size"] + 1)
    return layer_passes(cfg) * layer + cfg["total_ut_steps"] * exit_


def attention_flops_per_sample(cfg: dict) -> int:
    """Forward + backward of both attention contractions over the causal
    positions, a layer pass each: ``3 x 2 contractions x 2 x heads x
    head_dim`` a position."""
    s = cfg["input_shape"][0]
    per_position = 3 * 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_position * layer_passes(cfg) * (s * (s + 1) // 2)


def train_flops_per_sample(cfg: dict, fed: dict) -> int:
    """A sample is one packed row of ``S = input_shape[0]`` tokens."""
    s = cfg["input_shape"][0]
    return int(s * 6 * matmul_params_per_token(cfg)
               + attention_flops_per_sample(cfg))


def train_activation_bytes_per_sample(cfg: dict, fed: dict) -> int:
    """Least HBM traffic of one row's local step beside the parameters: the
    tokens read once; per layer pass the residual stream, q, k and v, the
    attention output and the MLP's two hidden activations written once
    (forward) and read once (backward) in the compute type; each exit's
    float32 logits written and read once."""
    act = ITEMSIZE[cfg["compute_dtype"]]
    s, h = cfg["input_shape"][0], cfg["hidden_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    per_pass = 2 * h + 4 * width + 2 * cfg["intermediate_size"]
    return int(s * (4 + 2 * act * layer_passes(cfg) * per_pass
                    + cfg["total_ut_steps"] * 2 * 4 * cfg["vocab_size"]))


def required_attention_scores(cfg: dict, fed: dict) -> int:
    """(query, key, head) positions a round's attention must score: the
    causal half of each row at every layer pass."""
    s = cfg["input_shape"][0]
    return (cfg["num_attention_heads"] * trained_lanes(fed)
            * fed["local_steps"] * fed["batch_size"] * layer_passes(cfg)
            * (s * (s + 1) // 2))


WORKS = {}
REQUIRED = {"attention_scores": required_attention_scores}

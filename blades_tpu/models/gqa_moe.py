"""A decoder-only language model with grouped-query attention, window and
full attention layers mixed, a rotary of its own for each kind of layer
(YaRN on the full ones) and an expert share computed as routing: the block
as Mellum2-12B-A2.5B-Instruct configures it
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json).

Input is ``(batch, S)`` int32 tokens of packed documents: a document
starts at each token ``BOS_ID`` (0), attention does not cross a boundary and
rotary positions restart at it (:func:`~blades_tpu.models.layers.
packed_positions`).

Every equation, by module (all from the published ``config.json``):

- block: ``x += attn(rms(x))``, ``x += moe(rms(x))``, ``rms_norm_eps``, no
  biases.  Every layer is sparse (``mlp_layer_types``), so the published
  ``intermediate_size`` (7168) is read by no layer and is no key here;
- :class:`GroupedQueryAttention`: ``q = x W_q`` -> ``num_attention_heads``
  x ``head_dim``; ``k = x W_k``, ``v = x W_v`` -> ``num_key_value_heads`` x
  ``head_dim``; query head ``h`` reads key head ``h // (heads //
  kv_heads)`` and k, v are never repeated; rotary on q and k; scores
  ``q . k / sqrt(head_dim)``, softmax in float32; ``o = (P v) W_o``.  Query
  ``i`` sees key ``j`` iff they are of one document, ``j <= i`` and, in a
  ``sliding_attention`` layer, ``i - j < sliding_window``.  Computed by
  :func:`~blades_tpu.models.layers.packed_causal_attention`: on a TPU, at
  a sequence and head widths its tiles take, one fused kernel a call
  (:mod:`blades_tpu.ops.attention`; a tile of scores never leaves VMEM);
  elsewhere XLA query blocks of ``attn_block``, each rematerialised in the
  backward pass (that path's own);
- rotary by layer type (``rope_parameters[layer_type]``): ``default`` is
  ``theta ** (-2i / head_dim)``; ``yarn`` is static (applied at every
  length): :func:`~blades_tpu.models.layers.yarn_inv_freq` and cos, sin
  times ``attention_factor``.  Pairs are the interleaved ones
  (:func:`~blades_tpu.models.layers.rotary_interleaved`); the published
  code turns halves, a fixed permutation of q's and k's features alike
  that leaves every score as it is;
- :class:`RoutedExperts`: ``p = softmax(x W_g)`` in float32 over ALL
  ``num_experts``, top-``num_experts_per_tok``, weights ``p_i / sum_topk
  p`` (``norm_topk_prob``); no bias, no scaling factor, no shared expert,
  no auxiliary loss.  The layer is told which experts it holds
  (``first_expert``, ``experts_held``) and computes the part of the result
  its own experts give, as routing: the selected pairs whose expert is
  held are gathered by expert and go through one grouped product a
  projection (:mod:`blades_tpu.ops.grouped`).  What the absent experts
  would add is left out; no token is dropped, no capacity is set.

Parameters the task must leave in float32 under mixed precision are named
by ``float32_params`` (the router).  Device scopes: ``blades/attn_window``,
``blades/attn_full``, ``blades/router``, ``blades/experts``,
``blades/head``.  Each layer sows ``stats/attn_scores`` (the (query, key)
positions its attention computes, from shapes: the XLA blocks' reach, or
the fused kernel's own block list where that ran), ``stats/attn_fused``
(1 where the fused kernel ran), ``stats/expert_tokens``,
``stats/routed_pairs`` and ``stats/expert_rows`` (pair rows the grouped
product worked on); :meth:`GqaMoeLM.round_counters` reduces a round's to
its row counters.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from blades_tpu.models.layers import (
    Linear,
    RMSNorm,
    attention_scores_computed,
    kernel_param,
    packed_causal_attention,
    packed_positions,
    rotary_interleaved,
    yarn_inv_freq,
)
from blades_tpu.ops import attention, grouped

# The token that starts a document in a packed row (data/datasets.py).
BOS_ID = 0
_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


def _freeze(value):
    """Lists and dicts of a YAML or JSON spec as hashable tuples."""
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


@dataclasses.dataclass(frozen=True)
class GqaMoeConfig:
    """The published ``config.json`` keys the model reads, under their own
    names, plus the chip's share (``first_expert``, ``experts_held`` of
    ``num_experts``; a vocabulary slice is just a smaller ``vocab_size``;
    a cut in depth reads the first ``num_hidden_layers`` of
    ``layer_types``).  Defaults are Mellum2-12B-A2.5B-Instruct's."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    layer_types: Tuple[str, ...] = _PERIOD * 7
    sliding_window: int = 1024
    rope_parameters: tuple = _freeze({
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}})
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    first_expert: int = 0
    experts_held: int = 64
    attn_block: int = 512

    @classmethod
    def from_dict(cls, d: dict) -> "GqaMoeConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            raise KeyError(f"unknown gqa_moe_lm keys {unknown}")
        cfg = cls(**{k: _freeze(v) for k, v in d.items()})
        if not (0 <= cfg.first_expert
                and cfg.first_expert + cfg.experts_held <= cfg.num_experts):
            raise ValueError(
                f"experts [{cfg.first_expert}, "
                f"{cfg.first_expert + cfg.experts_held}) are not among the "
                f"{cfg.num_experts} routed ones")
        if len(cfg.layer_types) < cfg.num_hidden_layers:
            raise ValueError(
                f"{cfg.num_hidden_layers} layers, {len(cfg.layer_types)} "
                "layer_types")
        for kind in set(cfg.layer_types[:cfg.num_hidden_layers]):
            cfg.rotary(kind)
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("num_attention_heads is no multiple of "
                             "num_key_value_heads")
        return cfg

    def rotary(self, layer_type: str) -> dict:
        """``{"theta", "inv_freq", "attention_factor"}`` of a layer type."""
        if layer_type not in ("sliding_attention", "full_attention"):
            raise ValueError(f"unknown layer type {layer_type!r}")
        rope = dict(dict(self.rope_parameters)[layer_type])
        theta = float(rope["rope_theta"])
        if rope["rope_type"] == "default":
            return {"theta": theta, "inv_freq": None,
                    "attention_factor": 1.0}
        if rope["rope_type"] != "yarn":
            raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
        return {"theta": theta,
                "inv_freq": yarn_inv_freq(
                    self.head_dim, theta, float(rope["factor"]),
                    int(rope["original_max_position_embeddings"]),
                    float(rope.get("beta_fast", 32)),
                    float(rope.get("beta_slow", 1))),
                "attention_factor": float(rope["attention_factor"])}


class GroupedQueryAttention(nn.Module):
    cfg: GqaMoeConfig
    layer_type: str

    @nn.compact
    def __call__(self, x, segment, position):
        c = self.cfg
        heads, kv_heads, dim = (c.num_attention_heads, c.num_key_value_heads,
                                c.head_dim)
        b, s, _ = x.shape
        window = c.sliding_window \
            if self.layer_type == "sliding_attention" else None
        rope = c.rotary(self.layer_type)
        scope = "blades/attn_full" if window is None else "blades/attn_window"
        with jax.named_scope(scope):
            q = Linear(heads * dim, name="q")(x).reshape(b, s, heads, dim)
            k = Linear(kv_heads * dim, name="k")(x).reshape(
                b, s, kv_heads, dim)
            v = Linear(kv_heads * dim, name="v")(x).reshape(
                b, s, kv_heads, dim)
            q = rotary_interleaved(q, position, rope["theta"],
                                   rope["inv_freq"], rope["attention_factor"])
            k = rotary_interleaved(k, position, rope["theta"],
                                   rope["inv_freq"], rope["attention_factor"])
            o = packed_causal_attention(q, k, v, segment, dim ** -0.5,
                                        c.attn_block, window)
            fused = attention.default_impl(s, dim, dim) != "jnp"
            # A host int from shapes; past int32 from two rows of 8192 on.
            self.sow("stats", "attn_scores", jnp.float32(b * heads * (
                attention.scores_computed(s, window) if fused else
                attention_scores_computed(s, c.attn_block, window))))
            self.sow("stats", "attn_fused", jnp.int32(fused))
            return Linear(c.hidden_size, name="o")(
                o.reshape(b, s, heads * dim))


class RoutedExperts(nn.Module):
    """The routed experts this chip holds of one expert layer.  Routes over
    all ``num_experts``; computes its own experts' pairs as routing."""

    cfg: GqaMoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h, f, held = c.hidden_size, c.moe_intermediate_size, c.experts_held
        b, s, _ = x.shape
        w_g = self.param("router_kernel", nn.initializers.normal(0.02),
                         (h, c.num_experts), jnp.float32)
        with jax.named_scope("blades/router"):
            p = jax.nn.softmax(jnp.dot(
                x.astype(jnp.float32), w_g.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), axis=-1)
            w, idx = lax.top_k(p, c.num_experts_per_tok)
            if c.norm_topk_prob:
                w = w / w.sum(-1, keepdims=True)
            local = idx - c.first_expert
            here = (local >= 0) & (local < held)
        with jax.named_scope("blades/experts"):
            gate = kernel_param(self, "experts_gate", (held, h, f))
            up = kernel_param(self, "experts_up", (held, h, f))
            down = kernel_param(self, "experts_down", (held, f, h))
            dt = x.dtype
            k = c.num_experts_per_tok
            y, sizes, rows = grouped.routed_ffn(
                x.reshape(b * s, h), local.reshape(b * s, k),
                here.reshape(b * s, k), w.reshape(b * s, k),
                gate.astype(dt), up.astype(dt), down.astype(dt))
        self.sow("stats", "expert_tokens", sizes)
        self.sow("stats", "routed_pairs", jnp.int32(idx.size))
        self.sow("stats", "expert_rows", rows)
        return y.reshape(b, s, h)


class DecoderLayer(nn.Module):
    cfg: GqaMoeConfig
    layer_type: str

    @nn.compact
    def __call__(self, x, segment, position):
        c = self.cfg
        x = x + GroupedQueryAttention(c, self.layer_type, name="attn")(
            RMSNorm(c.rms_norm_eps, name="attn_norm")(x), segment, position)
        return x + RoutedExperts(c, name="moe")(
            RMSNorm(c.rms_norm_eps, name="mlp_norm")(x))


class GqaMoeLM(nn.Module):
    cfg: GqaMoeConfig = GqaMoeConfig()

    # Leaves whose name holds one of these stay float32 under a bf16
    # compute type (core/task.py::cast_to_compute).
    float32_params: Tuple[str, ...] = ("router_kernel",)
    sequence_model = True
    loss_weights: Tuple[float, ...] = (1.0,)

    @staticmethod
    def round_counters(stats: dict) -> dict:
        """A round's row counters (``obs/schema.py``) from what the layers
        sowed, stacked ``(lanes, layers, ...)`` over the trained lanes: the
        expert share's four (``models/mla_moe.py``), the pair rows the
        grouped product worked on and the attention scores computed."""
        tokens = stats["expert_tokens"]
        return {
            "expert_tokens_max": tokens.max(),
            "expert_tokens_mean": tokens.mean(dtype=jnp.float32),
            "routed_here_share": tokens.sum() / stats["routed_pairs"].sum()
            .astype(jnp.float32),
            "zero_expert_blocks": (tokens == 0).sum(),
            "expert_pairs_here": tokens.sum(),
            "expert_rows_computed": stats["expert_rows"].sum(),
            "attn_scores_computed": stats["attn_scores"].sum(),
            "attn_fused_calls": stats["attn_fused"].sum(),
        }

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        c = self.cfg
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (c.vocab_size, c.hidden_size))
        head = kernel_param(self, "head", (c.hidden_size, c.vocab_size))
        segment, position = packed_positions(tokens, BOS_ID)
        x = emb[tokens]
        # No layer is rematerialised: the backward pass reads what each
        # layer's forward pass kept (q, k and v after the rotary, the
        # attention's output and log-sum-exp, the projections' inputs, the
        # routed layer's gathered rows and SwiGLU activations) and computes
        # no forward pass again.  At the chip's shapes (rows of 8192, one
        # lane a block, four layers) the compiler's account of the
        # language-model round's ``_train_block`` is 13.76 of 15.75 GB
        # with them, 10.21 GB under a layer-wide remat
        # (tools/aot_lm_round.py fedavg_codelm_crosssilo).  A longer row or
        # more rows a lane grows them in proportion: read that account
        # again before either.
        for i in range(c.num_hidden_layers):
            x = DecoderLayer(c, c.layer_types[i], name=f"layer_{i}")(
                x, segment, position)
        hidden = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
        with jax.named_scope("blades/head"):
            return (jnp.dot(hidden, head.astype(hidden.dtype),
                            preferred_element_type=jnp.float32),)


def gqa_moe_lm(num_classes=None, **kw) -> GqaMoeLM:
    """Catalog builder: ``num_classes`` is the vocabulary (slice)."""
    if num_classes is not None:
        kw.setdefault("vocab_size", int(num_classes))
    return GqaMoeLM(GqaMoeConfig.from_dict(kw))

"""A decoder-only language model with latent attention (MLA), an
expert-share layer and a multi-token-prediction module: the DeepSeek-V3
family's block, as JoyAI-LLM-Flash configures it
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json).

Input is ``(batch, S)`` int32 tokens of packed documents: a document
starts at each token ``BOS_ID`` (0), attention does not cross a boundary and
rotary positions restart at it (:func:`~blades_tpu.models.layers.
packed_positions`).

Every equation, by module:

- block: ``x += attn(rms(x))``, ``x += mlp(rms(x))``, no biases;
- :class:`LatentAttention`: ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` ->
  heads x (nope + rope); ``[c_kv; k_r] = x W_kva``, ``c_kv = rms(c_kv)``;
  ``[k_nope; v] = c_kv W_kvb``; ``k_r`` is one rotary key for all heads;
  scores ``(q_nope . k_nope + q_r . k_r) / sqrt(nope + rope)``, softmax in
  float32; ``o = (P v) W_o``.  Training materialises k and v from the
  latent: no absorbed form, no cache.  Computed by
  :func:`~blades_tpu.models.layers.packed_causal_attention`: on a TPU, at
  a sequence and head widths its tiles take, one fused kernel a call
  (:mod:`blades_tpu.ops.attention`); elsewhere XLA query blocks of
  ``attn_block``, each rematerialised in the backward pass (that path's
  own);
- :class:`ExpertShare`: ``s = sigmoid(x W_g)`` in float32 over ALL routed
  experts, top-k of ``s + b`` (``noaux_tc``, one group), weights
  ``s_i / sum_topk s * routed_scaling_factor``; the layer is told which
  experts it holds (``first_expert``, ``experts_held``) and computes the
  part of the result its own experts give, plus the shared expert.  What
  the absent experts would add is left out; no token is dropped and no
  capacity is set.  ``b`` is in the tree and gets no gradient.  The held
  experts run densely over every token with weight 0 where a token did
  not select them: exact, and 32x the routed work at 8 of 256 held;
- MTP: ``h' = W_eh [rms(h); rms(Emb(t_{i+1}))]`` -> one expert layer ->
  its norm -> the shared head.

Parameters the task must leave in float32 under mixed precision are named
by ``float32_params`` (the router: bf16 scores flip near-tied top-k
choices).  Device scopes: ``blades/attn``, ``blades/router``,
``blades/experts``, ``blades/head``.  Each expert layer sows
``stats/expert_tokens`` (tokens each held expert received) and
``stats/routed_pairs`` (the (token, expert) pairs it routed, held or not),
each attention ``stats/attn_fused`` (1 where the fused kernel ran);
:meth:`MlaMoeLM.round_counters` reduces a round's to its row counters.

The model returns one float32 ``(batch, S, vocab)`` logits plane per
prediction depth (``loss_weights`` weighs their losses): plane 0 predicts
token ``i + 1`` at position ``i``, plane 1 (MTP) token ``i + 2``.
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
    SwiGLU,
    kernel_param,
    packed_causal_attention,
    packed_positions,
    rotary_interleaved,
)
from blades_tpu.ops import attention


# The token that starts a document in a packed row (data/datasets.py).
BOS_ID = 0


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """The published ``config.json`` keys the model reads, under their own
    names, plus the chip's share (``first_expert``, ``experts_held`` of
    ``n_routed_experts``; a vocabulary slice is just a smaller
    ``vocab_size``).  Defaults are JoyAI-LLM-Flash's."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rms_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 1
    first_expert: int = 0
    experts_held: int = 256
    attn_block: int = 512
    # Weight of each MTP plane's loss beside the main one's (the config
    # gives none; DeepSeek-V3 trained with 0.3 then 0.1).
    mtp_loss_weight: float = 0.1

    @classmethod
    def from_dict(cls, d: dict) -> "MlaMoeConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            raise KeyError(f"unknown mla_moe_lm keys {unknown}")
        cfg = cls(**d)
        if not (0 <= cfg.first_expert
                and cfg.first_expert + cfg.experts_held
                <= cfg.n_routed_experts):
            raise ValueError(
                f"experts [{cfg.first_expert}, "
                f"{cfg.first_expert + cfg.experts_held}) are not among the "
                f"{cfg.n_routed_experts} routed ones")
        return cfg


class LatentAttention(nn.Module):
    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x, segment, position):
        c = self.cfg
        heads, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                             c.qk_rope_head_dim)
        b, s, _ = x.shape
        with jax.named_scope("blades/attn"):
            c_q = RMSNorm(c.rms_norm_eps, name="q_norm")(
                Linear(c.q_lora_rank, name="q_a")(x))
            q = Linear(heads * (nope + rope), name="q_b")(c_q).reshape(
                b, s, heads, nope + rope)
            kv = Linear(c.kv_lora_rank + rope, name="kv_a")(x)
            c_kv = RMSNorm(c.rms_norm_eps, name="kv_norm")(
                kv[..., :c.kv_lora_rank])
            k_r = rotary_interleaved(kv[..., c.kv_lora_rank:], position,
                                     c.rope_theta)
            kvb = Linear(heads * (nope + c.v_head_dim), name="kv_b")(
                c_kv).reshape(b, s, heads, nope + c.v_head_dim)
            q = jnp.concatenate(
                [q[..., :nope],
                 rotary_interleaved(q[..., nope:], position, c.rope_theta)],
                axis=-1)
            k = jnp.concatenate(
                [kvb[..., :nope],
                 jnp.broadcast_to(k_r[:, :, None, :], (b, s, heads, rope))],
                axis=-1)
            o = packed_causal_attention(
                q, k, kvb[..., nope:], segment, (nope + rope) ** -0.5,
                c.attn_block)
            self.sow("stats", "attn_fused", jnp.int32(attention.default_impl(
                s, nope + rope, c.v_head_dim) != "jnp"))
            return Linear(c.hidden_size, name="o")(
                o.reshape(b, s, heads * c.v_head_dim))


class ExpertShare(nn.Module):
    """The routed experts this chip holds of one expert layer, and the
    shared expert.  Routes over all ``n_routed_experts``."""

    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h, f, held = c.hidden_size, c.moe_intermediate_size, c.experts_held
        w_g = self.param("router_kernel", nn.initializers.normal(0.02),
                         (h, c.n_routed_experts), jnp.float32)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (c.n_routed_experts,), jnp.float32)
        with jax.named_scope("blades/router"):
            s = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), w_g.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            _, idx = lax.top_k(s + lax.stop_gradient(bias),
                               c.num_experts_per_tok)
            w = jnp.take_along_axis(s, idx, axis=-1)
            if c.norm_topk_prob:
                w = w / w.sum(-1, keepdims=True)
            w = w * c.routed_scaling_factor
            here = idx[..., None] == (c.first_expert + jnp.arange(held))
            w_held = (w[..., None] * here).sum(-2)          # (B, S, held)
            self.sow("stats", "expert_tokens",
                     here.sum((0, 1, 2)).astype(jnp.int32))
            self.sow("stats", "routed_pairs", jnp.int32(idx.size))
        with jax.named_scope("blades/experts"):
            gate = kernel_param(self, "experts_gate", (held, h, f))
            up = kernel_param(self, "experts_up", (held, h, f))
            down = kernel_param(self, "experts_down", (held, f, h))
            dt = x.dtype
            a = jax.nn.silu(jnp.einsum("bsh,ehf->bsef", x, gate.astype(dt))) \
                * jnp.einsum("bsh,ehf->bsef", x, up.astype(dt))
            a = a * w_held.astype(dt)[..., None]
            y = jnp.einsum("bsef,efh->bsh", a, down.astype(dt))
            for i in range(c.n_shared_experts):
                y = y + SwiGLU(f, name=f"shared_{i}")(x)
        return y


class DecoderLayer(nn.Module):
    cfg: MlaMoeConfig
    dense: bool

    @nn.compact
    def __call__(self, x, segment, position):
        c = self.cfg
        x = x + LatentAttention(c, name="attn")(
            RMSNorm(c.rms_norm_eps, name="attn_norm")(x), segment, position)
        h = RMSNorm(c.rms_norm_eps, name="mlp_norm")(x)
        if self.dense:
            return x + SwiGLU(c.intermediate_size, name="mlp")(h)
        return x + ExpertShare(c, name="moe")(h)


class MlaMoeLM(nn.Module):
    cfg: MlaMoeConfig = MlaMoeConfig()

    # Leaves whose name holds one of these stay float32 under a bf16
    # compute type (core/task.py::cast_to_compute).
    float32_params: Tuple[str, ...] = ("router_kernel", "router_bias")
    sequence_model = True

    @property
    def loss_weights(self) -> Tuple[float, ...]:
        return (1.0,) + (self.cfg.mtp_loss_weight,) \
            * self.cfg.num_nextn_predict_layers

    @staticmethod
    def round_counters(stats: dict) -> dict:
        """A round's row counters (``obs/schema.py``) from what the expert
        layers sowed, stacked ``(lanes, layers, ...)`` over the trained
        lanes: the most and the mean tokens a held expert received; the
        share of routed (token, expert) pairs whose expert is held here;
        the (lane, layer, held expert) blocks that received no token; the
        (lane, layer)s whose attention ran the fused kernel."""
        tokens = stats["expert_tokens"]
        return {
            "expert_tokens_max": tokens.max(),
            "expert_tokens_mean": tokens.mean(dtype=jnp.float32),
            "routed_here_share": tokens.sum() / stats["routed_pairs"].sum()
            .astype(jnp.float32),
            "zero_expert_blocks": (tokens == 0).sum(),
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
        for i in range(c.num_hidden_layers):
            x = DecoderLayer(c, i < c.first_k_dense_replace,
                             name=f"layer_{i}")(x, segment, position)

        def logits_of(hidden):
            with jax.named_scope("blades/head"):
                return jnp.dot(hidden, head.astype(hidden.dtype),
                               preferred_element_type=jnp.float32)

        planes = [logits_of(RMSNorm(c.rms_norm_eps, name="final_norm")(x))]
        for k in range(c.num_nextn_predict_layers):
            # Position i joins its hidden state with the embedding of the
            # token k + 1 ahead (the row's last positions see token 0's:
            # they have no target and are masked in the loss).
            ahead = jnp.roll(tokens, -(k + 1), axis=1)
            joined = jnp.concatenate(
                [RMSNorm(c.rms_norm_eps, name=f"mtp_{k}_h_norm")(x),
                 RMSNorm(c.rms_norm_eps, name=f"mtp_{k}_e_norm")(emb[ahead])],
                axis=-1)
            x = Linear(c.hidden_size, name=f"mtp_{k}_eh_proj")(joined)
            x = DecoderLayer(c, False, name=f"mtp_{k}_layer")(
                x, segment, position)
            planes.append(logits_of(
                RMSNorm(c.rms_norm_eps, name=f"mtp_{k}_norm")(x)))
        return tuple(planes)


def mla_moe_lm(num_classes=None, **kw) -> MlaMoeLM:
    """Catalog builder: ``num_classes`` is the vocabulary (slice)."""
    if num_classes is not None:
        kw.setdefault("vocab_size", int(num_classes))
    return MlaMoeLM(MlaMoeConfig.from_dict(kw))

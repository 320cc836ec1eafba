"""A looped (weight-tied) decoder-only language model: the held stack of
layers runs ``total_ut_steps`` times a forward pass with the same weights,
and every step exits through the final norm, an exit gate and the head.
The block as Ouro-2.6B configures it
(https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json; Zhu et
al. 2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).

Input is ``(batch, S)`` int32 tokens of packed documents: a document starts
at each token ``BOS_ID`` (0), attention does not cross a boundary and
rotary positions restart at it (:func:`~blades_tpu.models.layers.
packed_positions`).

Every equation, by module (the published module names where they are
known):

- layer (sandwich norms ``input_layernorm`` N1, ``input_layernorm_2`` N2,
  ``post_attention_layernorm`` N3, ``post_attention_layernorm_2`` N4, each
  an RMSNorm of its own, ``rms_norm_eps``, statistics in float32)::

      a  = h + N2(Attn(N1(h)))
      h' = a + N4(MLP(N3(a)))

- ``Attn``: ``q, k, v = x W_q, x W_k, x W_v``, each
  ``num_attention_heads`` (= ``num_key_value_heads``) x ``head_dim``, no
  bias; rotary ``rope_theta`` on q and k over all ``head_dim`` features
  (interleaved pairs, :func:`~blades_tpu.models.layers.rotary_interleaved`:
  the published code turns halves, a fixed permutation of q's and k's
  features alike that leaves every score as it is); scores ``q . k /
  sqrt(head_dim)``, causal within a document, no window, softmax in
  float32; ``o = (P v) W_o``.  Computed by :func:`~blades_tpu.models.
  layers.packed_causal_attention` (one fused kernel a call on a TPU);
- ``MLP(x) = W_down(silu(x W_gate) * x W_up)``, width ``intermediate_size``;
- recurrence, T = ``total_ut_steps``: ``h^0 = Embed(tokens)``; for t = 1..T
  ``h^t = N_f(Stack(h^{t-1}))``, ``Stack`` the held layers and ``N_f`` the
  final norm: ``h^t`` is step t's exit state and step t+1's input;
- exit t: logits ``z^t = h^t W_head`` (float32) over the vocabulary held;
  gate ``lambda^t = sigmoid(h^t . w_g + b_g)`` a position
  (``early_exit_gate``); exit distribution ``p(t) = lambda^t prod_{j<t} (1 -
  lambda^j)`` for t < T and ``p(T) = prod_{j<T} (1 - lambda^j)``, computed
  in log space (``log_sigmoid``) so that a saturated gate gives 0 and not
  ``0 * log 0``;
- loss (``core/task.py::Task.sequence_loss``): the mean over the positions
  with a target of ``sum_t p(t) CE^t - entropy_beta * H(p)``, ``CE^t`` the
  float32 cross-entropy of ``z^t`` against the next token and ``H(p) =
  -sum_t p(t) log p(t)``: the paper's entropy-regularised expected loss.
  Training runs all T steps: ``early_exit_threshold`` is read at inference
  only, and at 1 it runs every step, so the model's answer is ``z^T``.

The model returns its T logits planes with that answer first: ``(z^T, z^1,
..., z^{T-1})``, all predicting the next token (``plane_depths``), and sows
into the ``loss`` collection each plane's exit weight ``p(t)`` (``(B, S)``,
in plane order: ``plane_weights``) and ``-entropy_beta * H(p)``
(``position_term``), which the task's loss reads.

The T steps call the same module instances: the parameters hold the stack
once.  Nothing is rematerialised: at the chip's shapes the compiler's
account of the round's programs decides that
(``perfbench/configs/ouro_2p6b_pp12.json``, ``sequence_why``).

Parameters the task must leave in float32 under mixed precision are named
by ``float32_params`` (the exit gate).  Device scopes: ``blades/embed``;
``blades/attn`` (a layer's attention half: its two norms, the projections,
the rotary and the residual add) and inside it ``blades/attn_kernel`` (the
attention itself); ``blades/mlp`` (the MLP half, its two norms and residual
add); ``blades/exit`` (each step's final norm, gate, head product and the
exit distribution, and the task's cross-entropy and weighting of the
planes: ``loss_scope``).  Each attention sows ``stats/attn_fused`` (1 where
the fused kernel ran) and ``stats/attn_scores`` (the (query, key, head)
positions it scored, as ``models/gqa_moe.py`` counts them), each exit
``stats/exits``, and the last step's exit
mass ``stats/exit_last_mass`` over ``stats/exit_positions``;
:meth:`LoopedLM.round_counters` reduces a round's to its row counters.
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
    attention_scores_computed,
    kernel_param,
    packed_causal_attention,
    packed_positions,
    rotary_interleaved,
)
from blades_tpu.ops import attention

# The token that starts a document in a packed row (data/datasets.py).
BOS_ID = 0


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    """The published ``config.json`` keys the model reads, under their own
    names (a vocabulary slice is a smaller ``vocab_size``; a cut in depth a
    smaller ``num_hidden_layers``), and ``entropy_beta``, the objective's
    entropy weight, which the configuration does not give.  Defaults are
    Ouro-2.6B's."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    entropy_beta: float = 0.1
    attn_block: int = 512

    @classmethod
    def from_dict(cls, d: dict) -> "LoopedConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - names)
        if unknown:
            raise KeyError(f"unknown looped_lm keys {unknown}")
        cfg = cls(**d)
        if cfg.num_attention_heads != cfg.num_key_value_heads:
            raise ValueError("the looped model's attention has as many key "
                             "heads as query heads")
        if cfg.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be at least 1")
        return cfg


class Attention(nn.Module):
    cfg: LoopedConfig

    @nn.compact
    def __call__(self, x, segment, position):
        c = self.cfg
        heads, dim = c.num_attention_heads, c.head_dim
        b, s, _ = x.shape
        q = Linear(heads * dim, name="q")(x).reshape(b, s, heads, dim)
        k = Linear(heads * dim, name="k")(x).reshape(b, s, heads, dim)
        v = Linear(heads * dim, name="v")(x).reshape(b, s, heads, dim)
        q = rotary_interleaved(q, position, c.rope_theta)
        k = rotary_interleaved(k, position, c.rope_theta)
        with jax.named_scope("blades/attn_kernel"):
            o = packed_causal_attention(q, k, v, segment, dim ** -0.5,
                                        c.attn_block)
        fused = attention.default_impl(s, dim, dim) != "jnp"
        # A host int from shapes; past int32 from two rows of 8192 on.
        self.sow("stats", "attn_scores", jnp.float32(b * heads * (
            attention.scores_computed(s) if fused else
            attention_scores_computed(s, c.attn_block))))
        self.sow("stats", "attn_fused", jnp.int32(fused))
        return Linear(c.hidden_size, name="o")(o.reshape(b, s, heads * dim))


class DecoderLayer(nn.Module):
    """``a = h + N2(Attn(N1(h)))``, ``h' = a + N4(MLP(N3(a)))``."""

    cfg: LoopedConfig

    @nn.compact
    def __call__(self, h, segment, position):
        c = self.cfg
        eps = c.rms_norm_eps
        with jax.named_scope("blades/attn"):
            a = h + RMSNorm(eps, name="input_layernorm_2")(
                Attention(c, name="self_attn")(
                    RMSNorm(eps, name="input_layernorm")(h),
                    segment, position))
        with jax.named_scope("blades/mlp"):
            return a + RMSNorm(eps, name="post_attention_layernorm_2")(
                SwiGLU(c.intermediate_size, name="mlp")(
                    RMSNorm(eps, name="post_attention_layernorm")(a)))


class LoopedLM(nn.Module):
    cfg: LoopedConfig = LoopedConfig()

    # Leaves whose name holds one of these stay float32 under a bf16
    # compute type (core/task.py::cast_to_compute): the gate weighs every
    # step's loss, a position at a time.
    float32_params: Tuple[str, ...] = ("early_exit_gate",)
    sequence_model = True
    loss_scope = "blades/exit"

    @property
    def plane_depths(self) -> Tuple[int, ...]:
        """Every exit predicts the next token."""
        return (0,) * self.cfg.total_ut_steps

    @staticmethod
    def round_counters(stats: dict) -> dict:
        """A round's row counters (``obs/schema.py``) from what the model
        sowed, stacked over the trained lanes: the steps a forward pass ran,
        the mean exit weight the gate left on the last step over every
        position of the trained rows, the attention scores computed and the
        attention calls (layer passes) that ran the fused kernel."""
        return {
            "loop_steps": stats["exits"].shape[-1],
            "exit_last_step_mass": stats["exit_last_mass"].sum()
            / stats["exit_positions"].sum(),
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
        layers = [DecoderLayer(c, name=f"layer_{i}")
                  for i in range(c.num_hidden_layers)]
        final_norm = RMSNorm(c.rms_norm_eps, name="final_norm")
        gate = nn.Dense(1, kernel_init=nn.initializers.normal(0.02),
                        bias_init=nn.initializers.normal(0.02),
                        dtype=jnp.float32, precision=lax.Precision.HIGHEST,
                        name="early_exit_gate")
        segment, position = packed_positions(tokens, BOS_ID)
        with jax.named_scope("blades/embed"):
            h = emb[tokens]
        planes, log_gate, log_stay = [], [], []
        for _ in range(c.total_ut_steps):
            for layer in layers:
                h = layer(h, segment, position)
            with jax.named_scope("blades/exit"):
                h = final_norm(h)
                planes.append(jnp.dot(h, head.astype(h.dtype),
                                      preferred_element_type=jnp.float32))
                g = gate(h.astype(jnp.float32))[..., 0]
                log_gate.append(jax.nn.log_sigmoid(g))
                log_stay.append(jax.nn.log_sigmoid(-g))
                self.sow("stats", "exits", jnp.int32(1))
        with jax.named_scope("blades/exit"):
            # log p(t) = log lambda^t + sum_{j<t} log(1 - lambda^j); the
            # last step takes what the gates left (its own gate is unread).
            log_stay = jnp.stack(log_stay)
            before = jnp.concatenate([jnp.zeros_like(log_stay[:1]),
                                      jnp.cumsum(log_stay[:-1], axis=0)])
            log_p = jnp.concatenate([jnp.stack(log_gate)[:-1] + before[:-1],
                                     before[-1:]])
            p = jnp.exp(log_p)
            # Plane order: the answer (step T) first.
            order = [c.total_ut_steps - 1] + list(range(c.total_ut_steps - 1))
            for t in order:
                self.sow("loss", "plane_weights", p[t])
            self.sow("loss", "position_term",
                     c.entropy_beta * (p * log_p).sum(0))
            self.sow("stats", "exit_last_mass", p[-1].sum())
            self.sow("stats", "exit_positions", jnp.float32(p[-1].size))
        return tuple(planes[t] for t in order)


def looped_lm(num_classes=None, **kw) -> LoopedLM:
    """Catalog builder: ``num_classes`` is the vocabulary (slice)."""
    if num_classes is not None:
        kw.setdefault("vocab_size", int(num_classes))
    return LoopedLM(LoopedConfig.from_dict(kw))

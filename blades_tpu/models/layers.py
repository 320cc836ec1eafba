"""Shared layers: stateless batch normalisation, keyed dropout, and the
pack-axis dense primitive.

The reference pins ``track_running_stats=False`` on every BatchNorm
(ref: fllib/models/cifar10/resnet_cifar.py:10-18) so that federated weight
averaging never mixes desynchronised running statistics.  In JAX that
semantics is *simpler* than the stateful default: normalise by the current
batch's statistics, carry no state at all.  This keeps model application a
pure function ``(params, x) -> logits`` — which is what lets per-client
models be a stacked-params ``vmap``.

**Keyed dropout** (:func:`keyed_dropout`): dropout masks derived from an
explicit per-call key via ``fold_in(key, layer_index)`` instead of flax's
scope-path ``make_rng`` folding.  The mask then depends only on
``(key, layer index)`` — not on the module tree it is called from — which
is what lets the client lane-packing path (:mod:`blades_tpu.parallel.
packed`) reproduce each client's masks exactly inside a structurally
different grouped-kernel module.  Models opting in carry
``explicit_dropout = True`` and take ``dropout_key=`` as a call argument
(:meth:`blades_tpu.core.task.Task.apply` routes it).

**PackedDense**: P clients' ``(fin, fout)`` dense layers as one
``(P, fin, fout)`` block-batched einsum over ``(B, P, fin)`` activations —
the pack-axis formulation of ``nn.Dense`` (same contraction per group, no
cross-group terms), sized so narrow per-client matmuls still tile the MXU.
"""

from __future__ import annotations

import math
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blades_tpu.ops import attention

# --------------------------------------------------------------------------
# Hand-written batch-stats-norm VJP
# --------------------------------------------------------------------------
#
# Autodiff of the naive mean/var formulation leaves XLA with five
# separate reductions per BN layer in the backward; writing the standard
# BN backward by hand (two reductions, dscale reused for the dx projection)
# measured ~4% off the whole vmapped ResNet-10 training block on a v5e
# (artifacts/perf_r4/time_bn.py).  Stats accumulate in f32 with a
# two-pass centered variance (robust for any |mean|/std the activations
# reach); the backward is where the win lives.


def _bn_normalize(x, axes, eps, keepdims=False):
    """f32 stats + normalize shared by every BatchStatsNorm branch.
    Two-pass CENTERED variance: the one-pass E[x^2] - mean^2 form loses
    the variance entirely to f32 rounding when |mean|/std > ~2^12, which
    f32 activations can hit.

    Returns ``(xhat, mean, r)`` with mean/r cast to ``x.dtype``.
    """
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=keepdims)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=keepdims)
    r = lax.rsqrt(var + eps)
    mean = mean.astype(x.dtype)
    r = r.astype(x.dtype)
    return (x - mean) * r, mean, r


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_apply(x, scale, bias, eps):
    y, _ = _bn_apply_fwd(x, scale, bias, eps)
    return y


def _bn_apply_fwd(x, scale, bias, eps):
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    xhat, mean, r = _bn_normalize(x, axes, eps)
    y = xhat * scale + bias
    # Residuals: x is the producing conv's output, which XLA materializes
    # anyway — saving xhat instead would add one full activation set per
    # BN layer and compile-OOMs the 1000-client bench block.
    return y, (x, mean, r, scale, n)


def _bn_apply_bwd(eps, res, dy):
    x, mean, r, scale, n = res
    axes = tuple(range(dy.ndim - 1))
    xhat = (x - mean) * r
    dbias = jnp.sum(dy.astype(jnp.float32), axis=axes).astype(dy.dtype)
    dscale = jnp.sum((dy * xhat).astype(jnp.float32), axis=axes).astype(
        dy.dtype)
    dxhat = dy * scale
    mean_dxhat = jnp.sum(dxhat.astype(jnp.float32), axis=axes).astype(
        dy.dtype) / n
    mean_dxhat_xhat = dscale * scale / n
    dx = r * (dxhat - mean_dxhat - xhat * mean_dxhat_xhat)
    return dx, dscale, dbias


_bn_apply.defvjp(_bn_apply_fwd, _bn_apply_bwd)


def keyed_dropout(x, rate, key, layer_index, deterministic):
    """Inverted dropout with an explicitly derived mask key.

    ``mask = bernoulli(fold_in(key, layer_index), 1 - rate, x.shape)`` —
    a pure function of the call-site key and the layer's index, so the
    packed execution path can regenerate client ``g``'s mask from client
    ``g``'s key regardless of module structure.  ``deterministic=True``
    (eval) is the identity and needs no key.
    """
    if deterministic or rate == 0.0:
        return x
    if key is None:
        raise ValueError(
            "train-mode dropout needs an explicit dropout key: pass "
            "dropout_key= to the model call (Task.apply threads it)"
        )
    keep = 1.0 - rate
    mask = jax.random.bernoulli(
        jax.random.fold_in(key, layer_index), keep, x.shape
    )
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def packed_keyed_dropout(x, rate, keys, layer_index, deterministic):
    """:func:`keyed_dropout` over pack-axis activations ``(B, P, F)``.

    Group ``g``'s mask is ``bernoulli(fold_in(keys[g], layer_index),
    1 - rate, (B, F))`` — exactly the mask the unpacked model draws for
    client ``g`` under ``dropout_key = keys[g]``, which is what makes the
    packed trajectory match the unpacked one beyond fp reassociation.
    """
    if deterministic or rate == 0.0:
        return x
    if keys is None:
        raise ValueError(
            "train-mode packed dropout needs per-group keys: pass "
            "dropout_keys= (P keys, one per packed client)"
        )
    keep = 1.0 - rate
    batch, _, feat = x.shape

    def one_group(k):
        return jax.random.bernoulli(
            jax.random.fold_in(k, layer_index), keep, (batch, feat)
        )

    mask = jnp.moveaxis(jax.vmap(one_group)(keys), 0, 1)  # (B, P, F)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


class PackedDense(nn.Module):
    """P clients' dense layers as one block-batched einsum.

    Params mirror ``nn.Dense`` with a leading pack axis: ``kernel``
    ``(P, fin, fout)``, ``bias`` ``(P, fout)`` — exactly
    ``jnp.stack`` of the per-client leaves, which is the pack rule
    :mod:`blades_tpu.parallel.packed` applies.  Input/output are
    ``(B, P, fin)`` / ``(B, P, fout)``; group ``g`` only ever contracts
    with slice ``kernel[g]``, so no activations cross packed clients.
    """

    features: int
    pack: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        fin = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (self.pack, fin, self.features),
        )
        y = jnp.einsum("bpi,pio->bpo", x, kernel.astype(x.dtype))
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (self.pack, self.features)
            )
            y = y + bias.astype(y.dtype)[None]
        return y


class BatchStatsNorm(nn.Module):
    """Batch-statistics-only normalisation with learned scale/bias."""

    epsilon: float = 1e-5
    use_scale: bool = True
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        import os

        features = x.shape[-1]
        scale = (
            self.param("scale", nn.initializers.ones, (features,))
            if self.use_scale
            else None
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (features,))
            if self.use_bias
            else None
        )
        # Escape hatch to the pre-r4 two-pass jnp.mean/jnp.var autodiff
        # formulation.  Read at TRACE time: flipping it after a jitted
        # program compiled has no effect on that program — set it before
        # the first forward (fresh process), like BLADES_TPU_NO_PALLAS.
        hand_vjp = os.environ.get("BLADES_TPU_BN_VJP", "1") != "0"  # blades-lint: disable=jit-purity — documented fresh-process escape hatch, trace-time by contract (see comment above)
        if scale is not None and bias is not None and hand_vjp:
            return _bn_apply(x, scale.astype(x.dtype),
                             bias.astype(x.dtype), self.epsilon)
        axes = tuple(range(x.ndim - 1))
        if hand_vjp:  # use_scale/use_bias off: stats formula still
            y = _bn_normalize(x, axes, self.epsilon)[0]  # matches _bn_apply
        else:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            y = (x - mean) * lax.rsqrt(var + self.epsilon)
        if scale is not None:
            y = y * scale
        if bias is not None:
            y = y + bias
        return y


# --------------------------------------------------------------------------
# Sequence-model primitives: RMS norm, interleaved rotary, SwiGLU, and
# causal attention over packed documents (a fused kernel on a TPU,
# ops/attention.py; XLA query blocks elsewhere)
# --------------------------------------------------------------------------


class RMSNorm(nn.Module):
    """``x / rms(x) * scale``, statistics in float32, no bias."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        xf = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                            + self.epsilon)
        return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def kernel_param(module, name, shape, std=0.02):
    return module.param(name, nn.initializers.normal(std), shape)


class Linear(nn.Module):
    """Bias-free dense layer in the input's dtype."""

    features: int

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        w = kernel_param(self, "kernel", (x.shape[-1], self.features))
        return jnp.dot(x, w.astype(x.dtype))


class SwiGLU(nn.Module):
    """``down(silu(gate x) * up x)``, bias-free."""

    width: int

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        h = jax.nn.silu(Linear(self.width, name="gate")(x)) \
            * Linear(self.width, name="up")(x)
        return Linear(x.shape[-1], name="down")(h)


def packed_positions(tokens: jnp.ndarray, bos_id: int):
    """``(segment, position)`` of every token of packed rows ``(..., S)``:
    a document starts at each ``bos_id`` token (a row's first tokens, up
    to its first ``bos_id``, continue a document cut by the row's edge);
    positions restart at 0 with each document."""
    start = tokens == bos_id
    idx = jnp.arange(tokens.shape[-1], dtype=jnp.int32)
    segment = jnp.cumsum(start.astype(jnp.int32), axis=-1)
    last_start = lax.cummax(jnp.where(start, idx, 0), axis=tokens.ndim - 1)
    return segment, idx - last_start


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's blended rotary frequencies (Peng et al. 2023), static: pair
    ``i`` of ``dim // 2`` turns at ``theta ** (-2i / dim)`` where it makes
    over ``beta_fast`` turns inside the original context (extrapolated),
    at that over ``factor`` where it makes under ``beta_slow``
    (interpolated), and a linear blend of the two between.  A float32
    numpy vector for :func:`rotary_interleaved`'s ``inv_freq``."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def correction_dim(turns):
        return (dim * math.log(original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def rotary_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                       theta: float, inv_freq=None,
                       attention_factor: float = 1.0) -> jnp.ndarray:
    """Rotary embedding over interleaved pairs ``(x[2i], x[2i+1])`` of the
    last axis, angle ``position * theta ** (-2i / dim)``, in float32.
    ``x`` is ``(B, S, ..., dim)`` and ``positions`` ``(B, S)``.  With
    ``inv_freq`` ``(dim // 2,)`` the pairs turn at those frequencies
    instead (:func:`yarn_inv_freq`), and ``attention_factor`` scales the
    cosine and the sine alike (YaRN's ``mscale``)."""
    dim = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (dim // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def attention_key_start(q0: int, block: int, window=None) -> int:
    """The first key a query block that starts at ``q0`` reads: 0 without
    a window; under one, the first key its first query may see, ``q0 -
    window + 1``, rounded down to a lane tile (128, or the block where
    that is smaller) so that the slice stays aligned."""
    if window is None:
        return 0
    align = min(block, 128)
    return max(0, (q0 - window + 1) // align * align)


def attention_scores_computed(s: int, block: int = 512, window=None) -> int:
    """(query, key) positions of one row of ``s`` tokens whose score the
    XLA query blocks of :func:`packed_causal_attention` compute, a head:
    what its blocks read, masked or not.  (The fused kernel's count is
    :func:`blades_tpu.ops.attention.scores_computed`.)"""
    block = min(block, s)
    return sum(block * (q0 + block - attention_key_start(q0, block, window))
               for q0 in range(0, s, block))


def packed_causal_attention(q, k, v, segment, scale: float,
                            block: int = 512, window=None,
                            impl=None) -> jnp.ndarray:
    """Softmax attention, causal within a document.

    ``q`` is ``(B, S, H, dk)``, ``k`` ``(B, S, Hk, dk)``, ``v`` ``(B, S,
    Hk, dv)``, ``segment`` ``(B, S)``.  ``H`` is a multiple of ``Hk``:
    query head ``h`` reads key head ``h // (H // Hk)`` (grouped-query
    attention; k and v are never repeated).  With ``window`` a query sees
    only the keys less than ``window`` positions behind it.  Scores and
    the softmax are float32 on either path:

    - ``impl="kernel"`` (the default where :func:`blades_tpu.ops.attention.
      kernel_applicable`: a TPU, a sequence and head widths its tiles
      take): one fused kernel a call, which keeps a tile of scores in VMEM
      and computes them again in its own backward kernels; ``block`` is
      not read.  ``"interpret"`` is that kernel in Pallas's interpreter
      (the tests);
    - ``impl="jnp"`` (the default elsewhere): XLA query blocks of
      ``block``.  The block at ``q0`` reads keys ``[attention_key_start(
      q0), q0 + block)`` only and is rematerialised in the backward pass
      (this path's own ``jax.checkpoint``), so no ``(H, S, S)`` score array
      outlives its block."""
    s, heads, kv_heads = q.shape[1], q.shape[2], k.shape[2]
    if impl is None:
        impl = attention.default_impl(s, q.shape[-1], v.shape[-1])
    if impl != "jnp":
        return attention.fused_causal_attention(
            q, k, v, segment, scale, window, interpret=impl == "interpret")
    block = min(block, s)
    if s % block:
        raise ValueError(f"sequence length {s} is no multiple of the "
                         f"attention block {block}")
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads are no multiple of "
                         f"{kv_heads} key heads")
    group = heads // kv_heads
    # Equal heads contract as they always did; grouped heads carry the
    # group beside the key head, so one key head serves its whole group.
    qk, pv = ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd") if group == 1 else \
        ("bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd")

    @jax.checkpoint
    def one_block(qi, kj, vj, seg_q, seg_k, q0, k0):
        if group > 1:
            qi = qi.reshape(qi.shape[:2] + (kv_heads, group, qi.shape[-1]))
        sc = jnp.einsum(qk, qi, kj,
                        preferred_element_type=jnp.float32) * scale
        qpos = q0 + jnp.arange(qi.shape[1])
        kpos = jnp.arange(kj.shape[1])
        if window is not None:
            kpos = k0 + kpos
        ok = (seg_q[:, :, None] == seg_k[:, None, :]) \
            & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        ok = ok[:, None] if group == 1 else ok[:, None, None]
        sc = jnp.where(ok, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(vj.dtype)
        o = jnp.einsum(pv, p, vj)
        if group > 1:
            o = o.reshape(o.shape[:2] + (heads, o.shape[-1]))
        return o

    out = []
    for q0 in range(0, s, block):
        k0, end = attention_key_start(q0, block, window), q0 + block
        out.append(one_block(q[:, q0:end], k[:, k0:end], v[:, k0:end],
                             segment[:, q0:end], segment[:, k0:end], q0, k0))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

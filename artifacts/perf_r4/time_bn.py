"""Round-4: BN formulation variants inside the vmapped training block.

Each variant swaps BatchStatsNorm.__call__ (patched only during trace/
compile; compiled executables keep their traced program), then all
variants are timed interleaved in one process, min over >=6 passes.

Run: cd /root/repo && PYTHONPATH="$PYTHONPATH:." python artifacts/perf_r4/time_bn.py
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import blades_tpu.models.layers as layers_mod
from blades_tpu.core.task import TaskSpec

G = 50
BATCH = 32
LOCAL_STEPS = 1
REP = 8
PASSES = 6

_ORIG_CALL = layers_mod.BatchStatsNorm.__call__


# ---------------------------------------------------------------------------
# Variant BN bodies: all per-lane (B, H, W, C); vmap adds the client axis.
# ---------------------------------------------------------------------------


def bn_onepass(self, x):
    """E[x^2] - E[x]^2 so both stats come from ONE pass over x."""
    features = x.shape[-1]
    scale = self.param("scale", jax.nn.initializers.ones, (features,))
    bias = self.param("bias", jax.nn.initializers.zeros, (features,))
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    mean2 = jnp.mean(x * x, axis=axes)
    var = mean2 - mean * mean
    y = (x - mean) * lax.rsqrt(var + self.epsilon)
    return y * scale + bias


def bn_f32stats(self, x):
    """Stats accumulated in f32 (bf16 activations)."""
    features = x.shape[-1]
    scale = self.param("scale", jax.nn.initializers.ones, (features,))
    bias = self.param("bias", jax.nn.initializers.zeros, (features,))
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.mean(xf * xf, axis=axes) - mean * mean
    y = (xf - mean) * lax.rsqrt(var + self.epsilon)
    return (y * scale + bias).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_cvjp(x, scale, bias, eps):
    y, _ = _bn_cvjp_fwd(x, scale, bias, eps)
    return y


def _bn_cvjp_fwd(x, scale, bias, eps):
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(x * x, axis=axes) - mean * mean
    r = lax.rsqrt(var + eps)
    xhat = (x - mean) * r
    y = xhat * scale + bias
    return y, (xhat, r, scale, n)


def _bn_cvjp_bwd(eps, res, dy):
    xhat, r, scale, n = res
    axes = tuple(range(dy.ndim - 1))
    dbias = jnp.sum(dy, axis=axes)
    dscale = jnp.sum(dy * xhat, axis=axes)
    dxhat = dy * scale
    mean_dxhat = jnp.sum(dxhat, axis=axes) / n
    mean_dxhat_xhat = dscale * scale / n
    dx = r * (dxhat - mean_dxhat - xhat * mean_dxhat_xhat)
    return dx, dscale, dbias


_bn_cvjp.defvjp(_bn_cvjp_fwd, _bn_cvjp_bwd)


def bn_customvjp(self, x):
    """Hand-written BN backward (saves xhat; standard 2-reduction bwd)."""
    features = x.shape[-1]
    scale = self.param("scale", jax.nn.initializers.ones, (features,))
    bias = self.param("bias", jax.nn.initializers.zeros, (features,))
    return _bn_cvjp(x, scale.astype(x.dtype), bias.astype(x.dtype),
                    self.epsilon)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_cvjp2(x, scale, bias, eps):
    y, _ = _bn_cvjp2_fwd(x, scale, bias, eps)
    return y


def _bn_cvjp2_fwd(x, scale, bias, eps):
    axes = tuple(range(x.ndim - 1))
    n = 1
    for a in axes:
        n *= x.shape[a]
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(x * x, axis=axes) - mean * mean
    r = lax.rsqrt(var + eps)
    y = (x - mean) * r * scale + bias
    return y, (x, mean, r, scale, n)


def _bn_cvjp2_bwd(eps, res, dy):
    """Saves x (the conv output, which XLA materializes anyway) instead
    of xhat; recomputes xhat elementwise in the backward."""
    x, mean, r, scale, n = res
    axes = tuple(range(dy.ndim - 1))
    xhat = (x - mean) * r
    dbias = jnp.sum(dy, axis=axes)
    dscale = jnp.sum(dy * xhat, axis=axes)
    dxhat = dy * scale
    dx = r * (dxhat - jnp.sum(dxhat, axis=axes) / n
              - xhat * (dscale * scale / n))
    return dx, dscale, dbias


_bn_cvjp2.defvjp(_bn_cvjp2_fwd, _bn_cvjp2_bwd)


def bn_customvjp_savex(self, x):
    features = x.shape[-1]
    scale = self.param("scale", jax.nn.initializers.ones, (features,))
    bias = self.param("bias", jax.nn.initializers.zeros, (features,))
    return _bn_cvjp2(x, scale.astype(x.dtype), bias.astype(x.dtype),
                     self.epsilon)


import flax.linen as nn  # noqa: E402

import blades_tpu.models.resnet as resnet_mod  # noqa: E402


def bn_class(body):
    """A fresh flax Module class NAMED BatchStatsNorm (so param paths are
    unchanged) whose __call__ is the variant body."""
    ns = {
        "__annotations__": {"epsilon": float, "use_scale": bool,
                            "use_bias": bool},
        "epsilon": 1e-5,
        "use_scale": True,
        "use_bias": True,
        "__call__": nn.compact(body),
        "__module__": __name__,
    }
    return type("BatchStatsNorm", (nn.Module,), ns)


VARIANTS = {
    "base": layers_mod.BatchStatsNorm,
    "onepass": bn_class(bn_onepass),
    "f32stats": bn_class(bn_f32stats),
    "customvjp": bn_class(bn_customvjp),
    "customvjp_savex": bn_class(bn_customvjp_savex),
}


def make_timed(task, params, opt, bx, by, keys, mal):
    def body(c, _):
        bxp = bx + c * 1e-30
        upd, _o, loss, _ = task.local_round_batched(params, opt, bxp, by, keys,
                                                 mal)
        return loss.sum() + upd.sum() * 1e-30, None

    @jax.jit
    def run():
        out, _ = lax.scan(body, jnp.float32(0.0), None, length=REP)
        return out

    return run


def main():
    rng = np.random.default_rng(0)
    bx = jnp.asarray(rng.normal(size=(G, LOCAL_STEPS, BATCH, 32, 32, 3)),
                     jnp.float32)
    by = jnp.asarray(rng.integers(0, 10, size=(G, LOCAL_STEPS, BATCH)),
                     jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), G)
    mal = jnp.zeros((G,), bool)

    task = TaskSpec(model="resnet10", input_shape=(32, 32, 3), num_classes=10,
                    lr=0.1, compute_dtype="bfloat16").build()
    params = task.init_params(jax.random.PRNGKey(0))
    opt = jax.vmap(lambda _: task.init_client_opt_state(params))(
        jnp.arange(G))

    names = sys.argv[1:] or list(VARIANTS)
    runs = {}
    for name in names:
        resnet_mod.BatchStatsNorm = VARIANTS[name]
        try:
            run = make_timed(task, params, opt, bx, by, keys, mal)
            t0 = time.perf_counter()
            val = float(run())  # traces+compiles under the patch
            print(f"# compile {name}: {time.perf_counter() - t0:.1f}s "
                  f"val={val:.4f}", flush=True)
            runs[name] = run
        finally:
            resnet_mod.BatchStatsNorm = layers_mod.BatchStatsNorm

    times = {v: [] for v in runs}
    for p in range(PASSES):
        for v, run in runs.items():
            t0 = time.perf_counter()
            _ = float(run())
            times[v].append((time.perf_counter() - t0) / REP)

    print(json.dumps({v: {"ms_min": round(min(ts) * 1e3, 2)}
                      for v, ts in times.items()}, indent=2))


if __name__ == "__main__":
    main()

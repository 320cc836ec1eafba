"""Round-4 training-block timing: baseline vs remat variants.

Protocol (artifacts/PERF_NOTES_r3.md): in-jit lax.scan repetition whose
body input depends on the carry (else XLA hoists the loop-invariant
body), interleaved candidates in ONE process, min over >=6 passes.

Run: cd /root/repo && PYTHONPATH="$PYTHONPATH:." python artifacts/perf_r4/time_block.py
"""

from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blades_tpu.core.task import Task, TaskSpec
from blades_tpu.models.resnet import BasicBlock, ResNet

G = 50          # clients per block (bench.py client_block)
BATCH = 32
LOCAL_STEPS = 1
REP = 8
PASSES = 6


class RematTask(Task):
    """Full remat: recompute the forward during backward (saves only
    inputs), so forward activations never round-trip HBM."""

    def loss_fn(self, params, x, y, dropout_key=None):
        f = functools.partial(Task.loss_fn, self)
        return jax.checkpoint(f)(params, x, y, dropout_key)


def make_task(variant: str) -> Task:
    spec = TaskSpec(model="resnet10", input_shape=(32, 32, 3),
                    num_classes=10, lr=0.1, compute_dtype="bfloat16")
    base = spec.build()
    if variant == "base":
        return base
    if variant == "remat_full":
        return RematTask(spec=base.spec, model=base.model)
    if variant == "remat_block":
        # Save only residual-block boundaries; recompute inside each block.
        model = ResNet(nn.remat(BasicBlock), (1, 1, 1, 1), 10)
        return Task(spec=spec, model=model)
    if variant == "remat_block_full":
        model = ResNet(nn.remat(BasicBlock), (1, 1, 1, 1), 10)
        return RematTask(spec=spec, model=model)
    raise ValueError(variant)


def make_timed(task: Task, params, opt, bx, by, keys, mal):
    """Jitted REP-iteration scan over the block; body input depends on
    the carry, carry depends on the full update tensor."""

    def body(c, _):
        bxp = bx + c * 1e-30
        upd, _opt2, loss, _ = task.local_round_batched(
            params, opt, bxp, by, keys, mal
        )
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

    variants = sys.argv[1:] or ["base", "remat_full", "remat_block"]
    runs = {}
    for v in variants:
        task = make_task(v)
        params = task.init_params(jax.random.PRNGKey(0))
        opt = jax.vmap(lambda _: task.init_client_opt_state(params))(
            jnp.arange(G)
        )
        runs[v] = make_timed(task, params, opt, bx, by, keys, mal)

    # Warmup/compile all first.
    for v, run in runs.items():
        t0 = time.perf_counter()
        val = float(run())
        print(f"# compile+first {v}: {time.perf_counter() - t0:.1f}s "
              f"val={val:.4f}", flush=True)

    times = {v: [] for v in runs}
    for p in range(PASSES):
        for v, run in runs.items():
            t0 = time.perf_counter()
            _ = float(run())
            times[v].append((time.perf_counter() - t0) / REP)

    out = {v: {"ms_min": round(min(ts) * 1e3, 2),
               "ms_all": [round(t * 1e3, 2) for t in ts]}
           for v, ts in times.items()}
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()

"""Capture a device trace of the G=50 vmapped training block and dump
per-op self times grouped by category (the r3 methodology).

Run: cd /root/repo && PYTHONPATH="$PYTHONPATH:." python artifacts/perf_r4/profile_block.py [variant] [outdir]
"""

from __future__ import annotations

import glob
import sys
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

G = 50
BATCH = 32
LOCAL_STEPS = 1
REP = 8


def build_run(variant: str):
    import blades_tpu.models.layers as layers_mod
    import blades_tpu.models.resnet as resnet_mod
    from blades_tpu.core.task import TaskSpec

    if variant != "base":
        import importlib

        tb = importlib.import_module("time_bn")
        resnet_mod.BatchStatsNorm = tb.VARIANTS[variant]

    task = TaskSpec(model="resnet10", input_shape=(32, 32, 3), num_classes=10,
                    lr=0.1, compute_dtype="bfloat16").build()
    params = task.init_params(jax.random.PRNGKey(0))
    opt = jax.vmap(lambda _: task.init_client_opt_state(params))(
        jnp.arange(G))
    rng = np.random.default_rng(0)
    bx = jnp.asarray(rng.normal(size=(G, LOCAL_STEPS, BATCH, 32, 32, 3)),
                     jnp.float32)
    by = jnp.asarray(rng.integers(0, 10, size=(G, LOCAL_STEPS, BATCH)),
                     jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), G)
    mal = jnp.zeros((G,), bool)

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


def dump_hlo_stats(logdir: str, top: int = 40):
    """Parse the xplane proto and print per-op self time."""
    from xprof.convert import raw_to_tool_data as rtd

    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    assert files, f"no xplane under {logdir}"
    data, _ = rtd.xspace_to_tool_data(files, "hlo_stats", {})
    import gzip
    import json as j

    if isinstance(data, bytes):
        try:
            data = gzip.decompress(data)
        except Exception:
            pass
        data = data.decode()
    rows = j.loads(data)
    return rows


def main():
    variant = sys.argv[1] if len(sys.argv) > 1 else "base"
    logdir = sys.argv[2] if len(sys.argv) > 2 else f"/tmp/prof_{variant}"
    run = build_run(variant)
    print(f"# compiling {variant}...", flush=True)
    float(run())
    with jax.profiler.trace(logdir):
        v = float(run())
    print(f"# traced val={v:.4f}", flush=True)
    time.sleep(1)
    rows = dump_hlo_stats(logdir)
    cols = [c["id"] for c in rows["cols"]]
    recs = []
    for r in rows["rows"]:
        rec = dict(zip(cols, [c.get("v") for c in r["c"]]))
        recs.append(rec)
    by_cat = defaultdict(float)
    for r in recs:
        by_cat[r["category"]] += r["total_self_time"] or 0.0
    print("== per-category self time (ms per block iter, REP=%d) ==" % REP)
    for cat, us in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print(f"  {cat:40s} {us / 1e3 / REP:8.2f} ms")
    print(f"  {'TOTAL':40s} {sum(by_cat.values()) / 1e3 / REP:8.2f} ms")
    print("== top 30 ops ==")
    for r in sorted(recs, key=lambda r: -(r["total_self_time"] or 0))[:30]:
        expr = (r["hlo_op_expression"] or "")[:110].replace("\n", " ")
        print(f"  {(r['total_self_time'] or 0) / 1e3 / REP:7.3f} ms "
              f"x{int(r['occurrences'] or 0):4d} [{r['category']}] "
              f"{r['bound_by']} dma%={r['dma_stall_percent']}: {expr}")


if __name__ == "__main__":
    main()

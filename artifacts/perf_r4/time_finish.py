"""Round-4: fused finish kernel timing at bench scale (n=1000, d=4.9M).

Variants: median vs mean (radix cost), alie forge on/off, sanitize
on/off.  Protocol: in-jit scan with carry-dependent input (the carry
perturbs the malicious mask's float weights? no — perturb via updates),
interleaved, min over >=6 passes.

NOTE: the real matrix is bf16 and huge (9.8 GB); we can't scan-carry it
(double-buffer OOM).  Instead each timed call runs the kernel REP times
with the INPUT build outside: body depends on carry via a scalar added
to the forge_noise/updates? Adding to updates copies 9.8GB.  Trick: the
kernel's output feeds the carry, and the carry perturbs the *malicious
weights* wb through a (n,1)-sized input — but fused_finish takes a bool
mask.  So instead: time via host loop over independent dispatches of the
SAME compiled fn but fetch a value each iteration (forces completion;
per-dispatch overhead is small against 20-90ms kernels), min over many
iters, interleaved.

Run: cd /root/repo && PYTHONPATH="$PYTHONPATH:." python artifacts/perf_r4/time_finish.py
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from blades_tpu.ops.pallas_round import fused_finish

N = 1000
D = 4_903_242
PASSES = 8


def main():
    from blades_tpu.ops.pallas_select import _BLOCK_D

    d_alloc = -(-D // _BLOCK_D) * _BLOCK_D
    # Zeros: a random matrix would need 2x HBM to draw (f32 intermediate)
    # and the kernel's cost is data-independent (fixed radix step count).
    updates = jnp.zeros((N, d_alloc), jnp.bfloat16)
    mal = jnp.arange(N) < N // 4

    cfgs = {
        "median_alie_san": dict(forge=("alie", 1.5), agg=("median",),
                                sanitize=True),
        "median_noforge_nosan": dict(forge=None, agg=("median",),
                                     sanitize=False),
        "mean_alie_san": dict(forge=("alie", 1.5), agg=("mean",),
                              sanitize=True),
        "mean_noforge_nosan": dict(forge=None, agg=("mean",),
                                   sanitize=False),
        "trimmed_alie_san": dict(forge=("alie", 1.5), agg=("trimmed", 250),
                                 sanitize=True),
    }
    names = sys.argv[1:] or list(cfgs)

    REP = 6
    fns = {}
    for name in names:
        kw = cfgs[name]

        def f(u, m, kw=kw):
            # In-jit repetition; the mask depends on the carry through
            # c != c (False, but XLA can't prove it for a float carry),
            # so the kernel re-runs every iteration while the giant
            # matrix stays a read-only loop invariant (no carry copy).
            def body(c, _):
                m2 = m ^ (c != c)
                a, sq, bad = fused_finish(u, m2, None, **kw)
                return a[0] + sq[0], None

            out, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=REP)
            return out

        jf = jax.jit(f)
        t0 = time.perf_counter()
        v = float(jf(updates, mal))
        print(f"# compile {name}: {time.perf_counter() - t0:.1f}s v={v:.4f}",
              flush=True)
        fns[name] = jf

    times = {v: [] for v in fns}
    for p in range(PASSES):
        for name, jf in fns.items():
            t0 = time.perf_counter()
            _ = float(jf(updates, mal))
            times[name].append((time.perf_counter() - t0) / REP)

    print(json.dumps({v: {"ms_min": round(min(ts) * 1e3, 1),
                          "ms_med": round(sorted(ts)[len(ts) // 2] * 1e3, 1)}
                      for v, ts in times.items()}, indent=2))


if __name__ == "__main__":
    main()

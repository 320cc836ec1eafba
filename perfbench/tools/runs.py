#!/usr/bin/env python3
"""Run a list of benchmark runs one after another, each a process of its own
(this parent never touches JAX, so the chip is free for each child), and
keep each run's result line and the end of its standard error.

    python3 perfbench/tools/runs.py --out chiprun_out/sets.jsonl \
        [--cache-dir .jax_cache_setA] [--extra "--rehearse"] \
        workload:seed:seconds:trace [...]

``--cache-dir`` points ``JAX_COMPILATION_CACHE_DIR`` at a directory inside
the checkout: a new name starts a set cold, as the driver's sets start.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--cache-dir")
    ap.add_argument("--extra", default="")
    ap.add_argument("--label", default="")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    env = dict(os.environ)
    if args.cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                        args.cache_dir)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bad = 0
    for spec in args.runs:
        workload, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
               "--workload", workload, "--seed", seed, "--seconds", seconds,
               "--trace", trace] + args.extra.split()
        t = time.time()
        p = subprocess.run(cmd, cwd=CHECKOUT, env=env, capture_output=True,
                           text=True)
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except ValueError:
            result = None
        rec = {"label": args.label, "spec": spec, "rc": p.returncode,
               "wall_s": time.time() - t, "cache_dir": args.cache_dir,
               "result": result, "stderr_tail": p.stderr[-6000:]}
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        ok = p.returncode == 0 and result and result.get("correct")
        bad += not ok
        short = {k: v["value"] for k, v in
                 (result or {}).get("metrics", {}).items()}
        print(spec, "rc", p.returncode, "correct",
              (result or {}).get("correct"), json.dumps(short), flush=True)
        if not ok:
            print(p.stderr[-3000:], flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

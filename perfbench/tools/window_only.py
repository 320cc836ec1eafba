#!/usr/bin/env python3
"""A cell's set-up and timed window WITHOUT the reference and the
comparison after it (at d in the hundreds of millions those are over half a
run's wall time): what ``rounds_per_s`` reads on many seeds, and each
round's time beside the row counters that say how much work it held.  No
``correct``: a measurement of spread, never a result line.

    python3 perfbench/tools/window_only.py --workload mellum2_n10_median \
        --seconds 30 --out chiprun_out/window_only.jsonl SEED [...]

Each seed runs in a process of its own (this parent never touches JAX).
"""

import argparse
import gc
import json
import os
import subprocess
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(PERFBENCH)
COUNTERS = ("expert_pairs_here", "expert_rows_computed", "routed_here_share",
            "expert_tokens_max", "zero_expert_blocks")


def one(workload: str, seed: int, seconds: float, rehearse: bool) -> dict:
    for p in (PERFBENCH, CHECKOUT):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(CHECKOUT, ".no_data")
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from pb import cell as C
    from pb import sut, window
    from pb.manifest import Manifest

    if not rehearse:
        sut.place_compile_cache(CHECKOUT)
    cell = C.Cell(Manifest(CHECKOUT), workload, seed, rehearse)
    C.warm_up(cell, cell.traffic["warmup_rounds"], 0)
    gc.collect()
    gc.freeze()     # as run_cell does before its window
    setup_s = time.perf_counter() - T_PROCESS
    n_warm = len(cell.rows)
    win = C.run_window(cell, seconds)
    rows = cell.rows[n_warm:]
    return {"workload": workload, "seed": seed, "setup_s": setup_s,
            "rounds_per_s": window.rounds_per_s(win["t0"], win["ends"]),
            "round_s": [e - s for s, e in zip(win["starts"], win["ends"])],
            "failed": sum(sut.round_failed(r) for r in rows),
            "memory_peak_bytes": C.memory_peak_bytes(),
            "counters": {k: [float(r[k]) for r in rows] for k in COUNTERS
                         if rows and k in rows[0]}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="the traffic's tiny sizes on whatever JAX finds: "
                         "for finding faults here, no measurement")
    ap.add_argument("seeds", nargs="+", type=int)
    args = ap.parse_args()
    if args.child:
        print(json.dumps(one(args.workload, args.seeds[0], args.seconds,
                             args.rehearse)),
              flush=True)
        return 0
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bad = 0
    for seed in args.seeds:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seconds", str(args.seconds), "--out",
             args.out, "--child", str(seed)]
            + ["--rehearse"] * args.rehearse,
            cwd=CHECKOUT, capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        try:
            rec = json.loads(lines[-1])
        except (IndexError, ValueError):
            rec = {"seed": seed, "rc": p.returncode,
                   "stderr_tail": p.stderr[-3000:]}
            bad += 1
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps({k: rec.get(k) for k in
                          ("seed", "rounds_per_s", "setup_s", "rc")}),
              flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

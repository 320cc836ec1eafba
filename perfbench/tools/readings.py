#!/usr/bin/env python3
"""Read, on the chip at a cell's own size, what each limit is set from.

For each seed, in this one process: the program's first rounds through
``Fedavg.train()`` (the lower readings), the reference, then the reference
put in the program's place and computed in fp8 (the control: the upper
readings) and with each known fault planted (half of every batch left out;
half of the benign clients left out, their rows repeated).  Every number is
``compare.numbers(side, reference)``.  A state left unchanged reads 1 on the
leaf numbers by their definition and needs no run.

    python3 perfbench/tools/readings.py --workload r10_median \
        --out chiprun_out/readings.jsonl --sides control,half_batch SEED ...
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(PERFBENCH)
for p in (PERFBENCH, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--sides", default="control,half_batch,half_clients")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("seeds", nargs="+", type=int)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(CHECKOUT, ".no_data")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from pb import cell as C
    from pb import compare, sut
    from pb.manifest import Manifest

    device = C.device_facts()
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"readings: needs a TPU, JAX found {device}", file=sys.stderr)
        return 2
    if not args.rehearse:
        sut.place_compile_cache(CHECKOUT)
    manifest = Manifest(CHECKOUT)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in args.seeds:
        cell = C.Cell(manifest, args.workload, seed, args.rehearse)
        rounds = cell.compared_rounds
        t = time.perf_counter()
        prog = C.warm_up(cell, rounds, rounds)
        t_prog = time.perf_counter() - t
        cell.free()

        def follow(**kw):
            t = time.perf_counter()
            out = cell.follow(**kw)
            return out, time.perf_counter() - t

        ref, t_ref = follow()
        rec = {"workload": args.workload, "seed": seed, "device": device,
               "program_s": t_prog, "reference_s": t_ref,
               "program": compare.numbers(prog, ref)}
        for side in filter(None, args.sides.split(",")):
            kw = {"quant": "fp8"} if side == "control" else {"fault": side}
            out, secs = follow(**kw)
            rec[side] = compare.numbers(out, ref)
            rec[side + "_s"] = secs
            del out
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps({k: ({n: v for n, v in val.items()
                               if not n.startswith("_")}
                              if isinstance(val, dict) else val)
                          for k, val in rec.items()}), flush=True)
        del cell, prog, ref
    return 0


if __name__ == "__main__":
    sys.exit(main())

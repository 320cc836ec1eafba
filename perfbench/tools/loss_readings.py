#!/usr/bin/env python3
"""Read, on the chip at a cell's own size, what its limits are set from,
with a control that swaps the family's loss.

For each seed, in this one process: the program's first rounds through
``Fedavg.train()`` (the lower readings, with the rows' counters), the
reference, then the reference put in the program's place computed another
way.  A side is ``control`` (every contraction in fp8, as ``readings.py``
reads it), the name of another loss the family defines
(``last_step_loss_fn`` of ``families/looped_lm.py``: the model with its
mechanism left out), or one of the harness's planted faults
(``half_batch``, ``half_clients``, as ``readings.py`` plants them).  Every
number is ``compare.numbers(side, reference)``, and every side is decided
under the cell's limits (``<side>_correct``: a control must read false).

    python3 perfbench/tools/loss_readings.py --workload ouro_n10_median \
        --out perfbench_out/loss_readings.jsonl \
        --sides control,last_step_loss_fn,half_clients SEED ...
"""

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(PERFBENCH)
for p in (PERFBENCH, CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)

FAULTS = ("half_batch", "half_clients")
# Row counters kept from the program's rounds (obs/schema.py).
COUNTERS = ("loop_steps", "exit_last_step_mass", "attn_scores_computed",
            "attn_fused_calls", "tokens_trained")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--sides", default="control")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("seeds", nargs="+", type=int)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(CHECKOUT, ".no_data")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    from pb import cell as C
    from pb import compare, sut
    from pb.manifest import Manifest

    device = C.device_facts()
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"loss_readings: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 2
    if not args.rehearse:
        sut.place_compile_cache(CHECKOUT)
    manifest = Manifest(CHECKOUT)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sides = [s for s in args.sides.split(",") if s]
    for seed in args.seeds:
        cell = C.Cell(manifest, args.workload, seed, args.rehearse)
        rounds = cell.compared_rounds
        t = time.perf_counter()
        prog = C.warm_up(cell, rounds, rounds)
        t_prog = time.perf_counter() - t
        rows = [{k: float(r[k]) for k in COUNTERS if k in r}
                for r in cell.rows]
        cell.free()
        stats = jax.local_devices()[0].memory_stats() or {}
        t = time.perf_counter()
        ref = cell.follow()
        nums = compare.numbers(prog, ref)
        rec = {"workload": args.workload, "seed": seed, "device": device,
               "program_s": t_prog, "reference_s": time.perf_counter() - t,
               "bytes_in_use_after_free": stats.get("bytes_in_use"),
               "rows": rows, "program": nums,
               "program_correct": compare.decide(nums, cell.limits)[0]}
        family = cell.family
        for side in sides:
            t = time.perf_counter()
            if side == "control":
                out = cell.follow(quant="fp8")
            elif side in FAULTS:
                out = cell.follow(fault=side)
            else:
                cell.family = types.SimpleNamespace(
                    init_params=family.init_params,
                    num_params=family.num_params,
                    loss_fn=getattr(family, side))
                try:
                    out = cell.follow()
                finally:
                    cell.family = family
            rec[side] = compare.numbers(out, ref)
            rec[side + "_correct"] = compare.decide(rec[side],
                                                    cell.limits)[0]
            rec[side + "_s"] = time.perf_counter() - t
            del out
            # after each side too: a call cut at its time limit keeps it
            with open(args.out, "a") as f:
                f.write(json.dumps(dict(rec, partial=True)) + "\n")
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

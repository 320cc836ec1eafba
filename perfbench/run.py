#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the chips the cell
asks for.  Builds the cell's federation through the program's own entry
points on data and weights made from ``--seed``, drives its first rounds and
then a window of ``--seconds`` through ``Fedavg.train()``, one call a round,
then follows the first rounds with the plain reference and compares.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number compared beside its limit.
The same numbers are the last lines of standard error.  Every run leaves the
time of each of its rounds in ``perfbench_out/rounds/``.

Without a TPU holding the chips the cell asks for it prints no result and
exits 2.  ``--rehearse`` (a flag of this harness, not of the program) runs
the same control flow at a tiny federation on whatever JAX finds, for
finding faults without the chip; its line names the device it ran on and is
no measurement.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(CHECKOUT, "blades_tpu")):
        print("perfbench: no program in this checkout (blades_tpu/ is "
              "missing): nothing to measure", file=sys.stderr)
        return 2
    for p in (HERE, CHECKOUT):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Hermetic data: the program's loaders never look at a home directory.
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(CHECKOUT, ".no_data")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from pb.cell import run_cell

    code, result = run_cell(CHECKOUT, args.workload, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS,
                            rehearse=args.rehearse)
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Summarise the sets ``runs.py`` wrote: for each workload and label the
median and the spread (distance between the first and third quartile over
the median, ``statistics.quantiles(n=4)``) of every metric, the first run's
set-up apart, and the largest value of every number compared.

    python3 perfbench/tools/spread.py chiprun_out/sets.jsonl [...]
"""

import collections
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from pb.window import iqr_share  # noqa: E402


def main() -> int:
    sets = collections.OrderedDict()
    for path in sys.argv[1:]:
        for line in open(path):
            r = json.loads(line)
            w, _, _, trace = r["spec"].split(":")
            sets.setdefault((w, r["label"], trace), []).append(r)
    for (w, label, trace), runs in sets.items():
        ok = [r for r in runs if r["result"]]
        print(f"== {w} set {label} trace {trace}: {len(runs)} runs, "
              f"{sum(bool(r['result'] and r['result']['correct']) for r in runs)} correct")
        names = sorted({n for r in ok for n in r["result"]["metrics"]})
        for n in names:
            vals = [r["result"]["metrics"][n]["value"] for r in ok
                    if n in r["result"]["metrics"]]
            if n == "setup_s":
                print(f"   setup_s first {vals[0]:.3f}")
                vals = vals[1:]
            if len(vals) >= 2 and statistics.median(vals):
                print(f"   {n}: median {statistics.median(vals):.6g} "
                      f"spread {iqr_share(vals):.5f} min {min(vals):.6g} "
                      f"max {max(vals):.6g} n {len(vals)}")
            elif vals:
                print(f"   {n}: {vals[0]:.6g}")
        comp = collections.defaultdict(list)
        for r in ok:
            for n, c in r["result"].get("compared", {}).items():
                if c["value"] is not None:
                    comp[n].append(c["value"])
        for n, vals in comp.items():
            print(f"   compared {n}: max {max(vals):.3e} "
                  f"median {statistics.median(vals):.3e} n {len(vals)}")
        peaks = [r["result"]["device"]["memory_peak_bytes"] for r in ok]
        if peaks:
            print(f"   memory_peak_bytes max {max(peaks)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Run the four mesh round paths on four real chips through
``Fedavg.train()`` and check where the work landed.

Each path takes the single-chip giant-federation YAML's Median arm
(ResNet-10 at full width, 32x32x3, ALIE on a quarter of the clients,
bf16 compute) with ``num_devices: 4`` and its own ``execution``:

- ``flat``      the GSPMD round (``parallel/sharded.py``)
- ``dsharded``  the width-sharded all-to-all round (``parallel/dsharded.py``)
- ``hier``      per-chip pre-aggregation on a ``2x2`` mesh (``parallel/hier.py``)
- ``gossip``    the peer-graph round (``topology/gossip.py``)

For every path a descending ladder of client counts is tried until one
fits; each attempt is a few rounds plus a placement check (client stack
on four distinct devices, per-device ``bytes_in_use`` roughly equal —
everything on device 0 is the expected failure).  One JSON line per
attempt goes to stdout and to ``chiprun_out/chip_mesh.jsonl`` as it
happens, so a run cut short still leaves its findings.

    chiprun --chips 4 -- python tools/chip_mesh.py [path:n,n,... ...]
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

import chip_smoke  # noqa: E402

N_DEV = 4
ROUNDS = 3
PATHS = {
    "flat": {"execution": "dense"},
    "dsharded": {"execution": "dsharded"},
    "hier": {"execution": "hier", "mesh_shape": [2, 2]},
    "gossip": {"execution": "gossip",
               "topology_config": {"graph": "kregular", "k": 4}},
}
# First guesses from arithmetic (about 130 MB of bf16 activations per
# trained ResNet-10 client at batch 32, 19.6 MB per f32 update row).
LADDERS = {
    "flat": (256, 128, 64),
    "dsharded": (384, 256, 128),
    "hier": (128, 64, 32),
    "gossip": (128, 64, 32),
}


def attempt(path: str, n: int, compiles) -> dict:
    rec = {"path": path, "clients": n, "malicious": n // 4, "ok": False}
    algo = None
    t0 = time.perf_counter()
    try:
        algo = chip_smoke.build_trial(
            chip_smoke.YAML, "Median",
            {"num_devices": N_DEV, "num_clients": n,
             "num_malicious_clients": n // 4, **PATHS[path]})
        rec.update(chip_smoke.train_rounds(algo, ROUNDS, compiles))
        rec.update(chip_smoke.placement(algo))
        rec["peak_bytes_in_use"] = [
            d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
        rec["ok"] = True
    except Exception as e:  # an OOM is this script's finding
        rec["error"] = f"{type(e).__name__}: {e}"[:1200]
    finally:
        if algo is not None:
            algo.stop()
        del algo
        gc.collect()
        jax.clear_caches()
    rec["seconds"] = round(time.perf_counter() - t0, 1)
    return rec


def main(argv) -> int:
    dev = jax.devices()
    if dev[0].platform != "tpu" or len(dev) < N_DEV:
        print(f"chip_mesh: needs {N_DEV} TPU chips, JAX found {len(dev)} x "
              f"{dev[0].platform}", file=sys.stderr)
        return 2
    os.environ["BLADES_TPU_DATA_ROOT"] = os.path.join(ROOT, ".no_data")
    ladders = dict(LADDERS)
    if argv:
        ladders = {}
        for arg in argv:
            path, _, ns = arg.partition(":")
            ladders[path] = (tuple(int(v) for v in ns.split(","))
                             if ns else LADDERS[path])
    compiles = chip_smoke.CompileLog()
    os.makedirs("chiprun_out", exist_ok=True)
    held = {}
    with open(os.path.join("chiprun_out", "chip_mesh.jsonl"), "a") as log:
        for path, ladder in ladders.items():
            for n in ladder:
                rec = attempt(path, n, compiles)
                line = json.dumps(rec)
                print(line, flush=True)
                log.write(line + "\n")
                log.flush()
                if rec["ok"]:
                    held[path] = n
                    break
    print(json.dumps({"device_kind": dev[0].device_kind, "count": len(dev),
                      "largest_n_held": held}), flush=True)
    return 0 if len(held) == len(ladders) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""CCT (transformer backbone) TPU throughput evidence (VERDICT r4 #8).

The CCT/CVT zoo + pretrained import exist with unit tests, but through
round 4 no perf or curve artifact exercised the attention path on the
TPU.  This measures the same FL-round workload shape as bench.py —
FedAvg + ALIE + exact Median through the streamed single-chip round —
on the catalog CCT (cct_2_3x2_32: 2 encoder blocks, 2 heads, SeqPool;
``global_model: cct`` in tuned_examples/fedavg_cct_cifar10.yaml) at two
scales, and writes ``results.json`` next to this file.

Run on the TPU:  python artifacts/cct_bench/measure.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np

BATCH = 32
LOCAL_STEPS = 1


def bench_cct(num_clients: int, client_block: int, timed_rounds: int = 5,
              model: str = "cct") -> dict:
    import jax
    import jax.numpy as jnp

    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.parallel.streamed import streamed_step

    f = num_clients // 4
    task = TaskSpec(model=model, input_shape=(32, 32, 3), num_classes=10,
                    lr=0.1, compute_dtype="bfloat16").build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=BATCH,
                  num_batches_per_round=LOCAL_STEPS)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, BATCH, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, BATCH)), jnp.int32)
    ln = jnp.full((num_clients,), BATCH, jnp.int32)
    mal = make_malicious_mask(num_clients, f)

    state = fr.init(jax.random.PRNGKey(0), num_clients)
    d = sum(p.size for p in jax.tree.leaves(state.server.params))
    step = streamed_step(fr, client_block=client_block, d_chunk=1 << 17,
                         malicious_prefix=f)

    state, m = step(state, x, y, ln, mal, jax.random.PRNGKey(1))
    _ = float(m["train_loss"])  # concrete fetch inside the timed region

    t0 = time.perf_counter()
    for r in range(timed_rounds):
        state, m = step(state, x, y, ln, mal,
                        jax.random.fold_in(jax.random.PRNGKey(2), r))
    final = float(m["train_loss"])
    assert final == final
    dt = time.perf_counter() - t0
    return {
        "model": model, "clients": num_clients, "byzantine": f,
        "params": d, "client_block": client_block,
        "rounds_per_sec": round(timed_rounds / dt, 3),
        "train_loss_final": round(final, 4),
    }


def main():
    out = []
    # The tuned-example scale (n=60) and a giant-federation scale.
    for n, cb in ((60, 30), (1000, 50)):
        out.append(bench_cct(n, cb))
        print(json.dumps(out[-1]), flush=True)
        (Path(__file__).parent / "results.json").write_text(
            json.dumps(out, indent=2))


if __name__ == "__main__":
    main()

"""Worker process for the 2-process jax.distributed smoke test.

Usage: python multihost_worker.py <coordinator_addr> <num_procs> <proc_id>

Each process brings 4 virtual CPU devices; the global mesh spans all 8
across both processes — the TPU-native analogue of the reference's NCCL
``init_process_group`` bring-up (ref: fllib/communication/
communicator.py:119-184), with the client->server gradient push riding
the same distributed runtime the collectives use.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_platforms", "cpu")

from blades_tpu.parallel import init_distributed  # noqa: E402


def main(coord: str, num_procs: int, proc_id: int) -> None:
    init_distributed(coordinator_address=coord, num_processes=num_procs,
                     process_id=proc_id)
    assert jax.process_count() == num_procs, jax.process_count()
    assert jax.device_count() == 4 * num_procs, jax.device_count()

    import jax.numpy as jnp
    import numpy as np

    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.parallel import make_mesh, shard_map_step
    from blades_tpu.parallel.mesh import client_axis_sharding, replicated_sharding

    N = 16
    task = TaskSpec(model="mlp", lr=0.1, input_shape=(8, 8, 1)).build()
    server = Server.from_config(aggregator="Median", lr=1.0)
    adv = get_adversary("ALIE", num_clients=N, num_byzantine=4)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_clients=N)
    mesh = make_mesh()  # all 8 GLOBAL devices, both processes

    rng = np.random.default_rng(0)  # same host data on every process
    x = rng.normal(size=(N, 8, 8, 8, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(N, 8)).astype(np.int32)
    ln = np.full((N,), 8, np.int32)
    mal = np.asarray(make_malicious_mask(N, 4))

    cs = client_axis_sharding(mesh)
    rep = replicated_sharding(mesh)
    put = lambda a, s: jax.make_array_from_callback(  # noqa: E731
        a.shape, s, lambda idx: a[idx]
    )
    from blades_tpu.core.round import RoundState

    state = fr.init(jax.random.PRNGKey(0), N)
    state = RoundState(
        server=jax.tree.map(lambda a: put(np.asarray(a), rep), state.server),
        client_opt=jax.tree.map(lambda a: put(np.asarray(a), cs),
                                state.client_opt),
    )
    xs, ys, lns, mals = (put(a, cs) for a in (x, y, ln, mal))

    step = shard_map_step(fr, mesh)
    losses = []
    for r in range(3):
        state, m = step(state, xs, ys, lns, mals,
                        jax.random.fold_in(jax.random.PRNGKey(1), r))
        losses.append(float(m["train_loss"]))
    assert all(np.isfinite(losses)), losses
    print(f"proc {proc_id}: multihost round OK losses={losses}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))

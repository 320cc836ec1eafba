#!/usr/bin/env python3
"""Compile-only rehearsal of the four-chip cell that is not built yet:
ResNet-18 x 1000 clients, ALIE + Median, ``execution: dsharded`` on a
described (not attached) ``v5e:2x2``.  Nothing runs; the TPU compiler either
refuses the program (out of memory) or says what each device would hold.

    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_dsharded.py [model] [n]

A compile that passes is not a chip run.  The kernel gates ask
``jax.default_backend()`` and see the CPU here, so the finish compiles to its
``jnp`` path: the figure this is after is the training ``vmap``'s memory.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, CHECKOUT)


def main() -> int:
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet18"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
    f = n // 4
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from blades_tpu.algorithms import get_algorithm_class
    from blades_tpu.parallel.dsharded import _build_dsharded_body
    from blades_tpu.parallel.mesh import CLIENTS_AXIS
    from blades_tpu.tune import expand_grid, load_experiments_from_file

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices), (CLIENTS_AXIS,))
    (spec,) = load_experiments_from_file(os.path.join(
        CHECKOUT, "blades_tpu", "tuned_examples",
        "fedavg_cifar10_1000clients.yaml")).values()
    (trial,) = [t for t in expand_grid(spec["config"])
                if t["server_config"]["aggregator"]["type"] == "Median"]
    _, config = get_algorithm_class(spec["run"], return_config=True)
    config.update_from_dict(trial)
    config.update_from_dict({"global_model": model, "num_clients": n,
                             "num_malicious_clients": f,
                             "execution": "dsharded", "num_devices": 4})
    config.validate()
    fr = config.get_fed_round()
    body = _build_dsharded_body(fr, mesh, malicious_prefix=f)

    rep, by_client = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                             P(CLIENTS_AXIS))
    state = jax.eval_shape(lambda k: fr.init(k, n), jax.random.PRNGKey(0))

    def place(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    state = type(state)(server=place(state.server, rep),
                        client_opt=place(state.client_opt, by_client))
    cap = 192
    args = (state,
            jax.ShapeDtypeStruct((n, cap, 32, 32, 3), jnp.bfloat16,
                                 sharding=by_client),
            jax.ShapeDtypeStruct((n, cap), jnp.int32, sharding=by_client),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=by_client),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=by_client),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
    out = {"model": model, "num_clients": n, "topology": "v5e:2x2",
           "trained_lanes_per_chip": (n - f) // 4}
    t = time.time()
    try:
        lowered = jax.jit(body).lower(*args)
        out["lower_s"] = time.time() - t
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        out.update(compiled=True, compile_s=time.time() - t, per_device={
            k: int(getattr(m, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes") if hasattr(m, k)})
        text = compiled.as_text()
        out["collectives"] = {k: text.count(k) for k in (
            "all-to-all", "all-gather", "all-reduce", "tpu_custom_call")}
    except Exception as e:  # the compiler's refusal is the finding
        msg = f"{type(e).__name__}: {e}"
        out.update(compiled=False, seconds=time.time() - t,
                   error=msg[:1500], largest=msg[1500:4000])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Compile-only check of the language-model round's two big programs: the
TPU compiler builds ``_train_block`` and ``_finish_fused_compact``
(parallel/streamed.py) for device 0 of a described (not attached)
``v5e:2x2`` at a language-model YAML's shapes
(``tuned_examples/fedavg_lm_crosssilo.yaml``: 10 clients, 2 elided,
``client_block`` 1, rows of 4096 tokens; ``fedavg_codelm_crosssilo``: rows
of 8192, the grouped product over the routed pairs) and prints each
one's ``memory_analysis()``: arguments + outputs - aliased + temporaries is
what the program needs of the chip's 15.75 GB.  The matrix is built by the
round's own rule (``parallel/streamed.py::compact_matrix``: a row a plane
here, a block of one lane lying under a storage tile); each line also
carries the layouts the compiler gave it and the block's stores into it
(``is_index_aligned``, in place or not).

    JAX_PLATFORMS=cpu python3 tools/aot_lm_round.py [yaml-stem] [key=json ...]

``yaml-stem`` names the file under ``tuned_examples/`` (default
``fedavg_lm_crosssilo``).  ``key=json`` pairs override the YAML's
``global_model`` (e.g. ``num_nextn_predict_layers=1``: the MTP module at
the chip's size, which PR 29 decided by these numbers), ``input_shape=``
the row's length (PR 33 decided 8192 by them).  About two minutes a block.
Nothing runs on a device: a compile that passes is not a chip run
(tools/aot_train_block.py is the image cells' twin).
"""

import json
import os
import re
import sys
import time
from unittest import mock

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, CHECKOUT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from aot_train_block import store_ops
    from blades_tpu.algorithms import get_algorithm_class
    from blades_tpu.parallel.streamed import (
        block_plan,
        compact_matrix,
        streamed_step,
    )
    from blades_tpu.tune import expand_grid, load_experiments_from_file

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    args = sys.argv[1:]
    stem = args.pop(0) if args and "=" not in args[0] \
        else "fedavg_lm_crosssilo"
    (spec,) = load_experiments_from_file(os.path.join(
        CHECKOUT, "blades_tpu", "tuned_examples", stem + ".yaml")).values()
    (trial,) = [t for t in expand_grid(spec["config"])
                if t["server_config"]["aggregator"]["type"] == "Median"]
    _, config = get_algorithm_class(spec["run"], return_config=True)
    config.update_from_dict(trial)
    model = dict(config.global_model)
    for pair in args:
        key, value = pair.split("=", 1)
        if key == "input_shape":
            config.update_from_dict({key: json.loads(value)})
        else:
            model[key] = json.loads(value)
    config.update_from_dict({"global_model": model})
    config.validate()
    n, f = config.num_clients, config.num_malicious_clients
    block = config.client_block
    fr = config.get_fed_round()
    dtype = getattr(jnp, str(config.update_dtype))
    step = streamed_step(fr, client_block=block, d_chunk=config.d_chunk,
                         update_dtype=dtype, malicious_prefix=f)
    # The cell's compact geometry, which the round itself takes only on a
    # TPU backend (the kernel gate sees the CPU here).
    plan = block_plan(n, f, block, dtype, compact=True)
    state = jax.eval_shape(lambda k: fr.init(k, n), jax.random.PRNGKey(0))
    d = sum(p.size for p in jax.tree.leaves(state.server.params))
    # The matrix by the rule the round builds it by: a row a plane here,
    # where a block of one lane lies under a storage tile.
    matrix, finish_cols = compact_matrix(plan, n - f, d)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=chip)

    def report(name, lowered):
        t = time.time()
        out = {"program": name}
        try:
            compiled = lowered.compile()
            m = compiled.memory_analysis()
            # The matrix's layout as the compiler chose it, and how the
            # block stores into it.
            hlo = compiled.as_text()
            out["matrix_layouts"] = sorted(set(re.findall(
                r"\w+\[%s\](\{[^}]*\})" % ",".join(map(str, matrix)), hlo)))
            out["stores"] = store_ops(hlo, matrix[0])
            out.update({k: int(getattr(m, k)) for k in KEYS
                        if hasattr(m, k)})
            out["needs_bytes"] = (
                out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                - out["alias_size_in_bytes"] + out["temp_size_in_bytes"])
        except Exception as e:   # the compiler's own words
            out["refused"] = str(e)[:2000]
        out["compile_s"] = time.time() - t
        print(json.dumps(out), flush=True)

    seq, cap = tuple(config.input_shape)[0], 16
    print(json.dumps({"model": model, "num_params": d, "plan": plan._asdict(),
                      "matrix": list(matrix),
                      "finish_stripe_cols": finish_cols,
                      "topology": "v5e:2x2"}),
          flush=True)
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        report("_train_block", step.train_block.lower(
            shape(matrix, dtype), on_chip(state.client_opt),
            on_chip(state.server.params), shape((n, cap, seq), jnp.int32),
            shape((n, cap, seq), jnp.int32), shape((n,), jnp.int32),
            shape((n,), jnp.bool_), shape((n, 2), jnp.uint32),
            shape((n, 2), jnp.uint32), shape((), jnp.uint32), plan=plan))
        report("_finish_fused_compact", step.finish_fused_compact.lower(
            on_chip(state.server), shape(matrix, dtype),
            shape((n,), jnp.bool_), shape((n,), jnp.float32),
            shape((2,), jnp.uint32), nb_real=n - f))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Compile-only check of the streamed round's training block: the TPU
compiler builds ``_train_block`` (parallel/streamed.py) for device 0 of a
described (not attached) ``v5e:2x2`` at a benchmark cell's shapes, and
this prints what the optimized program says of the matrix store: whether
it is the aliased tile copy (ops/pallas_store.py) or a
``dynamic-update-slice`` and, for that, the ``index_known_bits`` XLA
proved of its row offset; the count of rematerialised instructions; and
``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 tools/aot_train_block.py [model] [n] [client_block]

``resnet10 1000 25`` and ``resnet18 768 24`` are the two cells: the first
has a padded last block (47 blocks of 16, 2 surplus lanes), the second
none, and each is ONE program.  Nothing runs on a device and no cell runs
this: a compile that passes is not a chip run
(perfbench/tools/compile_dsharded.py is the mesh twin).
"""

import json
import os
import re
import sys
import time
from unittest import mock

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def store_ops(hlo: str, rows: int) -> list[dict]:
    """The dynamic-update-slices into a ``rows``-high matrix, of two
    dimensions or of row planes: its layout, what XLA proved of their
    (row, column[, lane]) offsets, and whether they alias their operand
    (in place): the operation's own word, or that of the fusion whose
    root it is."""
    found, inside = [], None
    for line in hlo.splitlines():
        head = re.match(r"%(\S+) \(.*\) -> .* \{$", line)
        inside = head.group(1) if head else inside
        m = re.search(r"%(\S+) = \w+\[(\d+(?:,\d+)+)\](\S*) "
                      r"dynamic-update-slice\(", line)
        shape = [int(v) for v in m.group(2).split(",")] if m else [0]
        if shape[0] != rows:
            continue
        cfg = json.loads(line[line.index("backend_config=") + 15:])
        idx = cfg.get("indices_config", {})
        aliasing = cfg.get("aliasing_operands", {}).get("lists")
        if aliasing is None and inside:
            fusion = re.search(
                r"fusion\(.*calls=%%%s[,)].*backend_config=(\{.*\})$"
                % re.escape(inside), hlo, re.M)
            if fusion:
                aliasing = json.loads(fusion.group(1)).get(
                    "aliasing_operands", {}).get("lists")
        found.append({
            "name": m.group(1), "shape": shape, "layout": m.group(3),
            "index_known_zero_bits": [int(b["zeroes"]) for b in
                                      idx.get("index_known_bits", [])],
            "is_index_aligned": idx.get("is_index_aligned"),
            "aliasing_operands": aliasing,
        })
    return found


def main() -> int:
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet10"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
    client_block = int(sys.argv[3]) if len(sys.argv) > 3 else 25
    f = n // 4
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, CHECKOUT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

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
    (spec,) = load_experiments_from_file(os.path.join(
        CHECKOUT, "blades_tpu", "tuned_examples",
        "fedavg_cifar10_1000clients.yaml")).values()
    (trial,) = [t for t in expand_grid(spec["config"])
                if t["server_config"]["aggregator"]["type"] == "Median"]
    _, config = get_algorithm_class(spec["run"], return_config=True)
    config.update_from_dict(trial)
    config.update_from_dict({"global_model": model, "num_clients": n,
                             "num_malicious_clients": f,
                             "client_block": client_block})
    config.validate()
    fr = config.get_fed_round()
    dtype = getattr(jnp, str(config.update_dtype))
    step = streamed_step(fr, client_block=client_block,
                         d_chunk=config.d_chunk, update_dtype=dtype,
                         malicious_prefix=f)
    # The cells' compact geometry, which the round itself takes only on
    # a TPU backend (the kernel gate sees the CPU here).
    plan = block_plan(n, f, client_block, dtype, compact=True)
    state = jax.eval_shape(lambda k: fr.init(k, n), jax.random.PRNGKey(0))
    d = sum(p.size for p in jax.tree.leaves(state.server.params))
    # The matrix by the rule the round builds it by.
    matrix, _ = compact_matrix(plan, n - f, d)
    rows = matrix[0]

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=chip)

    cap = 192
    args = (shape(matrix, dtype), on_chip(state.client_opt),
            on_chip(state.server.params),
            shape((n, cap, 32, 32, 3), jnp.bfloat16),
            shape((n, cap), jnp.int32), shape((n,), jnp.int32),
            shape((n,), jnp.bool_), shape((n, 2), jnp.uint32),
            shape((n, 2), jnp.uint32), shape((), jnp.uint32))
    out = {"model": model, "num_clients": n, "client_block": client_block,
           "plan": plan._asdict(), "matrix": list(matrix),
           "topology": "v5e:2x2"}
    t = time.time()
    # The kernel gates ask jax.default_backend() and would see the CPU:
    # trace the block as the chip does.
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        lowered = step.train_block.lower(*args, plan=plan)
    compiled = lowered.compile()
    out["compile_s"] = time.time() - t
    hlo = compiled.as_text()
    m = compiled.memory_analysis()
    out["stores"] = store_ops(hlo, rows)
    out["tile_copies"] = len(re.findall(
        r"custom-call\(.*custom_call_target=\"tpu_custom_call\".*"
        r"store_row_block", hlo))
    out["remat_instructions"] = len(set(re.findall(r"%(\S*\.remat\S*) = ",
                                                   hlo)))
    out["memory"] = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(m, k)}
    out_dir = os.path.join(CHECKOUT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"aot_train_block_{model}_{n}.hlo")
    with open(path, "w") as fh:
        fh.write(hlo)
    out["hlo"] = path
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""What a ``(rows, columns)`` matrix costs in HBM on the chip it runs on:
``memory_stats()`` before and after allocating it, so that a storage tile's
padding shows (an 8-row bf16 matrix is 8 rows or a tile's 16).

    python3 perfbench/tools/hbm_probe.py 8 413959168 bfloat16
"""

import json
import sys

import jax
import jax.numpy as jnp


def main() -> int:
    rows, cols, dtype = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    dev = jax.devices()[0]
    before = dev.memory_stats()["bytes_in_use"]
    x = jnp.zeros((rows, cols), jnp.dtype(dtype))
    x.block_until_ready()
    after = dev.memory_stats()["bytes_in_use"]
    print(json.dumps({
        "device": dev.device_kind, "shape": [rows, cols], "dtype": dtype,
        "plain_bytes": rows * cols * jnp.dtype(dtype).itemsize,
        "allocated_bytes": after - before,
        "bytes_limit": dev.memory_stats().get("bytes_limit")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

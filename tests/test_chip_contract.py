"""What a CPU box can check of the chip contract.

- Every Pallas kernel lowers for ``platforms=["tpu"]`` at its gate's
  edge shapes (``tools/chip_kernels.CASES`` — the same table the chip
  run compiles and compares): catches Pallas API drift in seconds.  The
  table's cases of XLA's own code (the store into row planes, ISSUE 32)
  lower with no Mosaic call.
  Whether Mosaic then *compiles* them is the chip's answer
  (``chiprun -- python tools/chip_kernels.py``).
- GSPMD cannot partition a Mosaic custom call, so the flat mesh round
  must trace the ``jnp`` aggregators.
- ``chip_smoke.py`` refuses to run without a TPU.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from tools.chip_kernels import CASES  # noqa: E402


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_pallas_kernel_lowers_for_tpu(case):
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in case.inputs()]
    exported = jax.export.export(jax.jit(case.run), platforms=["tpu"])(*args)
    # A case that is XLA's own code (the store into row planes) holds no
    # Mosaic call, and must hold none.
    assert ("tpu_custom_call" in exported.mlir_module()) == case.mosaic


def test_gspmd_round_traces_jnp_aggregators_not_mosaic(monkeypatch):
    from blades_tpu.ops.aggregators import Median
    from blades_tpu.parallel.sharded import _gspmd_traced

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()), ("clients",))
    shardings = dict(in_shardings=NamedSharding(mesh, P("clients")),
                     out_shardings=NamedSharding(mesh, P()))
    x = jax.ShapeDtypeStruct((64, 1 << 16), jnp.float32)  # kernel-sized
    agg = Median()
    with pytest.raises(NotImplementedError, match="automatically partition"):
        jax.export.export(jax.jit(agg.aggregate, **shardings),
                          platforms=["tpu"])(x)
    scoped = jax.jit(_gspmd_traced(agg.aggregate), **shardings)
    module = jax.export.export(scoped, platforms=["tpu"])(x).mlir_module()
    assert "tpu_custom_call" not in module


def test_chip_smoke_refuses_to_run_without_a_tpu(capsys):
    import chip_smoke

    assert chip_smoke.main() != 0  # the suite's backend is the CPU
    out, err = capsys.readouterr()
    assert out == ""  # no result of any kind
    assert err.strip().startswith("chip_smoke: needs a TPU")
    assert err.count("\n") == 1

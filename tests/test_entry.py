"""The two driver-graded entry points.

Round 1 shipped a broken ``dryrun_multichip`` precisely because no test
imported ``__graft_entry__`` — these tests close that gap:

- ``entry()`` must return ``(fn, example_args)`` that jit-compiles.
- ``dryrun_multichip(8)`` must run in-process (conftest's 8-device CPU
  mesh) AND self-provision its own CPU mesh in a clean subprocess with
  no ``XLA_FLAGS``.
- On an accelerator backend it uses the chips that exist or raises; it
  never trades them for a virtual CPU mesh.
"""

import os
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_entry_compiles_and_runs():
    sys.path.insert(0, REPO_ROOT)
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)
    assert bool(jax.numpy.isfinite(out).all())


def test_provision_never_trades_chips_for_a_cpu_mesh(monkeypatch):
    sys.path.insert(0, REPO_ROOT)
    import __graft_entry__ as ge

    monkeypatch.setattr(jax, "device_count", lambda: 4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ge._provision_devices(4)  # enough chips: nothing to do
    with pytest.raises(RuntimeError, match="needs 8 devices"):
        ge._provision_devices(8)
    assert jax.config.jax_platforms == "cpu"  # conftest's, untouched


@pytest.mark.slow  # full ResNet-18 round on an 8-virtual-device mesh:
# minutes of XLA CPU compile on a 2-core host
def test_dryrun_multichip_inprocess():
    sys.path.insert(0, REPO_ROOT)
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)  # raises on failure


@pytest.mark.slow  # same program compiled from scratch in a clean subprocess
def test_dryrun_multichip_self_provisions_clean_process():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # CPU, never "whatever is there": on a machine with a chip the child
    # would fight this process's siblings for it.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as ge; ge.dryrun_multichip(8)"],
        # Generous: the subprocess compiles the full round from scratch and
        # shares the machine with whatever else the suite is running.
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=2400,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr}\nstdout:\n{proc.stdout}"
    assert "dryrun_multichip(8): OK" in proc.stdout

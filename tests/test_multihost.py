"""2-process jax.distributed (DCN) smoke test — the multi-process bring-up
the reference's NCCL communicator provided (ref: fllib/communication/
communicator.py:119-184), here via jax.distributed + a global mesh.

Spawns two worker subprocesses, each with 4 virtual CPU devices; the
federated round's collectives cross the process boundary.

The test SKIPS (with the probe's evidence in the reason) on hosts where
only single-process execution is available — e.g. this image's jaxlib,
whose CPU backend aborts cross-process collectives with "Multiprocess
computations aren't implemented on the CPU backend", or a box whose
loopback gRPC handshake cannot form a 2-process group at all.  A cheap
capability probe (a tiny cross-process psum, not the full federated
round) decides; genuine regressions in the round's collectives still
fail the test on capable hosts.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).parent

# Substrings that identify "this host cannot do multi-process jax at
# all" — as opposed to a bug in the federated round under test.
_CAPABILITY_ERRORS = (
    "Multiprocess computations aren't implemented",
    "DEADLINE_EXCEEDED",
    "failed to connect to all addresses",
)

_PROBE = r"""
import os
import sys
try:
    import jax
    jax.distributed.initialize(sys.argv[1], num_processes=2,
                               process_id=int(sys.argv[2]))
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    # One tiny cross-process collective: enough to prove (or disprove)
    # that this backend executes multi-process computations.
    mesh = Mesh(jax.devices(), ("d",))
    x = jnp.ones((len(jax.devices()),))
    y = jax.jit(lambda a: a.sum(), out_shardings=NamedSharding(mesh, P()))(
        jax.device_put(x, NamedSharding(mesh, P("d"))))
    print("probe ok", float(y), flush=True)
except Exception as e:
    print("probe err:", repr(e), flush=True)
# Skip the distributed atexit shutdown: after a failed collective the
# barrier hangs forever (observed: the worker survives its own traceback
# by minutes), and all the parent needs is the verdict above.
os._exit(0)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    # CPU, never "whatever is there": on a machine with a chip two
    # workers would fight for it.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    return env


def _spawn(args, env):
    return subprocess.Popen(
        [sys.executable, *args], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, cwd=str(HERE.parent),
    )


def _multiprocess_capability() -> str:
    """'' when 2-process jax works here, else the reason it cannot."""
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = _worker_env()
    procs = [_spawn(["-c", _PROBE, coord, str(i)], env) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        return ("2-process jax.distributed probe timed out forming the "
                "group (single-host-only environment)")
    for out in outs:
        for marker in _CAPABILITY_ERRORS:
            if marker in out:
                return (f"single-process host: the 2-process capability "
                        f"probe failed with {marker!r}")
    # An unrecognised probe failure is NOT treated as a capability gap —
    # the real test runs and reports it.
    return ""


# Spawns two real processes, each paying its own XLA CPU compile (~5 s
# plus interpreter start); the distributed round logic stays tier-1 on
# the in-process simulation tests (PR 20 budget rebalance).
@pytest.mark.slow
def test_two_process_distributed_round():
    reason = _multiprocess_capability()
    if reason:
        pytest.skip(reason)
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = _worker_env()
    env.pop("XLA_FLAGS", None)
    procs = [
        _spawn([str(HERE / "multihost_worker.py"), coord, "2", str(i)], env)
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert f"proc {i}: multihost round OK" in out, out

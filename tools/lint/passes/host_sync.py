"""host-sync: no device->host round trips inside jitted-round modules.

The AST generalization of the retired ``tests/test_no_host_sync.py``
grep: every ``device_get`` / ``block_until_ready`` / numpy conversion /
``.item()`` / ``float(<array expr>)`` inside the modules whose code runs
inside (or builds) the jitted round stalls the dispatch pipeline once
per round.  The sanctioned fetch lives in a HOST module
(``Fedavg._train_raw``'s ``blades/fetch``), which is not scanned; a
device-side line that must sync carries
``# blades-lint: disable=host-sync — <why>``.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Sequence

from tools.lint import astutil
from tools.lint.core import Finding, LintContext, LintPass

# Modules whose code runs inside (or traces into) the jitted round.
DEVICE_SIDE = (
    "blades_tpu/core/round.py",
    "blades_tpu/core/server.py",
    "blades_tpu/core/task.py",
    "blades_tpu/core/health.py",
    "blades_tpu/core/callbacks.py",
    "blades_tpu/data/sampler.py",
    "blades_tpu/data/augment.py",
    "blades_tpu/adversaries/base.py",
    "blades_tpu/adversaries/update_attacks.py",
    "blades_tpu/adversaries/training_attacks.py",
    "blades_tpu/faults/injector.py",
    "blades_tpu/comm/codecs.py",
    # Buffered-async subsystem (ISSUE 14): the cycle program and the
    # realization/weight modules trace into the jitted cycle; the host
    # engine (arrivals/engine.py) is deliberately NOT here — its
    # device_get of the realization windows is the sanctioned host
    # boundary.
    "blades_tpu/arrivals/cycle.py",
    "blades_tpu/arrivals/process.py",
    "blades_tpu/arrivals/weights.py",
    # Out-of-core state staging (ISSUE 15): the store + prefetcher ARE
    # the staging hot path — a stray blocking fetch there stalls the
    # round pipeline exactly like one inside the jitted round.  The
    # sanctioned prefetcher boundary (cohort-id fetch, the write-back
    # fetch, one-time store init) carries per-line justification
    # pragmas; everything else is a finding.
    "blades_tpu/state/store.py",
    "blades_tpu/state/prefetch.py",
    # Out-of-core training data (ISSUE 20): the data store + streaming
    # plumbing are the data-plane staging hot path — cohort gathers ride
    # the state prefetcher's FIFO worker and the chunked evaluator's
    # per-chunk scalar fetch is the ONE sanctioned eval sync (four
    # scalars per chunk, pragma'd at the site).  Any other blocking
    # fetch here stalls the round pipeline exactly like state staging.
    "blades_tpu/data/store.py",
    "blades_tpu/data/stream.py",
    # Client-lifetime ledger (ISSUE 16): observe() runs once per round
    # on the driver thread between dispatches — an unsanctioned device
    # fetch there re-introduces exactly the per-round stall the
    # deferred-row machinery removed.  The np.asarray coercions over
    # ALREADY-FETCHED rows are the sanctioned boundary and carry
    # per-line pragmas; any new sync is a finding.
    "blades_tpu/obs/ledger.py",
    # Control plane (ISSUE 17): policy decisions and the controller's
    # step() run once per round on the driver thread between dispatches
    # over ALREADY-FETCHED rows — an unsanctioned device fetch there
    # stalls the pipeline like any other, and worse: it would smuggle
    # device state into decisions the replay contract says are pure in
    # (policy, pre-state, sensor row, round, tick), making the journal
    # non-rederivable.  Raw wall-clock in decisions is the same hazard
    # and is already frozen out repo-wide by trace-discipline.
    "blades_tpu/control/policy.py",
    "blades_tpu/control/controller.py",
    "blades_tpu/ops/aggregators.py",
    "blades_tpu/ops/clustering.py",
    "blades_tpu/ops/layout.py",
    "blades_tpu/ops/masked.py",
    "blades_tpu/ops/pallas_round.py",
    "blades_tpu/ops/pallas_rowstats.py",
    "blades_tpu/ops/pallas_select.py",
    "blades_tpu/parallel/streamed.py",
    "blades_tpu/parallel/streamed_geometry.py",
    "blades_tpu/parallel/sharded.py",
    "blades_tpu/parallel/dsharded.py",
    "blades_tpu/parallel/packed.py",
    # Decentralized gossip round (ISSUE 19): the per-node round program
    # traces into shard_map — a stray sync there stalls every node's
    # dispatch.  graph.py is deliberately NOT here: it is host-side
    # numpy by design (tables are built once at setup).
    "blades_tpu/topology/gossip.py",
)

_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready",
               "np.asarray", "np.array", "numpy.asarray", "numpy.array"}
# jnp/jax attribute roots whose presence inside a float()/int() argument
# marks the argument as an on-device array expression.
_ARRAY_ROOTS = {"jnp", "jax"}
_REDUCTIONS = {"sum", "mean", "max", "min", "all", "any", "prod"}

_HINT = ("move the fetch to the sanctioned one (Fedavg._train_raw's "
         "blades/fetch), or pragma the line if it is genuinely "
         "setup-time/once-per-object")


def _is_array_expr(node: ast.AST) -> bool:
    """Heuristic: does this expression produce an on-device array?
    True when it mentions a ``jnp.``/``jax.`` attribute or calls an
    array reduction method (``x.sum()`` ...)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute):
            root = sub
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in _ARRAY_ROOTS:
                return True
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr in _REDUCTIONS:
            return True
    return False


class HostSyncPass(LintPass):
    name = "host-sync"
    doc = ("device->host sync (device_get / block_until_ready / "
           "np.asarray / .item() / float(array)) in jitted-round modules")

    def __init__(self, modules: Optional[Sequence[str]] = None):
        self.modules = tuple(modules) if modules is not None else DEVICE_SIDE

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        scanning_repo = (ctx.root / "blades_tpu").is_dir() \
            and self.modules is DEVICE_SIDE
        for rel in self.modules:
            src = ctx.file(rel)
            if src is None:
                # Partial scans (--changed / explicit paths) simply skip
                # absent modules; a module GONE from disk on a full scan
                # means this list went stale.
                if scanning_repo and not (ctx.root / rel).exists():
                    findings.append(Finding(
                        self.name, rel, 1,
                        "host-sync module list is stale: file is gone",
                        fix_hint="update DEVICE_SIDE in "
                                 "tools/lint/passes/host_sync.py"))
                continue
            if src.tree is None:
                continue
            for call in astutil.walk_calls(src.tree):
                cn = astutil.call_name(call)
                if cn in _SYNC_CALLS:
                    findings.append(Finding(
                        self.name, src.rel, call.lineno,
                        f"host-sync call {cn}() in a jitted-round module",
                        fix_hint=_HINT))
                elif (isinstance(call.func, ast.Attribute)
                        and call.func.attr in ("item", "block_until_ready")
                        and not call.args and not call.keywords):
                    findings.append(Finding(
                        self.name, src.rel, call.lineno,
                        f".{call.func.attr}() in a jitted-round module",
                        fix_hint=_HINT))
                elif (isinstance(call.func, ast.Name)
                        and call.func.id in ("float", "int")
                        and len(call.args) == 1
                        and _is_array_expr(call.args[0])):
                    findings.append(Finding(
                        self.name, src.rel, call.lineno,
                        f"{call.func.id}() on an array expression forces "
                        "a device sync in a jitted-round module",
                        fix_hint=_HINT))
        return findings

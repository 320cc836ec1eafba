"""trace-discipline: every measured second flows through the span layer.

:mod:`blades_tpu.obs.trace` is the SINGLE timing source of truth: phase
durations are spans (they aggregate, nest, export to Chrome traces, and
correlate with the jax profiler), and the sanctioned raw clock is
``obs.trace.now()``.  A raw ``time.time()`` / ``time.perf_counter()`` /
``time.monotonic()`` call anywhere else under ``blades_tpu/`` produces a
duration nobody can see in a trace — the drift this pass freezes out,
exactly like host-sync froze out stray ``device_get``\\ s.

Scope: ``blades_tpu/`` only (``tools/`` holds measurement harnesses
outside the traced driver).  The trace module itself is the
allowed home.  Detection covers the module-attribute
form (``time.perf_counter()``), ``from time import perf_counter``
aliases, and the ``_ns`` variants; ``time.sleep`` is not a measurement
and stays legal, as does passing ``time.perf_counter`` itself as an
injectable clock default (a reference, not a call).  Genuinely
sanctioned wall-clock stamps (e.g. the autotuner plan-cache
``created_unix`` metadata) carry the unified pragma with a reason.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set

from tools.lint import astutil
from tools.lint.core import Finding, LintContext, LintPass

#: Where raw clock reads are legal: the span layer itself.
TIMER_MODULES = (
    "blades_tpu/obs/trace.py",
)

#: ``time`` module attributes whose CALL is a duration/wall-clock read.
RAW_CLOCKS = frozenset({
    "time", "perf_counter", "monotonic",
    "time_ns", "perf_counter_ns", "monotonic_ns",
})

_HINT = ("time the block with a blades_tpu.obs.trace span "
         "(Tracer.span/time, or start/finish around non-nestable "
         "blocks), or read obs.trace.now() for a bare elapsed delta; "
         "pragma the line only for a sanctioned wall-clock metadata "
         "stamp")


class TraceDisciplinePass(LintPass):
    name = "trace-discipline"
    doc = ("raw time.time()/perf_counter()/monotonic() calls in "
           "blades_tpu/ outside the trace/timer modules")

    def __init__(self, prefixes: Optional[Sequence[str]] = None,
                 allowed: Optional[Sequence[str]] = None):
        self.prefixes = tuple(prefixes) if prefixes is not None \
            else ("blades_tpu",)
        self.allowed = frozenset(allowed) if allowed is not None \
            else frozenset(TIMER_MODULES)

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        for src in ctx.matching(self.prefixes):
            if src.rel in self.allowed or src.tree is None:
                continue
            time_mods, clock_aliases = self._imports(src.tree)
            if not time_mods and not clock_aliases:
                continue
            for call in astutil.walk_calls(src.tree):
                cn = astutil.call_name(call)
                if cn is None:
                    continue
                if cn in clock_aliases:
                    findings.append(Finding(
                        self.name, src.rel, call.lineno,
                        f"raw clock call {cn}() (imported from the time "
                        "module) outside the trace/timer modules",
                        fix_hint=_HINT))
                    continue
                head, _, tail = cn.rpartition(".")
                if head in time_mods and tail in RAW_CLOCKS:
                    findings.append(Finding(
                        self.name, src.rel, call.lineno,
                        f"raw clock call {cn}() outside the trace/timer "
                        "modules — this duration is invisible to the "
                        "span tree",
                        fix_hint=_HINT))
        return findings

    @staticmethod
    def _imports(tree: ast.Module):
        """(names the ``time`` module is bound to, names its clock
        functions are bound to) in this file — import-based, so a local
        variable or another module named ``time`` cannot false-positive."""
        time_mods: Set[str] = set()
        clock_aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "time":
                        time_mods.add(alias.asname or alias.name)
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name in RAW_CLOCKS:
                        clock_aliases[alias.asname or alias.name] = \
                            alias.name
        return time_mods, clock_aliases

"""Span tracing: the host-side timing source of truth.

A :class:`Tracer` times named spans (sweep -> trial -> round -> phase),
aggregates per-name phase statistics (``{name: {mean_s, total_s,
count}}`` — the ``timers`` field of every metrics row) and, armed
(``record=True``), keeps the span TREE and exports it as Chrome/Perfetto
trace JSON per trial (``--trace-dir``).

The profiler's clock: EVERY span, armed or not, enters a
``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` when the span
carries a ``step`` number).  Outside a profiler session that is an inert
``TraceMe``; inside one (``--trace``, or a harness's
``jax.profiler.start_trace``) the span lands on the calling thread's
line of the profile, beside the runtime's own host events and on the
clock of the device trace — so each device idle gap can be put down to
the phase the host was in.  ``record`` governs retention only: an
un-armed tracer keeps NO span, writes NO file, and its rows differ from
an armed run's in ``timers`` alone (tests/test_trace.py).

:func:`span` is the same call for code that holds no tracer (the round
bodies under ``parallel/``): it aggregates into — and, armed, nests
under — the tracer whose span is open on the calling thread, and is
annotation-only where none is.

Clock discipline: :func:`now` is THE duration clock.  Raw
``time.time()``/``time.perf_counter()`` calls anywhere else under
``blades_tpu/`` are blades-lint findings (the ``trace-discipline``
pass), so every measured second flows through this module and lands in
one place.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = [
    "Span", "Tracer", "now", "span", "trace",
    "validate_chrome_trace",
]


def now() -> float:
    """Monotonic seconds — the single sanctioned duration clock
    (``trace-discipline`` lint).  Use span contexts where a phase tree
    is wanted; ``now()`` directly where only an elapsed delta is."""
    return time.perf_counter()


# Recorded-span cap: a pathological million-round sweep must degrade to
# aggregation-only (an un-armed tracer's behavior), never OOM the host.  The
# cap is per tracer; dropped spans are counted in the export metadata.
MAX_RECORDED_SPANS = 200_000


@dataclasses.dataclass
class Span:
    """One timed region.  ``attrs`` carries provenance (plan_id,
    hbm_passes, agg_domain, comm_bytes_up, ...) given at
    :meth:`Tracer.start` or merged in via :meth:`Tracer.stamp_latest`."""

    name: str
    start_s: float
    end_s: Optional[float] = None
    step: Optional[int] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    children: List["Span"] = dataclasses.field(default_factory=list)
    # The entered profiler annotation, exited by Tracer.finish.
    _ann: Any = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def duration(self) -> float:
        return (self.end_s if self.end_s is not None else self.start_s) \
            - self.start_s


_profiler = None  # jax.profiler once resolved; False where unavailable


def _enter_annotation(name: str, step: Optional[int]):
    """Enter the span's jax profiler annotation and return it (``None``
    when jax or its profiler is unavailable — the span layer must work
    in a stripped-down host process).  Inert outside a profiler
    session."""
    global _profiler
    if _profiler is None:
        try:
            import jax.profiler as jp
        except Exception:
            jp = False
        _profiler = jp
    if not _profiler:
        return None
    try:
        ann = (_profiler.TraceAnnotation(name) if step is None else
               _profiler.StepTraceAnnotation(name, step_num=int(step)))
        ann.__enter__()
    except Exception:
        return None
    return ann


def _exit_annotation(ann) -> None:
    if ann is not None:
        try:
            ann.__exit__(None, None, None)
        except Exception:
            pass


# Per thread, the tracers that have a span open on it, innermost last:
# what the module-level span() aggregates into.
_thread = threading.local()


def _open_tracers() -> List["Tracer"]:
    try:
        return _thread.tracers
    except AttributeError:
        _thread.tracers = []
        return _thread.tracers


class Tracer:
    """Span timer + phase aggregator, every span on the profiler's
    clock (module docstring).

    ``record=False`` (default): aggregation only, nothing retained per
    span.  ``record=True`` (armed): additionally keeps the span TREE
    for Chrome-trace export.  ``clock`` is injectable for deterministic
    tests.
    """

    def __init__(self, record: bool = False, clock=now):
        self.record = bool(record)
        self._clock = clock
        self._totals: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._roots: List[Span] = []
        self._stack: List[Span] = []
        self._latest: Dict[str, Span] = {}
        self._recorded = 0
        self._dropped = 0

    # -- span lifecycle ------------------------------------------------------

    def start(self, name: str, step: Optional[int] = None,
              **attrs) -> Span:
        """Open a span (pair with :meth:`finish`).  The returned
        :class:`Span` always carries real start/end times, so
        ``finish(span); span.duration`` is the sanctioned way to
        measure a block the ``with`` form cannot wrap cleanly."""
        span = Span(name=name, start_s=self._clock(), step=step,
                    attrs=dict(attrs))
        if self.record:
            if self._recorded < MAX_RECORDED_SPANS:
                self._recorded += 1
                (self._stack[-1].children if self._stack
                 else self._roots).append(span)
                self._stack.append(span)
            else:
                self._dropped += 1
        _open_tracers().append(self)
        span._ann = _enter_annotation(name, step)
        return span

    def finish(self, span: Span) -> Span:
        _exit_annotation(span._ann)
        span._ann = None
        opened = _open_tracers()
        for i in range(len(opened) - 1, -1, -1):
            if opened[i] is self:
                del opened[i]
                break
        span.end_s = self._clock()
        self._totals[span.name] = self._totals.get(span.name, 0.0) \
            + span.duration
        self._counts[span.name] = self._counts.get(span.name, 0) + 1
        if self.record:
            if self._stack and self._stack[-1] is span:
                self._stack.pop()
            elif span in self._stack:
                # Out-of-order finish (a crash unwound past an explicit
                # start/finish pair): close everything above it too.
                while self._stack and self._stack[-1] is not span:
                    self._stack.pop()
                if self._stack:
                    self._stack.pop()
            self._latest[span.name] = span
        return span

    @contextmanager
    def span(self, name: str, step: Optional[int] = None,
             **attrs) -> Iterator[Span]:
        sp = self.start(name, step=step, **attrs)
        try:
            yield sp
        finally:
            self.finish(sp)

    # -- provenance ----------------------------------------------------------

    def stamp_latest(self, name: str, attrs: Dict[str, Any]) -> None:
        """Merge attrs into the most recently FINISHED span named
        ``name`` — the driver stamps round provenance (plan_id,
        hbm_passes, agg_domain, comm_bytes_up) after the row is
        finalized, which is after the dispatch span closed."""
        span = self._latest.get(name)
        if span is not None:
            span.attrs.update(attrs)

    def stamp_latest_of(self, names, attrs: Dict[str, Any]) -> None:
        """:meth:`stamp_latest` over alternatives: stamp whichever of
        ``names`` finished most recently (the driver's dispatch span is
        named ``compile`` the first time and ``round`` after)."""
        spans = [self._latest[n] for n in names if n in self._latest]
        if spans:
            max(spans, key=lambda s: s.end_s or 0.0).attrs.update(attrs)

    # -- aggregation (every row's ``timers``) --------------------------------

    def mean(self, name: str) -> float:
        c = self._counts.get(name, 0)
        return self._totals.get(name, 0.0) / c if c else 0.0

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {
            k: {"mean_s": self.mean(k), "total_s": self._totals[k],
                "count": self._counts[k]}
            for k in self._totals
        }

    def absorb(self, other: "Tracer") -> None:
        """Add ``other``'s phase aggregates to this tracer's: what a
        driver timed before it adopted this tracer (its build) stays in
        the rows' ``timers``."""
        for k, total in other._totals.items():
            self._totals[k] = self._totals.get(k, 0.0) + total
            self._counts[k] = self._counts.get(k, 0) + other._counts[k]

    # -- export --------------------------------------------------------------

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The span tree as Chrome/Perfetto trace JSON (``ph: "X"``
        complete events, microsecond timestamps; nesting is recovered by
        the viewer from containment on one tid)."""
        events: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "blades_tpu"}},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
             "args": {"name": "host spans"}},
        ]

        def emit(span: Span) -> None:
            # A still-open span (export mid-run / from a crash handler)
            # contributes no event of its own, but its FINISHED children
            # must still be walked — they are the tree being salvaged.
            if span.end_s is not None:
                args = dict(span.attrs)
                if span.step is not None:
                    args["step"] = span.step
                events.append({
                    "ph": "X", "name": span.name, "cat": "blades",
                    "ts": round(span.start_s * 1e6, 3),
                    "dur": round(span.duration * 1e6, 3),
                    "pid": 1, "tid": 1, "args": args,
                })
            for c in span.children:
                emit(c)

        for root in self._roots:
            emit(root)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"format": "blades_tpu.obs.trace", "version": 1,
                         "spans_recorded": self._recorded,
                         "spans_dropped": self._dropped},
        }

    def export(self, path) -> str:
        """Atomically write the Chrome trace JSON (faults/host-style
        tmp + fsync + ``os.replace``); returns the published path."""
        from blades_tpu.faults.host import atomic_write_json

        return atomic_write_json(self.to_chrome_trace(), path)


def span(name: str, step: Optional[int] = None):
    """A span for code that holds no tracer, as a context manager: a
    span of the tracer whose span is open on the calling thread
    (innermost), or the profiler annotation alone where none is (it
    then yields ``None``)."""
    opened = _open_tracers()
    if opened:
        return opened[-1].span(name, step=step)
    return _annotation_only(name, step)


@contextmanager
def _annotation_only(name: str, step: Optional[int]) -> Iterator[None]:
    ann = _enter_annotation(name, step)
    try:
        yield None
    finally:
        _exit_annotation(ann)


# ---------------------------------------------------------------------------
# jax profiler capture
# ---------------------------------------------------------------------------


@contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a jax profiler trace (device + host) into ``log_dir``.
    Every span's annotation lands inside this capture, so the
    ``--trace`` profiler hook and ``--trace-dir`` span export compose."""
    import jax.profiler

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# offline validation (tools/validate_metrics.py --trace)
# ---------------------------------------------------------------------------


def validate_chrome_trace(path) -> Tuple[int, List[str]]:
    """Schema-check an exported trace file: returns ``(num_span_events,
    errors)``.  Tolerant the same way the metrics validator is: a
    torn/unparseable file is ONE reported error, never an exception."""
    import json

    errors: List[str] = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as exc:
        return 0, [f"unreadable trace JSON: {exc}"]
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return 0, ["missing 'traceEvents' list"]
    num_spans = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ev.get("name"), str) or ph not in ("X", "M"):
            errors.append(f"event {i}: needs a str name and ph in {{X, M}}")
            continue
        if ph == "X":
            ts, dur = ev.get("ts"), ev.get("dur")
            if not isinstance(ts, (int, float)) \
                    or not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i} ({ev['name']}): X events need "
                              "numeric ts and dur >= 0")
                continue
            if not isinstance(ev.get("args", {}), dict):
                errors.append(f"event {i} ({ev['name']}): args must be "
                              "an object")
                continue
            num_spans += 1
    return num_spans, errors

"""From a profiler trace to numbers: the reduction every PR shares.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain form,

    {"devices": {name: {"modules": [[name, start_ns, dur_ns], ...],
                        "ops":     [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

(``modules``: one event a program launch; ``ops``: one an operation inside
it; ``host``: the spans of the thread that drives the rounds), and the
functions below read only that form, so that a small recorded trace kept
with the benchmark (``testdata/``) checks them without a chip.
"""

from __future__ import annotations

import re

ROUND_SPAN = "bench/round"


def load_xplane(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        out["lines"][plane.name] = sorted(lines)
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for key, names in (("modules", ("XLA Modules",)),
                               ("ops", ("XLA Ops",))):
                for n in names:
                    if n in lines:
                        dev[key] = [[short_name(e.name), float(e.start_ns),
                                     float(e.duration_ns)]
                                    for e in lines[n].events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            # The driving thread is the one that holds the round spans.
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
                if any(e[0] == ROUND_SPAN for e in evs):
                    out["host"] = evs
    return out


def short_name(name: str) -> str:
    """An operation's name as the trace gives it is its whole HLO line;
    keep what stands before `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def clip(events: list, t0: float, t1: float) -> list:
    """Events cut to ``[t0, t1]``; those outside it dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def traced_window(trace: dict) -> tuple:
    """``(start_ns, end_ns, rounds)`` of the round spans on the host."""
    spans = [e for e in trace["host"] if e[0] == ROUND_SPAN]
    if not spans:
        raise ValueError(f"no {ROUND_SPAN!r} span in the trace")
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans),
            len(spans))


def busy_union(events: list) -> list:
    """Disjoint ``[start, end]`` intervals covered by any event."""
    out = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: list) -> float:
    return sum(e - s for s, e in busy_union(events))


def device_events(trace: dict, key: str = "ops") -> dict:
    """Per device, the events of ``key`` inside the traced window; a device
    whose trace lacks the ops line falls back to its program launches."""
    t0, t1, _ = traced_window(trace)
    out = {}
    for name, dev in trace["devices"].items():
        evs = dev.get(key) or dev["modules"]
        out[name] = clip(evs, t0, t1)
    return out


def first_device(trace: dict, key: str = "ops") -> list:
    """The first device's events inside the traced window ([] where no
    device was traced)."""
    per_dev = device_events(trace, key)
    return per_dev[min(per_dev)] if per_dev else []


def busy_and_window_s(trace: dict) -> tuple:
    """Seconds in which an operation ran on the device, averaged over the
    devices that ran any, and the traced window's seconds."""
    t0, t1, _ = traced_window(trace)
    per_dev = [busy_ns(evs) for evs in device_events(trace).values() if evs]
    if not per_dev:
        raise ValueError("no operation ran on a device in the traced window")
    return sum(per_dev) / len(per_dev) / 1e9, (t1 - t0) / 1e9


def time_by_pattern(events: list, patterns: list) -> tuple:
    """``(seconds, launches)`` of the events whose name matches any of the
    regular expressions."""
    rx = [re.compile(p) for p in patterns]
    hit = [d for n, _, d in events if any(r.search(n) for r in rx)]
    return sum(hit) / 1e9, len(hit)


def heaviest(events: list, k: int = 10) -> list:
    """``[[name, seconds], ...]``: the ``k`` names that took most time."""
    tot = {}
    for n, _, d in events:
        tot[n] = tot.get(n, 0.0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """``[[host span, seconds], ...]``: the idle time of the first device
    inside the traced window, summed by the innermost host span that covers
    each gap's middle (``between-rounds`` where none does)."""
    t0, t1, _ = traced_window(trace)
    evs = first_device(trace)
    edges = [[t0, t0]] + busy_union(evs) + [[t1, t1]]
    host = sorted(clip(trace["host"], t0, t1), key=lambda e: (e[1], -e[2]))
    tot = {}
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b <= a:
            continue
        mid, name, best = (a + b) / 2, "between-rounds", None
        for n, s, d in host:
            if s > mid:
                break
            if s + d >= mid and (best is None or d <= best):
                name, best = n, d
        tot[name] = tot.get(name, 0.0) + (b - a)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]

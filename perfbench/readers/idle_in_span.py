"""Idle time of the first device inside the traced window that falls under
the program's host spans of the given names, per traced round, in ms.  With
``"complement": true`` the idle time under none of them.  The spans are the
program's own (``blades/*`` annotations on the driving thread's line);
``None`` where the trace holds no device operation or none of the spans."""
from pb import tracered


def idle_intervals(trace):
    """Disjoint ``[start, end]`` in which no operation ran on the first
    device, inside the traced window."""
    t0, t1, _ = tracered.traced_window(trace)
    edges = [[t0, t0]] + tracered.busy_union(tracered.first_device(trace)) \
        + [[t1, t1]]
    return [[a, b] for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]


def overlap_ns(a, b):
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx, spec):
    trace = ctx["trace"]
    if trace is None or not trace["devices"] \
            or not tracered.first_device(trace):
        return None
    t0, t1, rounds = tracered.traced_window(trace)
    spans = tracered.clip([e for e in trace["host"]
                           if e[0] in spec["spans"]], t0, t1)
    if not spans:
        return None
    idle = idle_intervals(trace)
    under = overlap_ns(idle, tracered.busy_union(spans))
    if spec.get("complement"):
        under = sum(b - a for a, b in idle) - under
    return under / 1e6 / rounds

"""The window's worst round by the program's own spans, in ms: the largest
excess of the named spans' time in one round (consecutive rows' ``timers``
differences) over its own median in the window.  Says on which side of the
fetch a long round fell: in the host's own work, or in its wait for the
device.  ``None`` where the rows carry none of the named spans."""
from pb.window import percentile
from readers.row_timer import total_s


def per_round_s(rows, spans):
    totals = [total_s(r, spans) for r in rows]
    if len(totals) < 3 or any(t is None for t in totals):
        return None
    return [b - a for a, b in zip(totals, totals[1:])]


def read(ctx, spec):
    rounds = per_round_s(ctx["rows"], spec["spans"])
    if rounds is None:
        return None
    return 1e3 * (max(rounds) - percentile(rounds, 50))

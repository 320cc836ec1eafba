"""Host time the program's own spans took, from the ``timers`` the program
stamps on every row (cumulative ``total_s`` per span name; a row's is taken
after its round's spans close).  Per round between the window's first and
last row, or with ``"absolute": true`` the total itself, for a span that
runs once.  In the metric's unit (``ms`` or ``s``).  ``None`` where the rows
carry none of the named spans."""

SCALE = {"s": 1.0, "ms": 1e3}


def total_s(row, spans):
    """Summed ``total_s`` of the named spans in one row, or ``None`` where
    the row has none of them."""
    timers = row.get("timers") or {}
    found = [timers[s]["total_s"] for s in spans if s in timers]
    return sum(found) if found else None


def read(ctx, spec):
    rows = ctx["rows"]
    if not rows:
        return None
    last = total_s(rows[-1], spec["spans"])
    if last is None:
        return None
    if spec.get("absolute"):
        return SCALE[spec["unit"]] * last
    first = total_s(rows[0], spec["spans"])
    if first is None or len(rows) < 2:
        return None
    return SCALE[spec["unit"]] * (last - first) / (len(rows) - 1)

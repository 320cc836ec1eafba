"""A counter the program stamps on every row of a round (``obs/schema.py``):
the metric's file names the ``counter``, and the reading is its sum over
the traced rounds over their wall time.  ``None`` where the rows carry no
such counter (a program that does not stamp it, or a task that has none)."""


def read(ctx, spec):
    rows, name, seconds = ctx["rows"], spec["counter"], ctx["traced_round_s"]
    if not rows or any(name not in r for r in rows):
        return None
    if not seconds or sum(seconds) <= 0:
        return None
    return sum(r[name] for r in rows[:len(seconds)]) / sum(seconds)

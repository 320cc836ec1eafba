"""A row counter over another quantity, both summed over the traced rounds:
the metric's file names the ``counter`` (``obs/schema.py``) and either
``over_counter``, another counter of the same rows, or ``over_required``, a
name in the family's ``REQUIRED`` table (``(cfg, fed) -> a round's count``,
from shapes alone).  ``None`` where the rows carry no such counter (a
program that does not stamp it) or the family has no such table."""


def read(ctx, spec):
    rows = ctx["rows"][:ctx["traced_rounds"]] if ctx.get("traced_rounds") \
        else ctx["rows"]
    name = spec["counter"]
    if not rows or any(name not in r for r in rows):
        return None
    if "over_counter" in spec:
        if any(spec["over_counter"] not in r for r in rows):
            return None
        below = sum(r[spec["over_counter"]] for r in rows)
    else:
        required = getattr(ctx["family"], "REQUIRED", {})
        if spec["over_required"] not in required:
            return None
        below = len(rows) * required[spec["over_required"]](
            ctx["config"], ctx["federation"])
    if below <= 0:
        return None
    return sum(r[name] for r in rows) / below

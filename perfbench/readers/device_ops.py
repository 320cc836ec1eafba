"""Operations inside a program (the device trace's operations line: a
kernel, where ``device_time_per_round`` and ``roofline`` read whole program
launches) whose names match the metric's ``patterns``.

Without a ``work``: their device time per traced round, in ms.

With a ``work`` and a ``counter``: their share of their roofline, in %,
where the kernel's work follows a count the program reports.  The family's
``COUNTED_WORKS[work]`` gives ``(operations, bytes)`` a round from the
configuration, the federation and the counter's mean over the traced rounds
(the routed pairs of a grouped product, which differ from their expectation
by the draw); the least time the chip could take for that over the
operations' device time.

``None`` where no operation matches (a program without the kernel), the
trace has no operations line, the rows carry no such counter or the family
has no such work."""
from pb import tracered


def read(ctx, spec):
    trace = ctx["trace"]
    if trace is None or not trace["devices"]:
        return None
    if not trace["devices"][min(trace["devices"])].get("ops"):
        return None   # first_device would fall back to program launches
    seconds, calls = tracered.time_by_pattern(
        tracered.first_device(trace, "ops"), spec["patterns"])
    if not calls or seconds <= 0:
        return None
    if "work" not in spec:
        return 1e3 * seconds / ctx["traced_rounds"]
    rows = ctx["rows"][:ctx["traced_rounds"]]
    works = getattr(ctx["family"], "COUNTED_WORKS", {})
    if (not rows or any(spec["counter"] not in r for r in rows)
            or spec["work"] not in works):
        return None
    flops, nbytes = works[spec["work"]](
        ctx["config"], ctx["federation"],
        sum(r[spec["counter"]] for r in rows) / len(rows))
    peaks = ctx["peaks"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    ctx["notes"][spec["name"] + ".bound_by"] = (
        "flops" if t_flops >= t_bytes else "hbm_bytes")
    return 100.0 * max(t_flops, t_bytes) * ctx["traced_rounds"] / seconds

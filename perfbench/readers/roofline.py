"""A layer's share of its roofline, in %: the least time the chip could take
for the work the layer is required to do (the larger of operations over peak
operations a second and bytes over peak bytes a second; the metric's file
names the work, ``pb/costs.py::work`` finds it in the family or among the
shared ones) over the device time of the launches that match."""
from pb import costs, tracered


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    evs = tracered.first_device(ctx["trace"], "modules")
    seconds, launches = tracered.time_by_pattern(evs, spec["patterns"])
    if not launches or seconds <= 0:
        return None
    peaks = ctx["peaks"]
    flops, nbytes = costs.work(ctx["family"], spec["work"])(
        ctx["config"], ctx["federation"])
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    ctx["notes"][spec["name"] + ".bound_by"] = (
        "flops" if t_flops >= t_bytes else "hbm_bytes")
    return 100.0 * max(t_flops, t_bytes) * ctx["traced_rounds"] / seconds

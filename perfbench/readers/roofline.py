"""A layer's share of its roofline, in %: the least time the chip could take
for the work the layer is required to do (the larger of operations over peak
operations a second and bytes over peak bytes a second, both from
``pb/costs.py``) over the device time of the launches that match."""
from pb import costs, tracered


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    evs = tracered.first_device(ctx["trace"], "modules")
    seconds, launches = tracered.time_by_pattern(evs, spec["patterns"])
    if not launches or seconds <= 0:
        return None
    cfg, fed, peaks = ctx["config"], ctx["federation"], ctx["peaks"]
    flops = {"train": costs.round_flops, "finish": lambda *_: 0}[
        spec["work"]](cfg, fed)
    nbytes = {"train": costs.train_bytes, "finish": costs.finish_bytes}[
        spec["work"]](cfg, fed)
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    ctx["notes"][spec["name"] + ".bound_by"] = (
        "flops" if t_flops >= t_bytes else "hbm_bytes")
    return 100.0 * max(t_flops, t_bytes) * ctx["traced_rounds"] / seconds

"""The whole round's share of the chips' peak, in %: operations one round
requires (the work the metric's file names; for ``train``, trained lanes
only: an elided lane does no work and gets no credit) times the traced
rounds, over their wall time, over chips times the peak."""
from pb import costs


def read(ctx, spec):
    wall = sum(ctx["traced_round_s"])
    if not ctx["traced_round_s"] or wall <= 0:
        return None
    flops, _ = costs.work(ctx["family"], spec["work"])(
        ctx["config"], ctx["federation"])
    return (100.0 * flops * len(ctx["traced_round_s"]) / wall
            / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]))

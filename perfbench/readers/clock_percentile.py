"""A percentile of the harness's clock around each round call, in ms."""
from pb.window import percentile


def read(ctx, spec):
    rounds = ctx["clock_round_s"]
    if not rounds:
        return None
    return 1e3 * percentile(rounds, spec["q"])

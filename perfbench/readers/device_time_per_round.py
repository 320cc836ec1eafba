"""Device time of the program launches whose names match, per traced round,
in ms."""
from pb import tracered


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    evs = tracered.first_device(ctx["trace"], "modules")
    seconds, launches = tracered.time_by_pattern(evs, spec["patterns"])
    if not launches:
        return None
    return 1e3 * seconds / ctx["traced_rounds"]

"""Program launches on the device inside the round spans, per traced round."""
from pb import tracered


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    evs = tracered.first_device(ctx["trace"], "modules")
    _, launches = tracered.time_by_pattern(evs, spec.get("patterns", [""]))
    if not launches:
        return None
    return launches / ctx["traced_rounds"]

"""1 - union of the device-operation intervals over the traced window, %."""
from pb import tracered


def read(ctx, spec):
    if ctx["trace"] is None or not ctx["trace"]["devices"]:
        return None
    busy, window = tracered.busy_and_window_s(ctx["trace"])
    return 100.0 * (1.0 - busy / window)

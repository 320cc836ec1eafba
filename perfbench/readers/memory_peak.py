"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip after the
window, in GB.  On this runtime it leaves out executables' temporaries: a
lower bound."""


def read(ctx, spec):
    peak = ctx["memory_peak_bytes"]
    return None if not peak else peak / 1e9

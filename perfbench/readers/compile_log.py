"""A number of the compile log: ``setup_seconds`` (tracing + lowering +
compiling or loading during set-up), ``window_compiles`` (backend compiles
plus persistent-cache loads between the window's start and end) or
``cache_misses`` (set-up's cache requests that did not hit)."""


def read(ctx, spec):
    log = ctx["compile"]
    field = spec["field"]
    if field == "setup_seconds":
        return log["setup"]["seconds"]
    if field == "window_compiles":
        w = log["window"]
        return w["backend_compiles"] + w["cache_requests"]
    if field == "cache_misses":
        s = log["setup"]
        return s["cache_requests"] - s["cache_hits"]
    raise ValueError(f"unknown compile-log field {field!r}")

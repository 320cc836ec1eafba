"""Per-layer readers.  Each module has ``read(ctx, spec) -> value | None``:
``spec`` is the metric's own file (``metrics/<name>.json``), ``ctx`` what the
run gathered.  A reader that finds nothing to read returns ``None`` and the
harness leaves the metric out of the line; it never returns 0 for a share of
a roofline or of a peak."""

"""One ``evaluate()`` after the window of the traced run, in ms."""


def read(ctx, spec):
    return ctx.get("eval_ms")

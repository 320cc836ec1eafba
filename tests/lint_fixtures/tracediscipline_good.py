"""Clean twin of tracediscipline_bad.py: every timing need met through
the span layer, plus the legal non-measurement uses of ``time``."""

import time

from blades_tpu.obs.trace import Tracer, now


def span_timed():
    timers = Tracer()
    with timers.span("phase"):
        busy = sum(range(10))
    return timers.summary(), busy


def sanctioned_clock_delta():
    t0 = now()                   # THE sanctioned raw clock
    busy = sum(range(10))
    return now() - t0, busy


def sleeping_is_not_measuring():
    time.sleep(0)                # not a clock read


def injectable_clock_default(clock=time.perf_counter):
    # A clock REFERENCE as an injectable default (the autotuner's
    # measure-fn pattern) is legal; only calls are findings.
    return clock


def pragmad_metadata_stamp():
    return {"created_unix": time.time()}  # blades-lint: disable=trace-discipline — wall-clock metadata stamp, not a duration measurement

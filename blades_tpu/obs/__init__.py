"""Observability: forensics, metrics, tracing, flight recording, watchdog.

Five pieces (ROADMAP: the postmortem/tracing layer the async and
multi-chip work will be debugged with):

- **on-device** (:mod:`blades_tpu.obs.forensics`): every aggregator's
  per-lane keep/trim/trust decision, scored against the true
  malicious-lane mask inside the jitted round — detection
  precision/recall/FPR as device scalars, zero overhead when disabled
  (the diagnostics outputs are dead-code-eliminated by XLA).
- **host-side metrics** (:mod:`blades_tpu.obs.metrics`, :mod:`~.schema`):
  a ``MetricsLogger`` with JSONL / CSV / stdout sinks emitting one
  schema-validated record per round, wired into
  :func:`blades_tpu.tune.sweep.run_experiments`.
- **span tracing** (:mod:`blades_tpu.obs.trace`): the host-side span
  tree (sweep -> trial -> round -> phase) with jax-profiler
  correlation, Chrome/Perfetto export per trial (``--trace-dir``), and
  the single duration clock every timer in the tree flows through.
- **flight recorder** (:mod:`blades_tpu.obs.flightrec`): a bounded ring
  of the last K rounds' digests, dumped atomically to
  ``flightrec.json`` on NaN aggregate / exception / preemption —
  replayable bit-identically via ``tools/replay_round.py``.
- **anomaly watchdog** (:mod:`blades_tpu.obs.watchdog`): schema-driven
  rules over the already-fetched rows (NaN aggregate, norm spike,
  FPR collapse, rounds/s regression), emitting ``watchdog_events`` and
  triggering the flight-recorder dump.
- **client ledger** (:mod:`blades_tpu.obs.ledger`): ONE longitudinal
  record per registered client (participation/flagged counts,
  detection-score EWMA, staleness/norm running stats), updated
  host-side from cohort-indexed diagnosis lanes with resident and
  disk-memmap backends, streaming shard checkpoints, and the
  ``tools/ledger_report.py`` query CLI.
"""

from blades_tpu.obs.flightrec import (  # noqa: F401
    FlightRecorder,
    validate_flightrec,
)
from blades_tpu.obs.forensics import detection_metrics  # noqa: F401
from blades_tpu.obs.ledger import (  # noqa: F401
    ClientLedger,
    DiskLedger,
    LedgerError,
    ResidentLedger,
    make_ledger,
    read_ledger,
    validate_ledger_checkpoint,
)
from blades_tpu.obs.metrics import (  # noqa: F401
    CsvSink,
    JsonlSink,
    MetricsLogger,
    Sink,
    StdoutSink,
)
from blades_tpu.obs.schema import (  # noqa: F401
    ROUND_RECORD_FIELDS,
    SchemaError,
    validate_jsonl,
    validate_record,
)
from blades_tpu.obs.trace import (  # noqa: F401
    Tracer,
    validate_chrome_trace,
)
from blades_tpu.obs.watchdog import (  # noqa: F401
    Watchdog,
    WatchdogEvent,
    WatchdogRule,
    default_rules,
)

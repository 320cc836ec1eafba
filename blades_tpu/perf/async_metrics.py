"""Batched device→host metric fetches for the training loop.

The sequential sweep used to block on ``float(metric)`` for every round
— one host↔device round trip per FL round, each of which drains the
dispatch pipeline.  The async loop instead carries *deferred rows*:
result dicts whose scalar metrics are still device arrays under the
``_device_metrics`` key, accumulated and fetched in ONE
``jax.device_get`` per flush.

Flush points are part of the durability contract, not an optimization
detail: rows must be on disk before any checkpoint that covers them
(otherwise a crash after the checkpoint leaves a round-sequence gap
that ``verify_result_rounds`` rejects), so the sweep flushes

- every ``metrics_every`` buffered rows,
- before every checkpoint save and before the simulated-preemption
  hook fires (the chaos layer's widest kill window),
- at loop exit, and best-effort on the failure path (a row whose
  device values are poisoned is dropped; its rounds replay
  deterministically from the restored checkpoint).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax

#: Key under which a deferred row carries its un-fetched device metrics.
DEVICE_METRICS_KEY = "_device_metrics"


def flush_rows(
    rows: List[Dict],
    finalize: Optional[Callable[[Dict], Dict]] = None,
) -> List[Dict]:
    """Fetch every pending device value across ``rows`` in one
    ``device_get``, then finalize each row (in order) into its host
    form.  Rows without deferred metrics pass through ``finalize``
    unchanged.  Returns the finalized rows; ``rows`` is not mutated
    beyond replacing the deferred values with their fetched forms."""
    pending = [r.get(DEVICE_METRICS_KEY) for r in rows]
    if any(p is not None for p in pending):
        fetched = jax.device_get(pending)
        for row, host in zip(rows, fetched):
            if host is not None:
                row[DEVICE_METRICS_KEY] = host
    if finalize is None:
        return list(rows)
    return [finalize(r) for r in rows]

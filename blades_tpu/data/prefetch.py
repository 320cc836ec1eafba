"""Device-resident data prefetch: stage round ``r+1`` while ``r`` computes.

Two layers:

- :func:`prefetch_to_device` — the generic double-buffered iterator: a
  host iterator of array pytrees is staged onto device ``size`` items
  ahead with ``jax.device_put`` (async on every backend), so the
  consumer never blocks on a synchronous host→device copy.  Use it
  wherever a loop feeds host-resident data to a device program.
- :class:`BatchPrefetcher` — the FL-round specialization the training
  loop uses: the next round's per-client batches are *sampled on
  device* (the jitted :meth:`~blades_tpu.core.round.FedRound.
  sample_round_batches` program, dispatched asynchronously) while the
  current round's training dispatch is still in flight.  Because the
  sampler consumes the same PRNG fold as the fused round program, the
  staged batches are bit-identical to what the round would have drawn
  itself — prefetch on/off changes WHEN the work is dispatched, never
  what is computed (regression-tested per aggregator).

The prefetcher is keyed by the driver's round index, not by comparing
PRNG keys: a key comparison would fetch 8 bytes from the device every
round, stalling the dispatch pipeline (the same sync the streamed
path's mask check avoids by identity caching).  The index
contract makes staleness structurally impossible in the happy path and
:meth:`BatchPrefetcher.invalidate` covers the one legitimate
discontinuity (checkpoint restore rewinds the key chain).
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Iterable, Iterator, Optional, Tuple

import jax


def prefetch_to_device(
    iterable: Iterable[Any],
    size: int = 2,
    device=None,
) -> Iterator[Any]:
    """Yield items of ``iterable`` staged onto ``device`` ``size`` items
    ahead (double-buffered at the default ``size=2``).

    ``jax.device_put`` only *enqueues* the transfer, so by the time the
    consumer asks for item ``r+1`` its copy has been overlapping the
    compute on item ``r``.  The buffer bounds device memory at
    ``size`` staged items."""
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    queue: collections.deque = collections.deque()
    it = iter(iterable)

    def stage():
        for item in it:
            queue.append(jax.device_put(item, device))
            return True
        return False

    for _ in range(size):
        if not stage():
            break
    while queue:
        item = queue.popleft()
        stage()
        yield item


class BatchPrefetcher:
    """Double-buffered per-client batch staging for the FL round.

    ``sample_fn(key) -> (bx, by)`` must be the (jitted) sampling half of
    the round program over the resident training arrays.  The driver
    calls :meth:`take` for the round it is about to dispatch and
    :meth:`stage` for the round after it; a staged entry whose index
    does not match the request (or anything after :meth:`invalidate`)
    is discarded and the batches are drawn synchronously — correctness
    never depends on the pipeline being warm."""

    def __init__(self, sample_fn: Callable[[jax.Array], Tuple]):
        self._sample = sample_fn
        self._staged: Optional[Tuple[int, Tuple]] = None

    def take(self, index: int, key: jax.Array) -> Tuple:
        """Batches for round ``index`` under ``key``: the staged entry
        when the pipeline is warm, else a synchronous draw."""
        staged, self._staged = self._staged, None
        if staged is not None and staged[0] == index:
            return staged[1]
        return self._sample(key)

    def stage(self, index: int, key: jax.Array) -> None:
        """Dispatch (asynchronously) the sampling program for round
        ``index`` under ``key`` and hold the result for :meth:`take`."""
        self._staged = (index, self._sample(key))

    def invalidate(self) -> None:
        """Drop any staged batches.  Must be called whenever the
        driver's key chain rewinds (checkpoint restore) — a stale entry
        would otherwise feed round ``r``'s batches to a different
        round ``r``."""
        self._staged = None

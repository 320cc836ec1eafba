"""Buffer donation + AOT executable cache for the round pipeline.

Every sweep trial used to pay its own ``jit`` trace + XLA compile of the
round program even when the grid only varies knobs that never reach the
program as constants (the seed grid is the canonical case: seeds change
data *values* and PRNG key *values* — both runtime arguments — and
nothing else).  :func:`cached_jit` makes that cost once-per-geometry:
the compiled executable is keyed on

    (caller key = role + static config fingerprint,
     donate_argnums,
     pytree structure + abstract shapes/dtypes of the arguments,
     the device set)

and shared process-wide, so a grid of N identically-shaped trials
lowers and compiles exactly once.  Hit/miss counts are kept globally
(:func:`cache_stats`) and per wrapper (``CachedFunction.hits`` /
``.misses``) so sweeps can surface them through the obs pipeline.

The *caller key* must fingerprint every value the traced program bakes
in as a constant (aggregator trim counts, server lr, DP thresholds,
adversary scale, ...).  :func:`fingerprint` hashes a JSON-able static
config; callers holding baked-in *arrays* (FLTrust's trusted root data
is the one case in this codebase) must digest the bytes into the key —
see :meth:`blades_tpu.algorithms.fedavg.Fedavg` — or skip the cache.

Donation rides the same wrapper: ``donate_argnums`` is recorded in the
lowering, so a cached executable invalidates its donated inputs exactly
like ``jax.jit(fn, donate_argnums=...)`` would.  The donated
``RoundState`` is what halves peak HBM for the largest tensors in the
system (the stacked client optimizer states and, through the streamed
path's own donation chain, the ``(n, d)`` update buffer).

:func:`enable_persistent_compilation_cache` wires JAX's on-disk
compilation cache (``jax_compilation_cache_dir``) underneath: the
in-process cache skips *tracing and dispatch table misses* within a
sweep; the persistent cache skips *XLA itself* across sweeps.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

_lock = threading.Lock()
# (caller_key, donate, avals_key, devices_key) -> compiled executable
_executables: Dict[Tuple, Any] = {}
# role (the first element of the caller key) -> {"hits": n, "misses": n}
_stats: Dict[str, Dict[str, int]] = {}


def fingerprint(static_config: Any) -> str:
    """Stable digest of a JSON-able static-config object (dicts, lists,
    scalars; unknown types stringify).  Two configs with equal
    fingerprints MUST lower to byte-identical programs at equal argument
    shapes — that is the caller's contract, not something this function
    can check."""
    blob = json.dumps(static_config, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()


def _aval_key(leaf) -> Tuple:
    aval = jax.api_util.shaped_abstractify(leaf)
    return (aval.shape, str(aval.dtype), bool(getattr(aval, "weak_type", False)))


def clear_cache() -> None:
    """Drop every cached executable and reset the counters (tests)."""
    with _lock:
        _executables.clear()
        _stats.clear()


def cache_stats() -> Dict[str, Any]:
    """Process-wide compile-cache counters: total hits/misses/entries
    plus a per-role breakdown (role = the first element of the caller
    key, e.g. ``"step"`` for the round program)."""
    with _lock:
        by_role = {r: dict(c) for r, c in _stats.items()}
        return {
            "hits": sum(c["hits"] for c in by_role.values()),
            "misses": sum(c["misses"] for c in by_role.values()),
            "entries": len(_executables),
            "by_role": by_role,
        }


class CachedFunction:
    """``jax.jit(fn, donate_argnums=...)`` with the compiled executable
    shared process-wide by ``(key, argument avals)``.

    The wrapper compiles lazily on first call (``lower().compile()``)
    and thereafter dispatches straight to the executable — including
    executables compiled by a *different* ``CachedFunction`` whose key
    and argument geometry match (that is the cross-trial sharing).
    ``hits``/``misses`` count this wrapper's own lookups; the global
    tallies aggregate by role in :func:`cache_stats`.
    """

    def __init__(
        self,
        fn: Callable,
        key: Tuple,
        donate_argnums: Sequence[int] = (),
    ):
        self._fn = fn
        self._key = tuple(key)
        self._role = str(key[0]) if key else "anon"
        self._donate = tuple(donate_argnums)
        self.hits = 0
        self.misses = 0

    # -- key --------------------------------------------------------------

    _devices_key: Optional[Tuple] = None  # class-level memo (stable per process)

    def _lookup_key(self, args) -> Tuple:
        # Built per dispatch (argument geometry may legitimately change
        # between calls), so keep it lean: the device set is memoized
        # process-wide — jax.devices() plus len(devices) str() calls per
        # round is pure waste in the loop this layer exists to thin out.
        if CachedFunction._devices_key is None:
            CachedFunction._devices_key = tuple(str(d) for d in jax.devices())
        leaves, treedef = jax.tree.flatten(args)
        avals = tuple(_aval_key(l) for l in leaves)
        return (self._key, self._donate, str(treedef), avals,
                CachedFunction._devices_key)

    # -- call -------------------------------------------------------------

    def __call__(self, *args):
        k = self._lookup_key(args)
        with _lock:
            compiled = _executables.get(k)
            tally = _stats.setdefault(self._role, {"hits": 0, "misses": 0})
            if compiled is not None:
                tally["hits"] += 1
                self.hits += 1
        if compiled is None:
            compiled = self.lower(*args).compile()
            with _lock:
                # First writer wins on a race; both compiled the same
                # program, so either executable is correct.
                compiled = _executables.setdefault(k, compiled)
                _stats[self._role]["misses"] += 1
                self.misses += 1
        return compiled(*args)

    def lower(self, *args):
        """Fresh lowering (used by XLA cost analysis); does not touch
        the executable cache."""
        return jax.jit(self._fn, donate_argnums=self._donate).lower(*args)

    @property
    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses}


def cached_jit(
    fn: Callable,
    *,
    key: Tuple,
    donate_argnums: Sequence[int] = (),
) -> CachedFunction:
    """Wrap ``fn`` in a :class:`CachedFunction`.

    ``key`` must start with a short role string (``"step"``,
    ``"evaluate"``, ...) and contain (or derive from) a
    :func:`fingerprint` of every static value the traced program bakes
    in.  Equal keys + equal argument geometry ⇒ the executable is
    reused verbatim.
    """
    return CachedFunction(fn, key=key, donate_argnums=donate_argnums)


def enable_persistent_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory, so a repeat run's XLA work is a disk read.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set JAX has already read it and no directory is set in code.
    Otherwise the cache lives at the FIXED ``<checkout>/.jax_cache``
    next to this package — the directory is part of JAX's cache key, so
    a path that moves between runs (temp dirs, pids, timestamps) never
    hits.  Thresholds drop to zero either way: most of the round's
    programs compile in under the 1 s default and would otherwise never
    be cached.  Must run before the process's first compile — JAX
    decides once whether the cache is in use.  Idempotent."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir

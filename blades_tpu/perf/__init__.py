"""Round-pipeline performance layer (host side).

The pieces that make the *orchestration around* the jitted
round as fast as the round itself (ByzFL arXiv:2505.24802 and
ring-allreduce Byzantine FL arXiv:2501.17392 both locate the
robust-FL throughput ceiling here, not in the defense kernels):

- :mod:`blades_tpu.perf.compile_cache` — buffer donation + an
  in-process AOT executable cache (``jit(...).lower().compile()`` keyed
  on abstract shapes/dtypes + a static round-config fingerprint) shared
  across sweep trials and lane groups, plus wiring for JAX's persistent
  compilation cache so repeat sweeps skip XLA entirely.
- :mod:`blades_tpu.data.prefetch` (sibling) — double-buffered
  device staging of the next round's per-client batches.
- :mod:`blades_tpu.perf.autotune` — the execution autotuner: measured
  plan selection over the round pipeline's perf levers (execution
  path, streamed ``d_chunk``, lane packing, MXU finish, prefetch)
  with a persistent on-disk plan cache.  See the README "Execution
  autotuner" section.
"""

from blades_tpu.perf.autotune import (  # noqa: F401
    Plan,
    PlanCache,
    PlanSpace,
    apply_plan,
    enumerate_plans,
    select_plan,
    timed_measure_fn,
)
from blades_tpu.perf.compile_cache import (  # noqa: F401
    CachedFunction,
    cache_stats,
    cached_jit,
    clear_cache,
    enable_persistent_compilation_cache,
    fingerprint,
)

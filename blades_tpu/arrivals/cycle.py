"""The buffered-async aggregation cycle as ONE pure jittable program.

Where the synchronous round (:mod:`blades_tpu.core.round`) runs every
client lockstep against the same params, the async cycle consumes ``K``
buffered ARRIVAL EVENTS — ``(client, tick, version)`` triples the host
engine accumulated — and for each event computes that client's local
round against the global params VERSION it last pulled, read from the
params-history ring the chaos layer's stale-update ring buffer was
promoted into: rather than replaying stale *updates* (the straggler
fault model), the ring retains stale *params* ``(H+1, d)`` and the
cycle computes honest updates against them — the FedBuff semantics.

    gather event clients' shards + opt states
    -> vmap(local_round at per-event params version) over the K events
    -> chaos lane corruption (event realization)
    -> adversary forge (lazy/free-riders included)
    -> staleness-weighted robust aggregate (Server.step_buffered)
    -> server step, params pushed into the history ring

PRNG discipline: each event's training key is
``fold_in(fold_in(key_base, tick), client)`` — pure in ``(seed, tick,
client)``, so a resumed trial re-derives the identical stream from the
checkpointed tick alone, with no key chain to replay.  The aggregation
key folds the server version.  Arrival/fault realizations never touch
these streams (they fold their own seeds, host-side).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from blades_tpu.core.round import RoundState
from blades_tpu.data.sampler import sample_batch
from blades_tpu.utils.tree import ravel_fn

#: Fold separating the async per-event training stream from the sync
#: driver's split chain of ``PRNGKey(seed)``.
ASYNC_TRAIN_FOLD = 0xA51C
#: Fold deriving the per-cycle aggregation key from the same base.
ASYNC_AGG_FOLD = 0xA99E


def event_train_key(key_base: jax.Array, tick, client) -> jax.Array:
    """The training key for one arrival event: pure in
    ``(seed, tick, client)``."""
    return jax.random.fold_in(jax.random.fold_in(key_base, tick), client)


def cycle_agg_key(key_base: jax.Array, version) -> jax.Array:
    """The aggregation key for the cycle fired at server ``version``."""
    return jax.random.fold_in(
        jax.random.fold_in(key_base, ASYNC_AGG_FOLD), version)


def init_history(params, staleness_cap: int) -> jax.Array:
    """The ``(H+1, d)`` params-history ring, every row the init params
    (a client pulling before the first aggregation sees version 0)."""
    ravel, _, d = ravel_fn(params)
    vec = ravel(params)
    return jnp.tile(vec[None, :], (staleness_cap + 1, 1))


def build_cycle(fed_round, *, staleness_cap: int, weight_schedule: str,
                weight_power: float, weight_cutoff: int,
                corrupt_mode=None, windowed_state: bool = False,
                forensics: bool = False):
    """Build the pure cycle function for ``fed_round`` (jit the result).

    Returns ``cycle(state, data_x, data_y, lengths, ev_clients,
    ev_ticks, ev_stale, ev_malicious, ev_corrupt, key_base, k_agg) ->
    (new_state, metrics)`` where the ``ev_*`` arrays are the host
    engine's ``(K,)`` event columns.  ``state.arrivals`` must carry the
    ``(H+1, d)`` params-history ring (:func:`init_history`).

    ``windowed_state=True`` is the out-of-core composition
    (blades_tpu/state): the registered population's opt rows live in a
    host/disk :class:`~blades_tpu.state.store.ClientStateStore`, so
    the cycle receives the EVENT COHORT's rows directly —
    ``state.client_opt`` is the ``(K, ...)`` gathered stack and
    ``data_x``/``data_y``/``lengths`` are the ``(K, ...)`` event
    shards the engine gathered host-side — and returns the updated
    cohort stack for the engine to scatter back, instead of indexing/
    updating a full ``(n, ...)`` device stack in the traced program.
    The gathered rows are bit-equal to what the resident indexing
    reads, so both modes produce identical cycles.

    ``forensics=True`` runs the aggregator's per-lane diagnostics on
    the staleness-scaled event matrix (``Server.step_buffered_diag``)
    and emits the cohort-shaped forensics bundle: the ``lane_*`` arrays
    are indexed IN EVENT ORDER, so lane ``i`` diagnoses registered
    client ``ev_clients[i]`` — the host driver stamps that id-vector
    alongside as ``lane_forensics["clients"]``.  Detection P/R/FPR are
    scored against the events' own malicious mask (every buffered row
    was delivered, so no participation conditioning applies).
    """
    task = fed_round.task
    hooks = fed_round._hooks()
    adv = fed_round.adversary
    # Lazy "replay" free-riders: malicious events compute against the
    # OLDEST retained params regardless of their true pull — they ship
    # maximally stale work while claiming freshness (the attack only an
    # async server can express; see adversaries.LazyAdversary).
    stale_replay = bool(getattr(adv, "wants_stale_replay", False))
    # Campaign adversaries (adversaries/campaigns.py): attacks that
    # adapt over virtual time declare `wants_ticks` and receive the
    # per-event arrival ticks — the same deterministic columns the
    # engine already built, so scheduled attacks replay bit-identically.
    wants_ticks = bool(getattr(adv, "wants_ticks", False))
    fill_value = None
    if corrupt_mode is not None:
        from blades_tpu.faults.injector import _CORRUPT_FILL

        fill_value = _CORRUPT_FILL[corrupt_mode]
    batch_size = fed_round.batch_size
    num_batches = fed_round.num_batches_per_round

    def cycle(
        state: RoundState,
        data_x: jax.Array,
        data_y: jax.Array,
        lengths: jax.Array,
        ev_clients: jax.Array,
        ev_ticks: jax.Array,
        ev_stale: jax.Array,
        ev_malicious: jax.Array,
        ev_corrupt: jax.Array,
        key_base: jax.Array,
        k_agg: jax.Array,
    ) -> Tuple[RoundState, dict]:
        hist = state.arrivals  # (H+1, d); row j = params j versions ago
        _, unravel, _ = ravel_fn(state.server.params)
        with jax.named_scope("blades/arrivals"):
            idx = jnp.clip(ev_stale, 0, staleness_cap)
            if stale_replay:
                idx = jnp.where(ev_malicious, staleness_cap, idx)
            params_vecs = hist[idx]  # (K, d) per-event params versions

        if windowed_state:
            ex, ey, eln = data_x, data_y, lengths
            opt_sel = state.client_opt
        else:
            ex = data_x[ev_clients]
            ey = data_y[ev_clients]
            eln = lengths[ev_clients]
            opt_sel = jax.tree.map(lambda a: a[ev_clients],
                                   state.client_opt)

        def one_event(pvec, opt, cx, cy, ln, tick, client, mal):
            ek = event_train_key(key_base, tick, client)
            k_sample, k_train = jax.random.split(ek)
            bkeys = jax.random.split(k_sample, num_batches)
            bx, by = jax.vmap(
                lambda kb: sample_batch(kb, cx, cy, ln, batch_size)
            )(bkeys)
            return task.local_round(
                unravel(pvec), opt, bx, by, k_train, mal,
                hooks.data, hooks.grad, hooks.round_begin, hooks.round_end,
            )[:3]

        with jax.named_scope("blades/step"):
            updates, new_opt, losses = jax.vmap(one_event)(
                params_vecs, opt_sel, ex, ey, eln,
                ev_ticks, ev_clients, ev_malicious,
            )
        if fill_value is not None:
            # Chaos lane corruption at delivery: the event realization is
            # host-computed (pure in (fault_seed, tick, client)); here the
            # flagged rows are overwritten with the configured garbage.
            with jax.named_scope("blades/faults"):
                updates = jnp.where(
                    ev_corrupt[:, None], jnp.full_like(updates, fill_value),
                    updates)
        if adv is not None and hasattr(adv, "on_updates_ready"):
            k_adv = jax.random.fold_in(k_agg, 2)
            forge_kwargs = {}
            if wants_ticks:
                forge_kwargs["ticks"] = ev_ticks
            with jax.named_scope("blades/forge"):
                updates = adv.on_updates_ready(
                    updates, ev_malicious, k_adv,
                    aggregator=fed_round.server.aggregator,
                    global_params=state.server.params,
                    **forge_kwargs,
                )
        trusted_update = fed_round.compute_trusted_update(
            state.server.params, jax.random.fold_in(k_agg, 1))
        if forensics:
            # Non-destructive lane-health probe at the same pre-aggregate
            # point the sync round takes it (post-corruption, post-forge:
            # what the server is about to judge).
            healthy = jnp.isfinite(updates).all(axis=-1)
        diag = None
        with jax.named_scope("blades/aggregate"):
            if forensics:
                server, agg, diag = fed_round.server.step_buffered_diag(
                    state.server, updates, staleness=ev_stale, key=k_agg,
                    trusted_update=trusted_update, schedule=weight_schedule,
                    power=weight_power, cutoff=weight_cutoff,
                )
            else:
                server, agg = fed_round.server.step_buffered(
                    state.server, updates, staleness=ev_stale, key=k_agg,
                    trusted_update=trusted_update, schedule=weight_schedule,
                    power=weight_power, cutoff=weight_cutoff,
                )
        ravel, _, _ = ravel_fn(server.params)
        hist = jnp.concatenate([ravel(server.params)[None], hist[:-1]],
                               axis=0)
        if windowed_state:
            client_opt = new_opt  # (K, ...): the engine scatters it back
        else:
            client_opt = jax.tree.map(
                lambda full, upd: full.at[ev_clients].set(upd),
                state.client_opt, new_opt,
            )
        benign = ((~ev_malicious) & (~ev_corrupt)).astype(jnp.float32)
        train_loss = (losses * benign).sum() / jnp.maximum(benign.sum(), 1.0)
        metrics = {
            "train_loss": train_loss,
            # Norms of the delivered rows (pre-weighting: the discount is
            # aggregation geometry, not client behavior).
            "update_norm_mean": jnp.linalg.norm(updates, axis=1).mean(),
            "agg_norm": jnp.linalg.norm(agg),
            "round": server.round,
        }
        if forensics:
            from blades_tpu.obs.forensics import detection_metrics

            # Cohort-shaped forensics: lane i diagnoses registered
            # client ev_clients[i].  Same "lane_" bundle contract as the
            # sync round (f32 for uniform scan stacking); the driver
            # pairs it with the event id-vector.
            metrics.update(detection_metrics(diag["benign_mask"],
                                             ev_malicious))
            metrics["num_unhealthy"] = (~healthy).sum()
            metrics["lane_benign_mask"] = diag["benign_mask"].astype(
                jnp.float32)
            metrics["lane_scores"] = diag["scores"].astype(jnp.float32)
            metrics["lane_healthy"] = healthy.astype(jnp.float32)
            metrics["lane_update_norms"] = jnp.linalg.norm(
                updates, axis=1).astype(jnp.float32)
        return RoundState(
            server=server, client_opt=client_opt,
            stale=getattr(state, "stale", None),
            residual=getattr(state, "residual", None),
            arrivals=hist,
            cohort=getattr(state, "cohort", None),
        ), metrics

    return cycle

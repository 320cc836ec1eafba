"""Multi-chip drivers of the FedRound program.

Two equivalent formulations of "shard clients over ICI, gather updates,
aggregate replicated" (SURVEY.md §7.2 step 5):

- :func:`sharded_step` — GSPMD.  The round function is already pure array
  code with a leading client axis; annotating in/out shardings lets XLA's
  partitioner place the ``all_gather`` that materialises the ``(n, d)``
  update matrix for the robust aggregator and keep everything else local.
  This is the production path: fewest constraints, compiler-fused.
- :func:`shard_map_step` — explicit per-device program with a hand-placed
  ``jax.lax.all_gather`` over the ``clients`` axis, mirroring what GSPMD
  derives; kept as the controlled/teachable formulation and as the escape
  hatch when collective placement must be pinned.

Both replace the reference's per-round "weights cross the wire" Ray hop
(ref: fllib/core/execution/worker_group.py:74-83): here the global params
are *born replicated*, and only the ``(n_local, d)`` update shards cross
ICI, once per round.
"""

from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from blades_tpu.core.round import FedRound, RoundState
from blades_tpu.core.server import ServerState
from blades_tpu.data.sampler import sample_client_batches
from blades_tpu.ops.pallas_select import auto_partitioned
from blades_tpu.parallel.mesh import (
    CLIENTS_AXIS,
    client_axis_sharding,
    replicated_sharding,
)


def _state_shardings(mesh: Mesh) -> RoundState:
    """A RoundState-shaped pytree-prefix of shardings: server replicated,
    client-stacked leaves sharded."""
    return RoundState(
        server=replicated_sharding(mesh), client_opt=client_axis_sharding(mesh)
    )


def _gspmd_traced(fn: Callable) -> Callable:
    """``fn`` traced under :func:`~blades_tpu.ops.pallas_select.
    auto_partitioned`: GSPMD cannot partition a Mosaic custom call, so
    the round's aggregators must trace their ``jnp`` paths here."""

    @wraps(fn)
    def traced(*args, **kwargs):
        with auto_partitioned():
            return fn(*args, **kwargs)

    return traced


def sharded_step(fr: FedRound, mesh: Mesh, donate: bool = True) -> Callable:
    """jit ``fr.step`` with GSPMD shardings over the client mesh axis.

    Returns ``step(state, x, y, lengths, malicious, key) -> (state, metrics)``
    with donated input state (buffers reused across rounds).
    """
    cs = client_axis_sharding(mesh)
    rep = replicated_sharding(mesh)
    st = _state_shardings(mesh)
    return jax.jit(
        _gspmd_traced(fr.step),
        in_shardings=(st, cs, cs, cs, cs, rep),
        out_shardings=(st, rep),
        donate_argnums=(0,) if donate else (),
    )


def sharded_evaluate(fr: FedRound, mesh: Mesh) -> Callable:
    cs = client_axis_sharding(mesh)
    rep = replicated_sharding(mesh)
    st = _state_shardings(mesh)
    return jax.jit(
        fr.evaluate, in_shardings=(st, cs, cs, cs), out_shardings=rep
    )


def shard_map_step(fr: FedRound, mesh: Mesh) -> Callable:
    """Explicit shard_map round: per-device local training on the device's
    client shard, one tiled ``all_gather`` of the update rows, replicated
    aggregation + server step.

    Same signature and semantics as :func:`sharded_step` (up to RNG: batch
    keys are folded per-device here, so draws differ from the GSPMD path —
    both are deterministic per seed).
    """
    axis = CLIENTS_AXIS
    state_spec = RoundState(server=P(), client_opt=P(axis))
    data_spec = P(axis)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(state_spec, data_spec, data_spec, data_spec, data_spec, P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    def _step(state: RoundState, data_x, data_y, lengths, malicious, key):
        n_local = data_x.shape[0]
        # Replicated split first, then a per-device fold of the sampling/
        # training keys — the adversary/aggregator/DP keys stay distinct
        # streams (no reuse of any client's key).
        k_local, k_adv, k_agg, k_dp = jax.random.split(key, 4)
        dev_key = jax.random.fold_in(k_local, lax.axis_index(axis))
        k_sample, k_train = jax.random.split(dev_key)

        bx, by = sample_client_batches(
            k_sample, data_x, data_y, lengths, fr.batch_size, fr.num_batches_per_round
        )
        hooks = fr._hooks()
        client_keys = jax.random.split(k_train, n_local)

        upd_local, client_opt, losses_local, _ = fr.task.local_round_batched(
            state.server.params, state.client_opt, bx, by, client_keys,
            malicious, *hooks,
        )

        upd_local = fr.apply_dp(
            upd_local, jax.random.fold_in(k_dp, lax.axis_index(axis))
        )

        # The one ICI collective of the round: materialise (n, d) everywhere.
        updates = lax.all_gather(upd_local, axis, axis=0, tiled=True)
        mal_all = lax.all_gather(malicious, axis, axis=0, tiled=True)
        losses = lax.all_gather(losses_local, axis, axis=0, tiled=True)
        # Drop ghost (padding) lanes — see FedRound.num_clients.
        k = fr.num_clients
        if k is not None and k < updates.shape[0]:
            updates, mal_all, losses = updates[:k], mal_all[:k], losses[:k]
        healthy = None
        if fr.health_check:
            from blades_tpu.core.health import sanitize_updates

            updates, healthy = sanitize_updates(updates)

        if fr.adversary is not None and hasattr(fr.adversary, "on_updates_ready"):
            updates = fr.adversary.on_updates_ready(
                updates, mal_all, k_adv,
                aggregator=fr.server.aggregator,
                global_params=state.server.params,
            )

        trusted_update = fr.compute_trusted_update(
            state.server.params, jax.random.fold_in(k_agg, 1)
        )
        server, agg = fr.server.step(
            state.server, updates, key=k_agg, trusted_update=trusted_update
        )
        benign = (~mal_all).astype(jnp.float32)
        train_loss = (losses * benign).sum() / jnp.maximum(benign.sum(), 1.0)
        metrics = {
            "train_loss": train_loss,
            "update_norm_mean": jnp.linalg.norm(updates, axis=1).mean(),
            "agg_norm": jnp.linalg.norm(agg),
            "round": server.round,
        }
        if fr.health_check:
            from blades_tpu.core.health import guard_server_state

            ok = jnp.isfinite(agg).all()
            server = guard_server_state(ok, server, state.server)
            metrics["num_unhealthy"] = (~healthy).sum()
            metrics["round_ok"] = ok
        return RoundState(server=server, client_opt=client_opt), metrics

    return jax.jit(_step)

"""Decentralized gossip round: peer-graph federation, no central server.

The FIFTH round path (after dense / streamed / dsharded / hier), and the
first with no coordinator: every node holds its OWN params replica,
trains locally, exchanges models with its graph neighborhood
(:mod:`blades_tpu.topology.graph`), robust-aggregates its neighbors'
updates with the per-node geometry of the existing aggregator suite, and
mixes params with doubly-stochastic gossip weights — one jitted
``shard_map`` program per round over the 1-D clients mesh, each chip
advancing its block of node replicas.

Round anatomy (all inside one trace)::

    train    θ_i --local rounds--> u_i                 (per node, vmapped)
    gather   all_gather u, ravel(θ), losses            (counted ICI)
    forge    dense-order health -> DP -> adversary     (replicated)
    select   per-node (k1, d) neighborhood matrices    (static slot tables)
    mix      θ̄_i = θ_i + Σ_s w[i,s] (θ_nbr − θ_i)     (deviation form)
    agg      per-node robust aggregate + optimizer     (vmapped server step)

RNG discipline — identical to :mod:`blades_tpu.parallel.hier`: the round
key splits 5 ways globally, per-client keys split to the TRUE count,
padded, sliced per chip.  Every node therefore draws the same batches
and local rounds as the single-chip dense program; on the COMPLETE graph
each node's neighborhood slots are ``0..n-1`` in ascending global order
(:meth:`TopologyConfig.neighbor_tables`), so its matrix IS the dense
matrix, deviation-form mixing over identical replicas is exactly the
identity, and complete-graph + Mean is pinned **bit-identical** to
centralized FedAvg at tolerance ZERO (tests/test_topology.py).

Threat model: update-forging adversaries run in the same dense order and
see the full matrix (omniscience convention); a ``topology_scoped``
adversary (:mod:`blades_tpu.adversaries.topology_attacks`) additionally
restricts WHICH receivers see forged rows — per-receiver matrices via a
static forged/clean row-select, out-edge poisoning and eclipse targeting.

Partition tolerance (``faults=`` with a dropout process): symmetric edge
dropout realized purely in ``(fault_seed, round)``
(``fold_in(round_key, EDGE_FOLD)``), dropped edges zero their mixing
weight and are replaced by the node's OWN row in its matrix; a node
whose live neighborhood falls below its aggregator's breakdown bound
(:func:`blades_tpu.ops.aggregators.breakdown_min_rows`) degrades LOUDLY
to self-trust (aggregate := own update) and is counted in the
``num_partitioned_nodes`` metric.  ``faults.inject`` is never called:
node-lane dropout/stragglers/corruption are server-path processes.

ICI accounting: every collective is counted on the
:class:`~blades_tpu.parallel.streamed_geometry.PassRecorder` and the
totals reconcile event-by-event against
:func:`blades_tpu.parallel.comm_model.gossip_round_volumes` in both
directions; the driver stamps the recorder's total into every row as
``gossip_ici_bytes``, like ``ici_bytes`` on the hier path.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from blades_tpu.core.round import FedRound, RoundState
from blades_tpu.core.server import ServerState
from blades_tpu.data.sampler import sample_client_batches_with_keys
from blades_tpu.ops.aggregators import BREAKDOWN_MIN_ROWS
from blades_tpu.parallel.mesh import (
    CLIENTS_AXIS,
    D_AXIS,
    client_axis_sharding,
    pad_to_multiple,
)
from blades_tpu.parallel.streamed_geometry import PassRecorder
from blades_tpu.topology.graph import TopologyConfig
from blades_tpu.utils.tree import ravel_fn

#: Fold applied to the fault round key for the edge-dropout draw — a
#: dedicated stream so node-lane fault processes (server paths) and edge
#: faults (this path) never alias even under the same fault seed.
EDGE_FOLD = 0xED6E


def _check_supported(fr: FedRound, topo: TopologyConfig, mesh: Mesh) -> None:
    axes = dict(mesh.shape)
    if int(axes.get(D_AXIS, 1)) != 1:
        raise ValueError(
            "gossip × 2-D mesh_shape is unsupported — the gossip round "
            "shards nodes over the 1-D clients mesh; drop mesh_shape")
    if fr.packing is not None:
        raise ValueError("gossip × packing is unsupported — resolve "
                         "packing off for the gossip path")
    if fr.codec is not None:
        raise ValueError("gossip × codec is unsupported — the wire codec "
                         "runs on server-bound updates, which do not "
                         "exist here")
    if fr.agg_domain != "f32":
        raise ValueError(
            f"gossip × agg_domain={fr.agg_domain!r} is unsupported — "
            "per-node neighborhood aggregation is f32-domain only")
    if fr.stateless_clients:
        raise ValueError("gossip × stateless clients (window=0) is "
                         "unsupported")
    if fr.forensics:
        raise ValueError("gossip × forensics is unsupported — per-lane "
                         "diagnostics assume the single server matrix")
    if fr.faults is not None:
        if fr.faults.needs_stale_buffer:
            raise ValueError(
                "gossip × straggler faults is unsupported — the stale "
                "ring buffer is a server-path process; gossip faults are "
                "EDGE dropout (use dropout_rate/dropout_schedule)")
        if fr.faults.corrupt_rate > 0.0:
            raise ValueError(
                "gossip × corruption faults is unsupported — lane "
                "corruption models server-bound transfers; gossip "
                "faults are EDGE dropout")
    if fr.num_clients is not None and int(fr.num_clients) != topo.num_nodes:
        raise ValueError(
            f"topology num_nodes={topo.num_nodes} != num_clients="
            f"{fr.num_clients}: on the gossip path every client IS a "
            "node — size the topology to the federation")
    k1 = topo.neighbor_tables().nbr_idx.shape[1]
    name = fr.server.aggregator.name
    if name in BREAKDOWN_MIN_ROWS:
        a, b = BREAKDOWN_MIN_ROWS[name]
        f_cfg = int(getattr(fr.server.aggregator, "num_byzantine", 0) or 0)
        need = a * f_cfg + b
        if need > k1:
            raise ValueError(
                f"gossip × {name}(num_byzantine={f_cfg}) needs "
                f"neighborhood matrices of >= {need} rows, but graph="
                f"{topo.graph!r} gives max closed-neighborhood size "
                f"{k1} — densify the graph (kregular with larger k, "
                "complete) or pick an aggregator with a smaller "
                "breakdown bound")


def _degradation_bound(fr: FedRound) -> Tuple[int, int]:
    """Static ``(a, b)`` of the aggregator's breakdown line ``a*f + b``
    (self-trust below it); unknown aggregators never degrade."""
    return BREAKDOWN_MIN_ROWS.get(fr.server.aggregator.name, (0, 1))


def gossip_step(
    fr: FedRound,
    mesh: Mesh,
    topo: TopologyConfig,
    recorder: Optional[PassRecorder] = None,
) -> Callable:
    """Gossip shard_map round over the 1-D ``(clients,)`` mesh.

    Returns ``(step, recorder)`` where ``step(state, x, y, lengths,
    malicious, key) -> (state, metrics)``: the STACKED per-node server
    state (leading axis ``n_pad``) and client state shard ``P(clients)``
    (:func:`gossip_federation` builds the placement), ``malicious``
    REPLICATED and UNPADDED, key replicated.  Metrics gain the
    consensus/partition sensors; ``recorder`` holds the round's
    ``ici_bytes`` and the per-collective ``ici_events`` for
    reconciliation against the comm model.
    """
    _check_supported(fr, topo, mesh)
    rec = recorder if recorder is not None else PassRecorder()
    c = int(dict(mesh.shape)[CLIENTS_AXIS])
    tabs = topo.neighbor_tables()
    n_real = topo.num_nodes
    k1 = tabs.nbr_idx.shape[1]
    a_bd, b_bd = _degradation_bound(fr)
    adv = fr.adversary
    topo_scoped = getattr(adv, "topology_scoped", False)
    if topo_scoped:
        recv_np = adv.receiver_mask(topo.adjacency())
    else:
        # Non-topology adversaries broadcast: every receiver sees the
        # forged matrix — exactly the dense threat model, which is what
        # keeps the complete-graph round bit-identical to centralized.
        recv_np = np.ones((n_real, n_real), bool)

    state_spec = RoundState(server=P(CLIENTS_AXIS), client_opt=P(CLIENTS_AXIS))
    data_spec = P(CLIENTS_AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(state_spec, data_spec, data_spec, data_spec, P(), P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    def _step(state: RoundState, data_x, data_y, lengths, malicious, key):
        n_local = data_x.shape[0]
        n_pad = c * n_local
        if n_real > n_pad:
            raise ValueError(
                f"topology num_nodes={n_real} incompatible with {c} "
                f"chips × {n_local} lanes")

        # Static slot tables, padded to the mesh-padded node count: a
        # ghost node's slots all point at itself with zero weight.
        ghost = n_pad - n_real
        nbr_np = tabs.nbr_idx
        valid_np, w_np = tabs.valid, tabs.w_slot
        recv_full = recv_np
        if ghost:
            gh = np.repeat(np.arange(n_real, n_pad, dtype=np.int32)[:, None],
                           k1, axis=1)
            nbr_np = np.concatenate([nbr_np, gh], axis=0)
            valid_np = np.concatenate(
                [valid_np, np.zeros((ghost, k1), bool)], axis=0)
            w_np = np.concatenate(
                [w_np, np.zeros((ghost, k1), np.float32)], axis=0)
        recv_full = np.zeros((n_pad, n_pad), bool)
        recv_full[:n_real, :n_real] = recv_np
        nbr_all = jnp.asarray(nbr_np)
        valid_all = jnp.asarray(valid_np)
        w_all = jnp.asarray(w_np)
        recv_all = jnp.asarray(recv_full)

        # DENSE key discipline (see blades_tpu/parallel/hier.py): global
        # 5-way split, per-client keys split to the TRUE count, padded,
        # sliced per chip.
        k_sample, k_train, k_adv, k_agg, k_dp = jax.random.split(key, 5)
        sample_keys = jax.random.split(k_sample, n_real)
        train_keys = jax.random.split(k_train, n_real)
        if ghost:
            sample_keys = jnp.pad(sample_keys, ((0, ghost), (0, 0)))
            train_keys = jnp.pad(train_keys, ((0, ghost), (0, 0)))
        start = lax.axis_index(CLIENTS_AXIS) * n_local
        local_sample = lax.dynamic_slice_in_dim(sample_keys, start, n_local, 0)
        local_train = lax.dynamic_slice_in_dim(train_keys, start, n_local, 0)
        mal_pad = jnp.pad(malicious, (0, ghost)) if ghost else malicious
        mal_local = lax.dynamic_slice_in_dim(mal_pad, start, n_local, 0)
        gidx = start + jnp.arange(n_local)

        with jax.named_scope("blades/sample"):
            bx, by = sample_client_batches_with_keys(
                local_sample, data_x, data_y, lengths,
                fr.batch_size, fr.num_batches_per_round,
            )
        hooks = fr._hooks()
        srv = state.server  # stacked ServerState, leading axis n_local
        example = jax.tree.map(lambda p: p[0], srv.params)
        ravel, unravel, _d = ravel_fn(example)

        # Per-node local training: unlike every server path, params are
        # MAPPED — each node trains from its own replica.
        def one_node(p, o, cbx, cby, ck, m):
            return fr.task.local_round(p, o, cbx, cby, ck, m, *hooks)[:3]

        with jax.named_scope("blades/step"):
            upd_local, client_opt, losses_local = jax.vmap(one_node)(
                srv.params, state.client_opt, bx, by, local_train, mal_local)
        d_full = upd_local.shape[1]
        th_local = jax.vmap(ravel)(srv.params)

        # Neighborhood exchange: the ONLY collectives of the round, all
        # counted with the comm-model (kind, payload) vocabulary.
        with jax.named_scope("blades/gather"):
            updates = lax.all_gather(upd_local, CLIENTS_AXIS, axis=0,
                                     tiled=True)
            rec.count_ici("updates_gather", "all_gather", n_pad * d_full * 4, c)
            theta = lax.all_gather(th_local, CLIENTS_AXIS, axis=0, tiled=True)
            rec.count_ici("params_gather", "all_gather", n_pad * d_full * 4, c)
            losses = lax.all_gather(losses_local, CLIENTS_AXIS, axis=0,
                                    tiled=True)
            rec.count_ici("losses_gather", "all_gather", n_pad * 4, c)

        # Replicated dense-order preprocessing over the REAL rows:
        # health -> DP -> forge, exactly finish_dense's sequence.
        u_r = updates[:n_real]
        healthy = None
        if fr.health_check:
            from blades_tpu.core.health import sanitize_updates

            u_r, healthy = sanitize_updates(u_r)
        u_r = fr.apply_dp(u_r, k_dp)
        clean = u_r
        forged = clean
        if adv is not None and hasattr(adv, "on_updates_ready"):
            with jax.named_scope("blades/forge"):
                forged = adv.on_updates_ready(
                    u_r, malicious, k_adv,
                    aggregator=fr.server.aggregator,
                    global_params=unravel(theta[0]),
                )
        zpad = ((0, ghost), (0, 0))
        clean_pad = jnp.pad(clean, zpad) if ghost else clean
        forged_pad = jnp.pad(forged, zpad) if ghost else forged

        # This chip's slice of the static tables.
        nbr_c = lax.dynamic_slice_in_dim(nbr_all, start, n_local, 0)
        valid_c = lax.dynamic_slice_in_dim(valid_all, start, n_local, 0)
        w_c = lax.dynamic_slice_in_dim(w_all, start, n_local, 0)
        recv_c = lax.dynamic_slice_in_dim(recv_all, start, n_local, 0)
        is_self = nbr_c == gidx[:, None]

        # Per-receiver neighborhood matrices: slot s of node i holds the
        # FORGED row of neighbor j = nbr[i, s] iff the adversary's edge
        # reaches this receiver, else j's clean row (identical for
        # benign j).  Peer rows may only be read here, through the
        # counted gather above (lint: topologydiscipline).
        def node_rows(nb, rrow):
            sel = jnp.take(rrow, nb)
            return jnp.where(sel[:, None], jnp.take(forged_pad, nb, axis=0),
                             jnp.take(clean_pad, nb, axis=0))

        with jax.named_scope("blades/select"):
            mat = jax.vmap(node_rows)(nbr_c, recv_c)  # (n_local, k1, d)

        degraded = None
        w_eff = w_c
        if fr.faults is not None:
            with jax.named_scope("blades/edge_faults"):
                # Symmetric edge dropout, pure in (fault_seed, round):
                # u_sym = min(u, u.T) keeps the realization symmetric
                # (a partitioned link is dead in both directions).
                round0 = srv.round[0]
                ek = jax.random.fold_in(fr.faults.round_key(round0),
                                        EDGE_FOLD)
                u = jax.random.uniform(ek, (n_real, n_real))
                drop_r = jnp.minimum(u, u.T) < fr.faults.dropout_rate_at(
                    round0)
                drop_full = jnp.zeros((n_pad, n_pad), bool)
                drop_full = drop_full.at[:n_real, :n_real].set(drop_r)
                drop_c = lax.dynamic_slice_in_dim(drop_full, start,
                                                  n_local, 0)
                dropped = jax.vmap(jnp.take)(drop_c, nbr_c)
                live = valid_c & (is_self | ~dropped)
                # Dead slots: zero mixing weight, own row in the matrix
                # (the static-shape analogue of a missing neighbor).
                w_eff = jnp.where(live, w_c, 0.0)
                own = lax.dynamic_slice_in_dim(clean_pad, start, n_local, 0)
                mat = jnp.where(live[:, :, None], mat, own[:, None, :])
                # Loud per-node degradation: live rows below the
                # aggregator's breakdown line a*f_i + b -> self-trust.
                mal_nbr = jax.vmap(jnp.take)(
                    jnp.broadcast_to(mal_pad, (n_local, n_pad)), nbr_c)
                f_i = (mal_nbr & live).sum(axis=1)
                degraded = live.sum(axis=1) < a_bd * f_i + b_bd

        # Gossip mixing in deviation form on the ROUND-INPUT params:
        # exact identity (up to +0.0) when all neighbor deviations are
        # bitwise zero — the complete-graph bit-identity mechanism.
        with jax.named_scope("blades/mix"):
            th_nbr = jax.vmap(lambda nb: jnp.take(theta, nb, axis=0))(nbr_c)
            mixed = th_local + jnp.einsum(
                "nk,nkd->nd", w_eff, th_nbr - th_local[:, None, :])

        # Per-node decomposed server step: robust aggregate over the
        # neighborhood matrix, optimizer step from the MIXED params.
        expects_trusted = getattr(fr.server.aggregator,
                                  "expects_trusted_row", False)
        k_agg1 = jax.random.fold_in(k_agg, 1)

        def node_agg(sv_i, mixed_i, mat_i):
            params_mixed = unravel(mixed_i)
            sv2 = ServerState(params=params_mixed, opt_state=sv_i.opt_state,
                              agg_state=sv_i.agg_state, round=sv_i.round)
            trusted = (fr.compute_trusted_update(params_mixed, k_agg1)
                       if expects_trusted else None)
            m2 = fr.server._with_trusted_row(mat_i, trusted)
            agg, ast = fr.server.aggregator(m2, sv2.agg_state, key=k_agg)
            return sv2, agg, ast

        with jax.named_scope("blades/aggregate"):
            sv2s, aggs, asts = jax.vmap(node_agg)(srv, mixed, mat)
        if degraded is not None:
            own_u = lax.dynamic_slice_in_dim(clean_pad, start, n_local, 0)
            aggs = jnp.where(degraded[:, None], own_u, aggs)

        def node_apply(sv_orig, sv2, agg, ast):
            new = fr.server.apply_aggregate(sv2, agg, ast)
            if fr.health_check:
                from blades_tpu.core.health import guard_server_state

                ok = jnp.isfinite(agg).all()
                # Fallback to the PRE-mix replica: a bad round leaves
                # the node exactly where it started, like dense.
                new = guard_server_state(ok, new, sv_orig)
            return new

        new_srv = jax.vmap(node_apply)(srv, sv2s, aggs, asts)

        aggn_local = jax.vmap(jnp.linalg.norm)(aggs)
        aggn = lax.all_gather(aggn_local, CLIENTS_AXIS, axis=0, tiled=True)
        rec.count_ici("aggnorm_gather", "all_gather", n_pad * 4, c)

        benign = (~malicious).astype(jnp.float32)
        losses_r = losses[:n_real]
        th_r = theta[:n_real]
        gram = th_r @ th_r.T
        sq = (jnp.diag(gram)[:, None] + jnp.diag(gram)[None, :] - 2.0 * gram)
        metrics = {
            "train_loss": (losses_r * benign).sum()
            / jnp.maximum(benign.sum(), 1.0),
            "update_norm_mean": jnp.linalg.norm(forged, axis=1).mean(),
            "agg_norm": aggn[0],
            "round": new_srv.round[0],
            "consensus_dist": jnp.sqrt(jnp.maximum(sq, 0.0).max()),
        }
        if degraded is not None:
            part_local = (degraded & (gidx < n_real)).sum().astype(jnp.int32)
            metrics["num_partitioned_nodes"] = lax.psum(part_local,
                                                        CLIENTS_AXIS)
            rec.count_ici("partitioned_psum", "psum", 4, c)
        else:
            metrics["num_partitioned_nodes"] = jnp.int32(0)
        if fr.health_check:
            metrics["num_unhealthy"] = (~healthy).sum()
            metrics["round_ok"] = jnp.isfinite(aggn[:n_real]).all()
        new_state = RoundState(server=new_srv, client_opt=client_opt,
                               arrivals=getattr(state, "arrivals", None),
                               cohort=getattr(state, "cohort", None))
        return new_state, metrics

    return jax.jit(_step), rec


def gossip_federation(mesh: Mesh, round_state: RoundState, data_arrays):
    """Place a federation onto the mesh for the gossip path.

    Unlike :func:`~blades_tpu.parallel.mesh.shard_federation` (which
    REPLICATES the single server), the server state is STACKED to one
    replica per mesh-padded node (``n_pad = ceil(n / c) * c``) and
    sharded on the leading node axis alongside the client state and
    data — every chip owns a contiguous block of node replicas.  Ghost
    replicas train on empty shards and gossip with zero weight; the
    round program slices them away from every metric.
    """
    cs = client_axis_sharding(mesh)
    n_dev = mesh.shape[CLIENTS_AXIS]
    # Node count from the data (client_opt may be leafless, e.g. plain
    # SGD client optimizers).
    n = data_arrays[0].shape[0]
    n_pad = -(-n // n_dev) * n_dev
    server = jax.tree.map(
        lambda a: jax.device_put(
            jnp.broadcast_to(a[None], (n_pad,) + jnp.shape(a)), cs),
        round_state.server,
    )
    client_opt = jax.tree.map(
        lambda a: jax.device_put(pad_to_multiple(a, n_dev), cs),
        round_state.client_opt,
    )
    state = dataclasses.replace(round_state, server=server,
                                client_opt=client_opt)
    data = tuple(
        jax.device_put(pad_to_multiple(a, n_dev), cs) for a in data_arrays
    )
    return state, data


def reshard_gossip_state(mesh: Mesh, round_state: RoundState) -> RoundState:
    """Re-place a checkpointed gossip state (per-node server stack
    ALREADY in the leading axis) onto the mesh — the resume half of
    :func:`gossip_federation`."""
    cs = client_axis_sharding(mesh)
    return dataclasses.replace(
        round_state,
        server=jax.device_put(round_state.server, cs),
        client_opt=jax.device_put(round_state.client_opt, cs),
    )


def gossip_evaluate(fr: FedRound) -> Callable:
    """Evaluation for gossip states: score the node-0 head replica with
    the standard dense evaluation — on a healthy (un-partitioned) run
    consensus makes every head equivalent, and ``consensus_dist`` is the
    sensor that says when that assumption broke."""

    @jax.jit
    def _evaluate(state: RoundState, test_x, test_y, lengths):
        head = jax.tree.map(lambda a: a[0], state.server)
        st = dataclasses.replace(state, server=head)
        return fr.evaluate(st, test_x, test_y, lengths)

    return _evaluate

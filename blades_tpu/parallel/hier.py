"""Hierarchical pod-scale round: per-shard pre-aggregation + ring gather.

The fourth round path (after dense / streamed / dsharded).  On a 2-D
``(clients, d)`` mesh (:func:`blades_tpu.parallel.mesh.make_mesh` with
``mesh_shape=(c, dd)``), client blocks train data-parallel per chip, a
robust pre-aggregation stage (:mod:`blades_tpu.ops.preagg` — bucketing or
nearest-neighbor mixing, ByzFL arXiv:2505.24802) reduces each chip's local
``(n_local, d)`` update block to ``m`` representatives, and the existing
robust aggregators run replicated over the gathered ``(c*m, d)`` matrix —
one tiled ring all-gather along ``clients`` (two-phase over the ``d`` torus
axis when ``dd > 1``) instead of shipping the full ``(n, d)`` matrix.

RNG discipline — the load-bearing design decision: unlike
:func:`~blades_tpu.parallel.sharded.shard_map_step` (which folds batch keys
per device), this path mirrors the DENSE stream exactly.  The round key
splits 5 ways globally, the per-client sample/train keys are split to the
TRUE client count, padded, and each chip takes its contiguous slice — so
every real lane draws the same batches and the same local round as the
single-chip dense program, and with ``bucket_size=1`` (identity pre-agg)
the whole round is **bit-identical** to ``FedRound.step`` on one chip.
That is the pinned tolerance of the robustness-grid acceptance test: zero.

ICI accounting: every collective the traced program issues is counted on
the :class:`~blades_tpu.parallel.streamed_geometry.PassRecorder` with the
same ``(kind, payload)`` vocabulary as :mod:`blades_tpu.parallel.comm_model`
(ring wire factors applied per chip); the driver stamps the recorder's
``ici_bytes`` into every row next to the trace-time ``preagg_kept``
metric.  The recorder's totals reconcile event-by-event against
:func:`~blades_tpu.parallel.comm_model.hier_round_volumes` in both
directions (tests/test_hier.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from blades_tpu.core.round import FedRound, RoundState
from blades_tpu.data.sampler import sample_client_batches_with_keys
from blades_tpu.ops.preagg import (
    PREAGG_FLAVORS,
    bucket_count,
    bucket_representatives,
    nnm_representatives,
)
from blades_tpu.parallel.mesh import CLIENTS_AXIS, D_AXIS
from blades_tpu.parallel.streamed_geometry import PassRecorder


def hier_kept_counts(n_real: int, n_local: int, c: int, bucket_size: int):
    """Per-chip real-representative counts under bucketing.

    Chip ``i`` owns lanes ``[i*n_local, (i+1)*n_local)``; ghosts are the
    contiguous global tail, so its real-lane count is
    ``r_i = clip(n_real - i*n_local, 0, n_local)`` and it emits
    ``ceil(r_i / b)`` real representatives — all static, so the gathered
    matrix's real rows form a static prefix of length ``sum(...)``.
    """
    b = int(bucket_size)
    return [
        -(-min(max(int(n_real) - i * int(n_local), 0), int(n_local)) // b)
        for i in range(int(c))
    ]


def _check_supported(fr: FedRound, preagg: str, bucket_size: int) -> None:
    if preagg not in PREAGG_FLAVORS:
        raise ValueError(f"unknown preagg flavor {preagg!r}; use one of "
                         f"{PREAGG_FLAVORS}")
    if bucket_size < 1:
        raise ValueError(f"bucket_size must be >= 1, got {bucket_size}")
    if fr.packing is not None:
        raise ValueError("hier × packing is unsupported — resolve packing "
                         "off for the hierarchical path")
    if fr.codec is not None:
        raise ValueError("hier × codec is unsupported — the wire codec "
                         "runs on per-lane updates, which never leave "
                         "their chip here")
    if fr.stateless_clients:
        raise ValueError("hier × stateless clients (window=0) is "
                         "unsupported")
    if fr.faults is not None and fr.faults.needs_stale_buffer:
        raise ValueError("hier × straggler stale-buffer faults is "
                         "unsupported — use dropout/corruption processes")


def hier_step(
    fr: FedRound,
    mesh: Mesh,
    preagg: str = "bucket",
    bucket_size: int = 1,
    recorder: Optional[PassRecorder] = None,
) -> Callable:
    """Hierarchical shard_map round over a ``(clients[, d])`` mesh.

    Returns ``(step, recorder)`` where ``step(state, x, y, lengths,
    malicious, key) -> (state, metrics)``: data/client state sharded
    ``P(clients)``, ``malicious`` REPLICATED and UNPADDED
    (``(num_clients,)`` — the program pads it internally), key
    replicated.  Metrics gain a trace-time ``preagg_kept`` stamp;
    ``recorder`` holds the round's ``ici_bytes`` and the per-collective
    ``ici_events`` for reconciliation against the comm model.
    """
    _check_supported(fr, preagg, bucket_size)
    rec = recorder if recorder is not None else PassRecorder()
    axes = dict(mesh.shape)
    c = int(axes[CLIENTS_AXIS])
    dd = int(axes.get(D_AXIS, 1))
    b = int(bucket_size)

    state_spec = RoundState(server=P(), client_opt=P(CLIENTS_AXIS))
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
        n_real = int(fr.num_clients) if fr.num_clients is not None else n_pad
        if n_real > n_pad or n_real < 1:
            raise ValueError(
                f"num_clients={n_real} incompatible with {c} chips × "
                f"{n_local} lanes")
        reals = [min(max(n_real - i * n_local, 0), n_local)
                 for i in range(c)]
        if preagg == "nnm":
            m = n_local
            kept = n_real
            rmin = min(r for r in reals if r > 0)
            if rmin < b:
                raise ValueError(
                    f"nnm bucket_size={b} exceeds the smallest chip-local "
                    f"real-lane count ({rmin}) — shrink bucket_size or "
                    f"rebalance mesh_shape")
        else:
            m = bucket_count(n_local, b)
            kept = sum(hier_kept_counts(n_real, n_local, c, b))
        if fr.faults is not None and kept != n_real:
            raise ValueError(
                "hier × faults needs an identity-height pre-aggregation "
                f"(kept={kept} != num_clients={n_real}) — set "
                "bucket_size=1 or disable the fault processes")

        # DENSE key discipline: global 5-way split, per-client keys split
        # to the TRUE count, padded, sliced per chip — see module docstring.
        k_sample, k_train, k_adv, k_agg, k_dp = jax.random.split(key, 5)
        sample_keys = jax.random.split(k_sample, n_real)
        train_keys = jax.random.split(k_train, n_real)
        pad = n_pad - n_real
        if pad:
            sample_keys = jnp.pad(sample_keys, ((0, pad), (0, 0)))
            train_keys = jnp.pad(train_keys, ((0, pad), (0, 0)))
        start = lax.axis_index(CLIENTS_AXIS) * n_local
        local_sample = lax.dynamic_slice_in_dim(sample_keys, start, n_local, 0)
        local_train = lax.dynamic_slice_in_dim(train_keys, start, n_local, 0)
        mal_pad = jnp.pad(malicious, (0, pad)) if pad else malicious
        mal_local = lax.dynamic_slice_in_dim(mal_pad, start, n_local, 0)

        with jax.named_scope("blades/sample"):
            bx, by = sample_client_batches_with_keys(
                local_sample, data_x, data_y, lengths,
                fr.batch_size, fr.num_batches_per_round,
            )
        hooks = fr._hooks()
        with jax.named_scope("blades/step"):
            upd_local, client_opt, losses_local, _ = \
                fr.task.local_round_batched(
                    state.server.params, state.client_opt, bx, by,
                    local_train, mal_local, *hooks,
                )
        d_full = upd_local.shape[1]

        # Per-shard robust pre-aggregation: (n_local, d) -> (m, d).
        gidx = start + jnp.arange(n_local)
        real = gidx < n_real
        with jax.named_scope("blades/preagg"):
            if preagg == "nnm":
                reps = nnm_representatives(upd_local, real, b)
            else:
                reps = bucket_representatives(upd_local, real, b)

        # Ring collectives: gather representatives (two-phase over the d
        # torus axis when it exists) + the per-lane losses.  Payloads are
        # the comm-model TOTAL convention; the recorder applies the ring
        # wire factor per chip.
        with jax.named_scope("blades/gather"):
            if dd > 1:
                d_pad = -(-d_full // dd) * dd
                col = d_pad // dd
                reps_p = jnp.pad(reps, ((0, 0), (0, d_pad - d_full)))
                di = lax.axis_index(D_AXIS)
                reps_col = lax.dynamic_slice_in_dim(reps_p, di * col, col, 1)
                g1 = lax.all_gather(reps_col, CLIENTS_AXIS, axis=0, tiled=True)
                rec.count_ici("reps_gather_clients", "all_gather", c * m * col * 4, c)
                updates = lax.all_gather(g1, D_AXIS, axis=1, tiled=True)
                rec.count_ici("reps_gather_d", "all_gather", c * m * d_pad * 4, dd)
                updates = updates[:, :d_full]
            else:
                updates = lax.all_gather(reps, CLIENTS_AXIS, axis=0,
                                         tiled=True)
                rec.count_ici("reps_gather_clients", "all_gather",
                              c * m * d_full * 4, c)
            losses = lax.all_gather(losses_local, CLIENTS_AXIS, axis=0,
                                    tiled=True)[:n_real]
            rec.count_ici("losses_gather", "all_gather", n_pad * 4, c)
        updates = updates[:kept]

        # Representative-level malicious mask.  Bucketing: a representative
        # is malicious iff ANY bucket member is (the strongest-adversary
        # convention at bucket granularity; b=1 recovers the exact dense
        # mask).  NNM keeps matrix height, so each representative inherits
        # its center lane's flag.
        if preagg == "nnm":
            rep_mal = malicious
        else:
            per_dev = mal_pad.reshape(c, n_local)
            per_dev = jnp.pad(per_dev, ((0, 0), (0, m * b - n_local)))
            rep_mal = per_dev.reshape(c, m, b).any(axis=-1).reshape(c * m)
            rep_mal = rep_mal[:kept]

        participation = straggled = None
        stale = getattr(state, "stale", None)
        if fr.faults is not None:
            with jax.named_scope("blades/faults"):
                updates, stale, participation, straggled, _corrupted = (
                    fr.faults.inject(updates, stale, state.server.round)
                )

        new_state, metrics = fr.finish_dense(
            state, updates, client_opt, losses, rep_mal,
            k_adv, k_agg, k_dp,
            participation=participation, straggled=straggled,
            stale=stale, loss_benign=~malicious,
        )
        # Trace-time constant, the hbm_passes stamp pattern.  The wire
        # total stays on the recorder (``rec.ici_bytes``, a Python int):
        # at ResNet width it passes 2**31 bytes from ~150 clients on.
        metrics["preagg_kept"] = jnp.int32(kept)
        return new_state, metrics

    return jax.jit(_step), rec

"""The federated round as ONE pure jittable function.

This replaces the reference's entire hot loop — weight broadcast over Ray,
actor-pool scatter, object-store gather, adversary post-hook, server step
(ref: blades/algorithms/fedavg/fedavg.py:203-245) — with a single XLA
program:

    sample batches -> vmap(local_round) over clients -> adversary forge
    -> robust aggregate -> server optimizer step

Weight "sync" is a broadcast (``in_axes=None``); the update "gather" is the
stacked ``(n, d)`` matrix already on device.  Under ``shard_map`` (see
:mod:`blades_tpu.parallel`) the client axis shards over the mesh and the
gather becomes an ICI collective.

The decentralized gossip path (:mod:`blades_tpu.topology`) reuses this
same round decomposition with NO central server: each node runs
``task.local_round`` from its OWN params replica, then the per-node
neighborhood matrix feeds ``server.aggregator`` with per-node geometry —
the ``FedRound`` fields below (task, server, adversary, faults, health)
are the single source of round semantics for all five execution paths.
"""

from __future__ import annotations

import dataclasses
from collections import namedtuple
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from blades_tpu.core.callbacks import CallbackChain
from blades_tpu.core.server import Server, ServerState
from blades_tpu.core.task import (
    Task,
    identity_data_hook,
    identity_grad_hook,
    identity_round_begin_hook,
    identity_round_end_hook,
)
from blades_tpu.data.sampler import sample_client_batches

Hooks = namedtuple("Hooks", ["data", "grad", "round_begin", "round_end"])


@dataclasses.dataclass
class RoundState:
    """Full training state: replicated server + stacked per-client opt states.

    ``stale`` is the chaos layer's ``(staleness, n, d)`` stale-update ring
    buffer (row ``-1`` oldest; see :mod:`blades_tpu.faults.injector`) —
    ``None`` unless a straggler process is configured, so the pytree of a
    fault-free run carries no extra leaves and existing checkpoints /
    sharding specs are unchanged.

    ``residual`` is the comm subsystem's ``(n, d)`` error-feedback
    residual (see :mod:`blades_tpu.comm.codecs`) — the same ``None``-
    when-off discipline: only a top-k codec with error feedback adds the
    leaf, so codec-free (and identity-codec) pytrees/checkpoints are
    unchanged.

    ``arrivals`` is the buffered-async subsystem's ``(H+1, d)``
    params-history ring (see :mod:`blades_tpu.arrivals`): row ``j``
    holds the raveled global params from ``j`` versions ago, so an
    arriving client's update is computed against the version it actually
    pulled.  Same ``None``-when-off discipline — only
    ``execution="async"`` adds the leaf.

    ``cohort`` is the participation-window subsystem's ``(window,)``
    int32 vector of REGISTERED client ids (see
    :mod:`blades_tpu.state`): under a windowed state store,
    ``client_opt`` (and ``residual``) stack only the sampled cohort's
    rows and ``cohort`` records which registered clients they belong
    to; the registered-population remainder lives behind the driver's
    :class:`~blades_tpu.state.store.ClientStateStore` handle — a HOST
    object (it owns numpy arrays / memmaps and a worker thread), so
    the handle itself stays on :class:`~blades_tpu.algorithms.fedavg.
    Fedavg` with the same ``None``-when-off discipline and never
    enters this pytree.  ``cohort=None`` (every pre-window build, and
    every run without a windowed store) keeps the pytree — and
    therefore checkpoints and sharding specs — unchanged.
    """

    server: ServerState
    client_opt: Any  # pytree stacked over the client axis
    stale: Any = None
    residual: Any = None
    arrivals: Any = None
    cohort: Any = None


jax.tree_util.register_pytree_node(
    RoundState,
    # getattr: checkpoints pickled before the chaos/comm/arrivals/state
    # layers existed restore as RoundState instances without the late
    # fields.
    lambda s: ((s.server, s.client_opt, getattr(s, "stale", None),
                getattr(s, "residual", None),
                getattr(s, "arrivals", None),
                getattr(s, "cohort", None)), None),
    lambda _, c: RoundState(*c),
)


@dataclasses.dataclass(frozen=True)
class FedRound:
    """Static round config binding task, server, and (optional) adversary."""

    task: Task
    server: Server
    adversary: Any = None  # duck-typed: data_hook/grad_hook/on_updates_ready
    batch_size: int = 32
    num_batches_per_round: int = 1  # ref: algorithm_config.py:63 default 1
    # True federation size.  When the client axis is zero-padded to a mesh
    # multiple (see parallel/mesh.py shard_federation), lanes >= num_clients
    # are ghosts: they run the (harmless) local round for shape regularity
    # but are statically sliced away before forging, aggregation and
    # metrics.  None means "every lane is real".
    num_clients: Optional[int] = None
    # Differential privacy on client updates (ref: blades/clients/
    # dp_client.py:32-43): clip each update row to dp_clip_threshold, add
    # N(0, (noise_factor * clip)^2) noise.  None disables.
    dp_clip_threshold: Optional[float] = None
    dp_noise_factor: Optional[float] = None
    # Server root dataset (x, y) for trust-bootstrapped aggregators
    # (FLTrust): each round the server trains its own local round on this
    # clean data and the result becomes the trusted reference row.
    trusted_data: Optional[Tuple[jax.Array, jax.Array]] = None
    # Client callback chain (ref: fllib/clients/callbacks.py): tuple of
    # blades_tpu.core.callbacks.ClientCallback, applied to EVERY lane,
    # composing BEFORE the adversary's hooks (the reference appends the
    # attack callback last).
    client_callbacks: Tuple = ()
    # Failure detection + elastic recovery (see core/health.py): zero
    # non-finite client lanes before aggregation and skip the server
    # update when the aggregate itself is non-finite.  Adds
    # ``num_unhealthy``/``round_ok`` metrics.  Costs one extra pass over
    # the update matrix, so opt-in.
    health_check: bool = False
    # Defense forensics (obs subsystem): aggregate via the aggregator's
    # diagnose() path and emit per-lane telemetry — the benign/trim mask,
    # per-lane scores, the lane-health mask — plus Byzantine detection
    # precision/recall/FPR scored against the true malicious mask, all as
    # extra jit outputs.  False keeps the round program LITERALLY
    # unchanged (Python-level branch on static config); the diagnose()
    # aggregate shares __call__'s trace, so numerics match either way.
    forensics: bool = False
    # Chaos layer (blades_tpu/faults): a FaultInjector composing dropout /
    # straggler / lane-corruption processes inside the jitted round, with
    # participation-aware aggregation.  None (the default) keeps the round
    # program LITERALLY unchanged — bit-identical numerics (Python-level
    # branch on static config) — and a full-participation round under an
    # injector still takes the dense aggregation trace via lax.cond.
    faults: Any = None
    # Comm subsystem (blades_tpu/comm): a CodecConfig whose encode->decode
    # transform compresses the client updates inside the jitted round —
    # BEFORE fault injection and robust aggregation, so every aggregator
    # sees the quantized geometry, the adversary forges post-codec, and
    # lane corruption composes with encoded payloads.  None keeps the
    # program literally unchanged; the "identity" codec is a regression-
    # tested bit-transparent no-op.
    codec: Any = None
    # Client lane-packing (blades_tpu/parallel/packed.py): a
    # ClientPacking(pack=P) spec folds P clients into one grouped-kernel
    # vmap lane for the LOCAL round only — updates are unpacked back to
    # the dense (n, d) matrix before codecs, faults, DP, forging and
    # aggregation, so everything downstream (and RoundState itself, which
    # stays in canonical unpacked layout — checkpoints are layout-free)
    # sees exactly the geometry it sees today.  None keeps the round
    # program literally unchanged; set via FedavgConfig.resources(
    # client_packing=...), whose "auto" mode gates eligibility loudly.
    packing: Any = None
    # Aggregation domain under a codec (blades_tpu/comm): "f32" decodes
    # the wire payload to the dense f32 matrix before the defenses (the
    # bit-identical default — the pre-wire-domain program, literally);
    # "wire" keeps quantized updates PACKED (int8 + per-row scales)
    # through the defense statistics via Server.step_wire — the fused
    # traversals read one byte per coordinate, per-row scales apply
    # algebraically to the accumulated statistics, and the adversary
    # still forges post-codec: it reads the quantized-domain geometry
    # and its forged rows re-enter the same int8 wire
    # (CodecConfig.requantize_rows).  Config-time validation restricts
    # "wire" to dense single-chip rounds with a deferrable codec and
    # none of the f32-domain-only features (faults/health/forensics/DP).
    agg_domain: str = "f32"
    # Chunk width of the wire-domain statistics traversals (the streamed
    # d_chunk knob applied to the dense wire path; kernel-eligible
    # shapes take the fused pallas stripe kernel instead).
    agg_d_chunk: int = 1 << 17
    # Stateless clients (blades_tpu/state, the window=0 degenerate
    # case): every round re-initializes the per-client optimizer state
    # instead of carrying it — no per-client information persists
    # across rounds, so the participation-window store has nothing to
    # hold.  False keeps the round program literally unchanged.
    stateless_clients: bool = False

    # -- construction -------------------------------------------------------

    def init(self, key: jax.Array, num_clients: int) -> RoundState:
        params = self.task.init_params(key)
        opt0 = self.task.init_client_opt_state(params)
        client_opt = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (num_clients,) + jnp.shape(x)), opt0
        )
        stale = residual = None
        if self.faults is not None and self.faults.needs_stale_buffer:
            from blades_tpu.utils.tree import ravel_fn

            _, _, d = ravel_fn(params)
            # Buffer rows match the POST-ghost-slice matrix (true
            # federation size), the shape inject() sees.
            stale = self.faults.init_stale_buffer(
                self.num_clients or num_clients, d
            )
        if self.codec is not None and self.codec.needs_residual:
            from blades_tpu.utils.tree import ravel_fn

            _, _, d = ravel_fn(params)
            # Error-feedback residual rows also match the post-ghost-
            # slice matrix — the shape encode_decode() sees.
            residual = self.codec.init_residual(
                self.num_clients or num_clients, d
            )
        return RoundState(
            server=self.server.init(params, num_clients),
            client_opt=client_opt,
            stale=stale,
            residual=residual,
        )

    def init_windowed(self, key: jax.Array, window: int):
        """:meth:`init` for a participation-window run
        (:mod:`blades_tpu.state`): the per-client stacks are NOT
        materialised — at the registered populations the window store
        exists for (1M clients), a dense ``(n, d)`` broadcast would
        OOM before the store could ever help.  Returns ``(state,
        template)`` where ``state`` carries the server only
        (``client_opt=None`` until the first cohort is staged) and
        ``template`` is ONE client's persistent-state row
        (:func:`blades_tpu.state.store.client_state_template`) the
        store broadcasts host/disk-side.  The server's aggregator
        state is sized to ``window`` — the matrix it will actually
        aggregate every round."""
        from blades_tpu.state.store import client_state_template

        params = self.task.init_params(key)
        template = client_state_template(self, params)
        return RoundState(
            server=self.server.init(params, window), client_opt=None,
        ), template

    # -- hooks --------------------------------------------------------------

    def _hooks(self) -> Hooks:
        """Compose the client callback chain with the adversary's hooks."""
        adv_data = (
            getattr(self.adversary, "data_hook", identity_data_hook)
            if self.adversary is not None else identity_data_hook
        )
        adv_grad = (
            getattr(self.adversary, "grad_hook", identity_grad_hook)
            if self.adversary is not None else identity_grad_hook
        )
        if not self.client_callbacks:
            return Hooks(adv_data, adv_grad,
                         identity_round_begin_hook, identity_round_end_hook)
        chain = CallbackChain(tuple(self.client_callbacks))

        def data(x, y, malicious):
            x, y = chain.on_batch_begin(x, y, malicious)
            return adv_data(x, y, malicious)

        def grad(grads, malicious):
            return adv_grad(chain.on_backward_end(grads, malicious), malicious)

        return Hooks(data, grad, chain.on_round_begin, chain.on_round_end)

    # -- the round ----------------------------------------------------------

    def sample_round_batches(
        self,
        data_x: jax.Array,
        data_y: jax.Array,
        lengths: jax.Array,
        key: jax.Array,
    ) -> Tuple[jax.Array, jax.Array]:
        """The batch-sampling half of :meth:`step`, split out so a
        prefetcher (:mod:`blades_tpu.data.prefetch`) can stage round
        ``r+1``'s batches while round ``r`` computes.  Consumes the SAME
        ``k_sample`` fold of the round key as :meth:`step`, so::

            step(state, x, y, ln, mal, key)
            == step_prebatched(state, *sample_round_batches(x, y, ln, key),
                               mal, key)

        bit-for-bit (regression-tested per aggregator in
        ``tests/test_perf.py``)."""
        k_sample = jax.random.split(key, 5)[0]
        # named_scope: trace-time HLO metadata only (numerics untouched)
        # — the profiler shows this op cluster as blades/sample inside
        # whatever span dispatched the round (obs/trace.py).
        with jax.named_scope("blades/sample"):
            return sample_client_batches(
                k_sample, data_x, data_y, lengths, self.batch_size,
                self.num_batches_per_round,
            )

    def step(
        self,
        state: RoundState,
        data_x: jax.Array,
        data_y: jax.Array,
        lengths: jax.Array,
        malicious: jax.Array,
        key: jax.Array,
    ) -> Tuple[RoundState, dict]:
        """One full FL round (pure; jit/shard_map this).

        Args:
            state: current :class:`RoundState`.
            data_x/data_y/lengths: stacked padded client shards.
            malicious: ``(n,)`` bool mask (the domain fault injection).
            key: round PRNG key.
        """
        bx, by = self.sample_round_batches(data_x, data_y, lengths, key)
        return self.step_prebatched(state, bx, by, malicious, key)

    def step_prebatched(
        self,
        state: RoundState,
        bx: jax.Array,
        by: jax.Array,
        malicious: jax.Array,
        key: jax.Array,
    ) -> Tuple[RoundState, dict]:
        """:meth:`step` with the per-client batches already drawn
        (``(n, num_batches, batch, ...)``, from
        :meth:`sample_round_batches` under the same round key).  The
        round key is re-split identically and the sampling fold simply
        goes unused, so the RNG stream — and therefore every output —
        matches :meth:`step` exactly."""
        num_clients = bx.shape[0]
        k_sample, k_train, k_adv, k_agg, k_dp = jax.random.split(key, 5)
        del k_sample  # consumed by sample_round_batches
        hooks = self._hooks()
        client_keys = jax.random.split(k_train, num_clients)
        if self.stateless_clients:
            # window=0 degenerate case (blades_tpu/state): clients keep
            # no state across rounds — every lane starts from a fresh
            # optimizer init (a trace-time constant broadcast, fused by
            # XLA), and the carried stack is ignored.
            opt0 = self.task.init_client_opt_state(state.server.params)
            state = dataclasses.replace(
                state,
                client_opt=jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x, (num_clients,) + jnp.shape(x)), opt0))

        # Phase named_scopes (blades/<phase>): HLO op-name metadata for
        # the profiler/span correlation — trace-time only, numerics
        # untouched on every path (tests/test_trace.py pins this).
        if self.packing is not None:
            # Lane-packing (parallel/packed.py): P clients per grouped-
            # kernel vmap lane.  Eligibility (resolve_client_packing)
            # guarantees every hook is identity here, and the per-client
            # PRNG streams replicate the unpacked discipline exactly.
            from blades_tpu.parallel.packed import packed_local_round_batched

            with jax.named_scope("blades/step"):
                updates, client_opt, losses = packed_local_round_batched(
                    self.task, self.packing.pack, state.server.params,
                    state.client_opt, bx, by, client_keys, malicious,
                )
        else:
            with jax.named_scope("blades/step"):
                updates, client_opt, losses, _ = self.task.local_round_batched(
                    state.server.params, state.client_opt, bx, by,
                    client_keys, malicious, *hooks,
                )
        # Drop ghost (padding) lanes before anything consumes the matrix.
        k = self.num_clients
        if k is not None and k < updates.shape[0]:
            updates, losses, malicious = updates[:k], losses[:k], malicious[:k]
        # Comm subsystem (blades_tpu/comm): the simulated wire.  Encode ->
        # decode runs at the point the updates "leave the clients" —
        # before fault injection (a straggler's ring buffer then stores
        # and replays POST-codec rows; lane corruption overwrites encoded
        # payloads) and before forging (the adversary reads and exploits
        # the compressed-domain geometry every defense will see).  The
        # rounding key is a dedicated fold of the round key, so the
        # existing sample/train/adv/agg/dp streams are untouched and a
        # codec-free build stays bit-identical.
        residual = getattr(state, "residual", None)
        if self.codec is not None:
            from blades_tpu.comm.codecs import CODEC_KEY_FOLD

            codec_key = jax.random.fold_in(key, CODEC_KEY_FOLD)
            if self.agg_domain == "wire":
                # Wire-domain aggregation: the payload stays PACKED
                # (q int8, per-row scales) through forging and the
                # defense statistics — the dense f32 matrix is never
                # rebuilt.  Identity codec: the wire IS f32 (scales is
                # None), so the round falls through to the standard
                # path below, bit-identical to agg_domain="f32".
                with jax.named_scope("blades/encode"):
                    q, wire_scales, residual = self.codec.decode_deferred(
                        updates, residual, codec_key
                    )
                if wire_scales is None:
                    updates = q
                else:
                    return self._finish_wire(
                        state, q, wire_scales, residual, client_opt,
                        losses, malicious, k_adv, k_agg,
                    )
            else:
                with jax.named_scope("blades/encode"):
                    updates, residual = self.codec.encode_decode(
                        updates, residual, codec_key
                    )
        # Chaos layer (blades_tpu/faults): dropout / stragglers / lane
        # corruption, realized deterministically from (fault seed, round).
        # Runs at the point the updates "arrive at the server" — before
        # the health check, so corruption is exactly what sanitize_updates
        # must catch.  Forging runs AFTER, on the full matrix: the
        # adversary stays omniscient (it sees every locally-computed
        # update, dropped lanes' included — the strongest-adversary
        # convention of the Byzantine literature), while the SERVER only
        # ever aggregates the participating cohort.
        participation = straggled = None
        stale = getattr(state, "stale", None)
        if self.faults is not None:
            with jax.named_scope("blades/faults"):
                updates, stale, participation, straggled, _corrupted = (
                    self.faults.inject(updates, stale, state.server.round)
                )
        return self.finish_dense(
            state, updates, client_opt, losses, malicious,
            k_adv, k_agg, k_dp,
            participation=participation, straggled=straggled,
            stale=stale, residual=residual,
        )

    def finish_dense(
        self,
        state: RoundState,
        updates: jax.Array,
        client_opt,
        losses: jax.Array,
        malicious: jax.Array,
        k_adv: jax.Array,
        k_agg: jax.Array,
        k_dp: jax.Array,
        *,
        participation=None,
        straggled=None,
        stale=None,
        residual=None,
        loss_benign=None,
    ) -> Tuple[RoundState, dict]:
        """The dense aggregation tail of :meth:`step_prebatched` — health
        check, DP, adversary forge, trusted row, robust aggregate, server
        step and the metrics dict — over an already-assembled ``(n, d)``
        update matrix.  Split out so the hierarchical multi-chip round
        (:mod:`blades_tpu.parallel.hier`) can run the IDENTICAL finish
        over its gathered representative matrix: under an identity
        pre-aggregation the whole mesh round is then bit-identical to
        the single-chip dense program by construction.

        ``loss_benign`` decouples the train-loss mask from ``malicious``
        for callers whose ``updates`` rows are not 1:1 with ``losses``
        rows (hier with ``bucket_size>1``: updates are bucket
        representatives, losses stay per-lane) — ``None`` keeps the
        dense behavior (``~malicious``).
        """
        healthy = None
        if self.health_check:
            from blades_tpu.core.health import sanitize_updates

            updates, healthy = sanitize_updates(updates, participation)
        elif self.forensics:
            # Non-destructive probe of sanitize_updates' predicate at the
            # SAME point in the round (pre-DP, pre-forge), so the
            # num_unhealthy metric means the same thing whether or not
            # health_check is recovering the lanes it counts.
            healthy = jnp.isfinite(updates).all(axis=-1)
            if participation is not None:
                healthy = healthy | ~participation
        updates = self.apply_dp(updates, k_dp)

        if self.adversary is not None and hasattr(self.adversary, "on_updates_ready"):
            with jax.named_scope("blades/forge"):
                updates = self.adversary.on_updates_ready(
                    updates, malicious, k_adv,
                    aggregator=self.server.aggregator,
                    global_params=state.server.params,
                )

        trusted_update = self.compute_trusted_update(
            state.server.params, jax.random.fold_in(k_agg, 1)
        )
        diag = None
        with jax.named_scope("blades/aggregate"):
            if self.forensics:
                server, agg, diag = self.server.step_diag(
                    state.server, updates, key=k_agg,
                    trusted_update=trusted_update,
                    participation=participation,
                )
            else:
                server, agg = self.server.step(
                    state.server, updates, key=k_agg,
                    trusted_update=trusted_update,
                    participation=participation,
                )
        benign = ((~malicious) if loss_benign is None
                  else loss_benign).astype(jnp.float32)
        if participation is not None:
            # Loss and norm summaries cover the lanes that reported: a
            # dropped lane's local round ran (shape regularity) but its
            # numbers never reached the server.
            benign = benign * participation.astype(jnp.float32)
            norms = jnp.linalg.norm(updates, axis=1)
            p = participation.astype(jnp.float32)
            update_norm_mean = (norms * p).sum() / jnp.maximum(p.sum(), 1.0)
        else:
            update_norm_mean = jnp.linalg.norm(updates, axis=1).mean()
        train_loss = (losses * benign).sum() / jnp.maximum(benign.sum(), 1.0)
        metrics = {
            "train_loss": train_loss,
            "update_norm_mean": update_norm_mean,
            "agg_norm": jnp.linalg.norm(agg),
            "round": server.round,
        }
        if self.faults is not None:
            metrics["num_participating"] = participation.sum().astype(jnp.int32)
            metrics["num_dropped"] = (~participation).sum().astype(jnp.int32)
            metrics["num_straggled"] = straggled.sum().astype(jnp.int32)
            if self.faults.needs_stale_buffer:
                # Staleness summary on the SYNC straggler path, in the
                # same schema fields the async arrival rows stamp
                # (blades_tpu/arrivals) — a straggled lane delivered the
                # update it computed `staleness` rounds ago (age holds
                # for the pre-warmup zeros too: they stand in for work
                # that old), every other participant delivered fresh
                # (age 0), so sync-vs-async staleness is comparable in
                # one schema.
                age = straggled.astype(jnp.float32) * jnp.float32(
                    self.faults.staleness)
                psum = jnp.maximum(
                    participation.astype(jnp.float32).sum(), 1.0)
                metrics["staleness_mean"] = age.sum() / psum
                metrics["staleness_max"] = age.max().astype(jnp.int32)
        if self.health_check:
            from blades_tpu.core.health import guard_server_state

            ok = jnp.isfinite(agg).all()
            server = guard_server_state(ok, server, state.server)
            metrics["num_unhealthy"] = (~healthy).sum()
            metrics["round_ok"] = ok
        if self.forensics:
            from blades_tpu.obs.forensics import detection_metrics

            # Lane-health mask: sanitize_updates' mask when health_check
            # ran, else the probe taken above at the same point — surfaced
            # instead of silently zeroed/ignored.
            healthy_mask = healthy
            metrics.update(detection_metrics(
                diag["benign_mask"], malicious, participation=participation
            ))
            if not self.health_check:
                metrics["num_unhealthy"] = (~healthy_mask).sum()
            # Per-lane bundle (prefix "lane_"): hosts split these from the
            # scalar metrics.  One dtype (f32) for the whole bundle.
            metrics["lane_benign_mask"] = diag["benign_mask"].astype(jnp.float32)
            metrics["lane_scores"] = diag["scores"].astype(jnp.float32)
            metrics["lane_healthy"] = healthy_mask.astype(jnp.float32)
            # Per-lane update norms (post-forge: the rows the aggregator
            # judged) — the client ledger's longitudinal norm stream.
            # Purely additional output: masks/scores above are untouched.
            metrics["lane_update_norms"] = jnp.linalg.norm(
                updates, axis=1).astype(jnp.float32)
        return RoundState(server=server, client_opt=client_opt, stale=stale,
                          residual=residual,
                          arrivals=getattr(state, "arrivals", None),
                          cohort=getattr(state, "cohort", None)), metrics

    def _finish_wire(
        self,
        state: RoundState,
        q: jax.Array,
        scales: jax.Array,
        residual,
        client_opt,
        losses: jax.Array,
        malicious: jax.Array,
        k_adv: jax.Array,
        k_agg: jax.Array,
    ) -> Tuple[RoundState, dict]:
        """The wire-domain tail of :meth:`step_prebatched`: forge, robust
        aggregate and server step over the PACKED payload ``(q int8,
        scales)`` — the dense f32 matrix is materialized exactly once,
        and only when an update-forging adversary needs the full
        quantized-domain geometry (counted in ``dequant_rows``).

        The adversary contract matches the f32 domain — it forges
        POST-codec, reading the same quantized geometry every defense
        will see — with one wire-honest difference: its forged rows ride
        the same int8 wire as every client's
        (:meth:`~blades_tpu.comm.codecs.CodecConfig.requantize_rows`,
        deterministic round-to-nearest), where the f32 domain hands the
        defense full-precision forged rows that never passed the wire.
        Validation (config.py) keeps faults/health/forensics/DP off this
        path; metrics carry the same scalar keys plus the planner's
        traversal accounting (``hbm_passes``/``hbm_passes_unfused``/
        ``dequant_rows``, trace-time constants like the streamed path's).
        """
        from blades_tpu.parallel.streamed_geometry import PassRecorder

        dequant_extra = 0
        if self.adversary is not None and hasattr(
            self.adversary, "on_updates_ready"
        ):
            from blades_tpu.comm.codecs import dequantize

            with jax.named_scope("blades/forge"):
                dec = dequantize(q, scales)  # blades-lint: disable=streamed-pass-discipline — sanctioned forge materialization: the adversary reads the FULL quantized-domain geometry (strongest-adversary convention); the single decode is counted in dequant_rows
                dec = self.adversary.on_updates_ready(
                    dec, malicious, k_adv,
                    aggregator=self.server.aggregator,
                    global_params=state.server.params,
                )
                q, scales = self.codec.requantize_rows(dec, q, scales,
                                                       malicious)
            dequant_extra = q.shape[0]
        trusted_update = self.compute_trusted_update(
            state.server.params, jax.random.fold_in(k_agg, 1)
        )
        recorder = PassRecorder()
        with jax.named_scope("blades/aggregate"):
            server, agg, sq = self.server.step_wire(
                state.server, q, scales, key=k_agg,
                trusted_update=trusted_update, d_chunk=self.agg_d_chunk,
                recorder=recorder,
            )
        benign = (~malicious).astype(jnp.float32)
        train_loss = (losses * benign).sum() / jnp.maximum(benign.sum(), 1.0)
        metrics = {
            "train_loss": train_loss,
            # Decoded-row norms from the statistics bundle (s_i²·Σq_ij²),
            # not a dedicated f32 traversal; differs from the f32 path's
            # jnp.linalg.norm by reassociated rounding only.
            "update_norm_mean": jnp.sqrt(jnp.maximum(sq, 0.0)).mean(),
            "agg_norm": jnp.linalg.norm(agg),
            "round": server.round,
            # Planner traversal accounting, frozen at trace time exactly
            # like the streamed path's hbm stamps.
            "hbm_passes": jnp.int32(recorder.executed),
            "hbm_passes_unfused": jnp.int32(recorder.unfused),
            "dequant_rows": jnp.int32(recorder.dequant_rows + dequant_extra),
        }
        return RoundState(
            server=server, client_opt=client_opt,
            stale=getattr(state, "stale", None), residual=residual,
            arrivals=getattr(state, "arrivals", None),
            cohort=getattr(state, "cohort", None),
        ), metrics

    def compute_trusted_update(self, global_params, key) -> Optional[jax.Array]:
        """The server's own local round on its clean root data (FLTrust's
        trusted reference, Cao et al. arXiv:2012.13995).  Fresh optimizer
        state each round — the server has no persistent client identity."""
        if self.trusted_data is None or not getattr(
            self.server.aggregator, "expects_trusted_row", False
        ):
            return None
        tx, ty = self.trusted_data
        k_sample, k_train = jax.random.split(key)
        from blades_tpu.data.sampler import sample_batch

        keys = jax.random.split(k_sample, self.num_batches_per_round)
        batches = jax.vmap(
            lambda kb: sample_batch(kb, tx, ty, jnp.array(tx.shape[0]), self.batch_size)
        )(keys)
        opt0 = self.task.init_client_opt_state(global_params)
        update, *_ = self.task.local_round(
            global_params, opt0, batches[0], batches[1], k_train,
            jnp.array(False),
        )
        return update

    def apply_dp(self, updates: jax.Array, key: jax.Array) -> jax.Array:
        """Per-client DP: clip rows + Gaussian noise (ref: blades/clients/
        dp_client.py:32-43).  Runs before adversary forging — malicious
        lanes are overwritten afterwards, matching the reference where the
        DP callback fires only in honest local training."""
        if self.dp_clip_threshold is None:
            return updates
        from blades_tpu.ops import masked as _masked

        clipped = _masked.clip_rows_to_norm(updates, self.dp_clip_threshold)
        # `is not None` (not truthiness): under experiment lanes
        # (tune/lanes.py) the noise factor is a traced per-lane scalar,
        # which cannot be bool()ed; a concrete 0.0 adds exactly zero noise
        # either way.
        if self.dp_noise_factor is not None:
            sigma = self.dp_noise_factor * self.dp_clip_threshold
            noise = sigma * jax.random.normal(key, updates.shape, updates.dtype)
            clipped = clipped + noise
        return clipped

    # -- evaluation ---------------------------------------------------------

    def evaluate(
        self,
        state: RoundState,
        test_x: jax.Array,
        test_y: jax.Array,
        lengths: jax.Array,
        batch_size: Optional[int] = None,
    ) -> dict:
        """Vmapped per-client eval + weighted reduction
        (ref: blades/algorithms/fedavg/fedavg.py:247-279)."""
        n, cap = test_x.shape[0], test_x.shape[1]
        mask = jnp.arange(cap)[None, :] < lengths[:, None]

        def one_client(cx, cy, m):
            return self.task.evaluate(state.server.params, cx, cy, m)

        with jax.named_scope("blades/eval"):
            per_client = jax.vmap(one_client)(test_x, test_y, mask)
        total = jnp.maximum(per_client["count"].sum(), 1.0)
        return {
            "test_loss": per_client["ce_sum"].sum() / total,
            "test_acc": per_client["top1_sum"].sum() / total,
            "test_acc_top3": per_client["top3_sum"].sum() / total,
            "num_samples": total,
        }

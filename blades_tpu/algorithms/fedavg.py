"""Fedavg driver (ref: blades/algorithms/fedavg/fedavg.py + fllib
Algorithm).

The Tune-Trainable surface — ``train()`` per round with periodic
evaluation folded into the result dict, ``save_checkpoint``/
``load_checkpoint``, frozen config — without the Trainable inheritance:
this class IS the trainable the sweep runner drives.

Setup replaces the reference's actor/dataset choreography
(ref: fedavg.py:127-201) with: build dataset arrays, build the FedRound
program, optionally shard it over a mesh, jit once.  Checkpoints carry
FULL state — params, server optimizer, aggregator state, stacked client
optimizer states, round counter, RNG key — fixing the reference's
config-only ``__getstate__`` gap (ref: fllib/algorithms/algorithm.py:206-219,
SURVEY.md §5).
"""

from __future__ import annotations

import math
import pickle
import warnings
from dataclasses import replace as _dc_replace
from pathlib import Path
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blades_tpu.adversaries import make_malicious_mask
from blades_tpu.core import FedRound
from blades_tpu.data import DatasetCatalog
from blades_tpu.obs.trace import Tracer, now


class Fedavg:
    """FedAvg with Byzantine clients and a robust server."""

    def __init__(self, config):
        self.config = config
        self.timers = Tracer()
        with self.timers.span("blades/setup"):
            self._setup()

    # -- setup (ref: fedavg.py:127-201) -------------------------------------

    def _setup(self) -> None:
        """The build, under ``blades/setup``, in three named parts that
        the rows' ``timers`` carry from the first row on: ``.../data``
        (dataset, device stacks, and — nested inside ``.../round``,
        where the streamed branch does it — the stack's cast to the
        compute dtype), ``.../model`` (model and state init),
        ``.../round`` (``get_fed_round``, autotune, the round
        callable).  Host time: a device copy or cast is timed as far as
        its enqueue."""
        cfg = self.config
        with self.timers.span("blades/setup/data"):
            self.dataset = DatasetCatalog.get_dataset(
                cfg.dataset, num_clients=cfg.num_clients, iid=cfg.iid,
                alpha=cfg.dirichlet_alpha, seed=cfg.seed,
            )
        with self.timers.span("blades/setup/round"):
            self.fed_round: FedRound = cfg.resolve_augment_for_data(
                cfg.get_fed_round(), self.dataset)
            if getattr(self.fed_round.server.aggregator,
                       "expects_trusted_row", False):
                self.fed_round = self._attach_root_data(self.fed_round)
        # Out-of-core async: the event cohort's opt rows live behind a
        # host/disk store (blades_tpu/state).
        self._ooc_async = (cfg.execution == "async"
                           and cfg.state_store != "resident")
        with self.timers.span("blades/setup/model"):
            self._setup_state()
        with self.timers.span("blades/setup/data"):
            self._setup_data()
        with self.timers.span("blades/setup/round"):
            self._setup_round()
        self._setup_bookkeeping()

    def _setup_state(self) -> None:
        cfg = self.config
        self.malicious = make_malicious_mask(cfg.num_clients,
                                             cfg.num_malicious_clients)
        self._key = jax.random.PRNGKey(cfg.seed)
        init_key, self._key = jax.random.split(self._key)
        # Out-of-core per-client state (blades_tpu/state): a sync
        # participation window (state_window >= 1), or an async run
        # whose event-cohort opt rows live behind a host/disk store.
        # Either way the per-client stacks must NOT be materialised at
        # init — at the registered populations the store exists for, a
        # dense broadcast would OOM before the store could help.
        sw = getattr(cfg, "state_window", None)
        self._windowed = sw is not None and sw >= 1
        self._state_store = None   # ClientStateStore handle (None = off)
        self._state_pf = None      # StatePrefetcher (sync windowed only)
        self._window_prev = None   # (cohort ids, device rows) of round r-1
        self._row_template = None  # one client's persistent-state row
        if self._windowed or self._ooc_async:
            server_rows = (int(sw) if self._windowed
                           else cfg.get_async_spec().agg_every)
            self.state, self._row_template = self.fed_round.init_windowed(
                init_key, server_rows)
        else:
            self.state = self.fed_round.init(init_key, cfg.num_clients)

    def _setup_data(self) -> None:
        cfg = self.config
        # The windowed/out-of-core paths keep the training shards
        # HOST-resident (cohort rows are gathered per round); every
        # other path stages the full stacks onto the device as before.
        self._host_train = (self.dataset.train.x, self.dataset.train.y,
                            self.dataset.train.lengths)
        if self._windowed or self._ooc_async:
            self._train_arrays = None
        else:
            self._train_arrays = tuple(jnp.asarray(a)
                                       for a in self._host_train)
        # Out-of-core training data (blades_tpu/data/store): on the
        # cohort-shaped paths the data plane sits behind a DataStore —
        # `resident` reproduces the legacy host-array staging ops
        # bit-for-bit, `memmap` holds the shards as sharded memory-
        # mapped files so host RSS tracks the cohort, not the
        # registration count.  Dense full-participation paths keep
        # their device-resident stacks untouched.
        self._data_store = None  # DataStore handle (None = legacy plane)
        self._data_pf = None     # DataPrefetcher staging adapter
        self._eval_chunk_fn = None  # jitted streaming-eval chunk program
        self._eval_chunks = 0    # chunks walked by the last streaming eval
        if self._windowed or self._ooc_async:
            from blades_tpu.data.store import make_data_store
            from blades_tpu.data.stream import DataPrefetcher

            self._data_store = make_data_store(
                getattr(cfg, "data_store", "resident"), self._host_train,
                directory=getattr(cfg, "data_dir", None))
            self._data_pf = DataPrefetcher(self._data_store)
        # Streaming eval rides the memmap data plane: the test stack
        # stays HOST-resident and evaluate() walks it in bounded
        # device-sized chunks instead of device-putting it whole.
        streaming_eval = (self._data_store is not None
                          and self._data_store.backend == "memmap")
        if streaming_eval:
            tx = self.dataset.test.x
            ty = self.dataset.test.y
            tln = self.dataset.test.lengths
        else:
            tx = jnp.asarray(self.dataset.test.x)
            ty = jnp.asarray(self.dataset.test.y)
            tln = jnp.asarray(self.dataset.test.lengths)
        cap = cfg.evaluation_num_samples
        if cap is not None and cap < tx.shape[1]:
            # Per-client eval subsample: bounds device memory + eval cost
            # at giant scale.  Shard rows are index-SORTED (partition.py
            # returns np.sort-ed indices), so taking the first rows would
            # bias any non-randomly-ordered test set — draw a seeded
            # random subset of each client's true rows instead.
            import numpy as np

            rng = np.random.default_rng(cfg.seed ^ 0x5EED)
            n = tx.shape[0]
            pick = np.zeros((n, cap), np.int32)
            for i in range(n):
                k = int(tln[i])
                pick[i] = (rng.choice(k, size=cap, replace=False)
                           if k >= cap else np.arange(cap) % max(k, 1))
            if streaming_eval:
                # Host twin of the device subsample below — the memmap
                # plane keeps the test stack off the device entirely.
                tx = np.take_along_axis(
                    tx, pick.reshape((n, cap) + (1,) * (tx.ndim - 2)),
                    axis=1)
                ty = np.take_along_axis(ty, pick, axis=1)
                tln = np.minimum(tln, cap)
            else:
                tx = jnp.take_along_axis(
                    tx,
                    jnp.asarray(pick).reshape((n, cap) + (1,) * (tx.ndim - 2)),
                    axis=1,
                )
                ty = jnp.take_along_axis(ty, jnp.asarray(pick), axis=1)
                tln = jnp.minimum(tln, cap)
        self._test_arrays = (tx, ty, tln)
        if streaming_eval:
            from blades_tpu.data.stream import make_chunk_evaluator

            self._eval_chunk_fn = make_chunk_evaluator(self.fed_round.task)

    def _setup_round(self) -> None:
        cfg = self.config
        # Execution autotuner (perf/autotune.py): resolve the measured
        # plan — or the checkpoint/operator pin, or the cached winner —
        # and materialise it into the config knobs BEFORE the pipeline
        # below reads them.  None when autotune is off: every path then
        # behaves exactly as before.
        self._plan = None
        self._plan_provenance = None
        if getattr(cfg, "autotune_mode", None):
            self._plan, self._plan_provenance = self._resolve_autotune_plan()
            self._apply_plan(self._plan)

        self._prefetcher = None   # set by _setup_dense_pipeline when active
        self._cache_wrappers = []  # CachedFunctions feeding the obs counters
        self._async = None        # AsyncEngine under execution="async"
        self._hier_recorder = None  # PassRecorder under execution="hier"
        self._gossip_recorder = None  # PassRecorder under execution="gossip"
        self._topology = None  # NeighborTables under execution="gossip"
        self.mesh = None
        # Client permutation applied to the stacked arrays (d-sharded
        # elision layout); None = natural order.  Checkpoints record it
        # so per-client state realigns across execution modes.
        self._client_order = None
        if cfg.execution == "async":
            # Buffered-async execution (blades_tpu/arrivals): a host
            # engine drives the virtual arrival clock, version vector and
            # bounded buffer; each train() call is one aggregation cycle
            # (one server round).  RoundState gains the (H+1, d) params-
            # history ring so arriving clients compute against the
            # version they actually pulled.
            from blades_tpu.arrivals import AsyncEngine

            if self._ooc_async:
                # The event cohort's opt rows come from the window
                # store (gathered per cycle, scattered back after);
                # the version vector is already keyed by registered id.
                from blades_tpu.state import make_store

                self._state_store = make_store(
                    cfg.state_store, cfg.num_clients, self._row_template,
                    directory=getattr(cfg, "state_dir", None))
                # Host-resident shards: the engine gathers the event
                # cohort's data rows per cycle.
                self._train_arrays = self._host_train
            self._async = AsyncEngine(
                self.fed_round, cfg.get_async_spec(), cfg.num_clients,
                train_seed=int(cfg.seed),
                fault_injector=cfg.get_fault_injector(),
                state_store=self._state_store,
                data_store=self._data_pf,
                forensics=bool(cfg.forensics),
            )
            self.state = _dc_replace(
                self.state,
                arrivals=self._async.init_history(self.state.server.params))
            self._step = None
            self._evaluate = jax.jit(self.fed_round.evaluate)
        elif self._windowed:
            self._setup_windowed_pipeline()
        elif cfg.execution == "gossip":
            # Decentralized gossip federation (blades_tpu/topology): every
            # node keeps its own params replica; one round = local train →
            # neighborhood exchange → per-node robust aggregation → mixing.
            # Engages on any device count (a 1-chip mesh still runs the
            # per-node program; the all_gathers just carry zero wire cost).
            from blades_tpu.parallel import make_mesh
            from blades_tpu.topology import (gossip_evaluate,
                                             gossip_federation, gossip_step)

            self.mesh = make_mesh(num_devices=cfg.num_devices)
            self._topology = cfg.get_topology()
            # Malicious mask stays REPLICATED and UNPADDED, like hier:
            # gossip_step pads and slices it inside the traced program
            # (dense-mirroring RNG needs the true node count).
            self.state, self._train_arrays = gossip_federation(
                self.mesh, self.state, self._train_arrays
            )
            self._step, self._gossip_recorder = gossip_step(
                self.fed_round, self.mesh, self._topology
            )
            # Evaluation reads the node-0 replica head; test arrays stay
            # in their default (replicated) placement.
            self._evaluate = gossip_evaluate(self.fed_round)
        elif cfg.num_devices and cfg.num_devices > 1:
            from blades_tpu.parallel import make_mesh, shard_federation, sharded_step
            from blades_tpu.parallel.sharded import sharded_evaluate

            self.mesh = make_mesh(num_devices=cfg.num_devices,
                                  mesh_shape=getattr(cfg, "mesh_shape", None))
            use_hier = cfg.execution == "hier"
            use_dsharded = cfg.execution == "dsharded" or (
                cfg.execution == "auto" and self._dsharded_auto()
            )
            mal_prefix = self._dsharded_elision_prefix() if use_dsharded \
                else None
            if mal_prefix:
                # Malicious-lane elision needs every chip's local lanes
                # laid out [f/n_dev malicious | benign]: permute the
                # client axis BEFORE sharding (client identity rides
                # along — data, mask, and per-client test shards move
                # together; opt-state init is client-symmetric).
                from blades_tpu.parallel.dsharded import elision_client_order

                self._client_order = elision_client_order(
                    cfg.num_clients, mal_prefix, cfg.num_devices)
                order = jnp.asarray(self._client_order)
                self._train_arrays = tuple(a[order]
                                           for a in self._train_arrays)
                self._test_arrays = tuple(a[order]
                                          for a in self._test_arrays)
                self.malicious = self.malicious[order]
            if use_hier:
                # Hierarchical path: data + client state shard P(clients),
                # but the malicious mask stays REPLICATED and UNPADDED —
                # hier_step pads and slices it inside the traced program
                # (dense-mirroring RNG needs the true client count).
                from blades_tpu.parallel import replicated_sharding

                self.state, self._train_arrays = shard_federation(
                    self.mesh, self.state, self._train_arrays
                )
                self.malicious = jax.device_put(
                    self.malicious, replicated_sharding(self.mesh))
            else:
                self.state, arrays = shard_federation(
                    self.mesh, self.state,
                    self._train_arrays + (self.malicious,)
                )
                self._train_arrays, self.malicious = arrays[:3], arrays[3]
            _, self._test_arrays = shard_federation(
                self.mesh, self.state, self._test_arrays
            )
            if use_hier:
                from blades_tpu.parallel import hier_step

                self._step, self._hier_recorder = hier_step(
                    self.fed_round, self.mesh,
                    preagg=getattr(cfg, "preagg", "bucket"),
                    bucket_size=int(getattr(cfg, "bucket_size", 1)),
                )
            elif use_dsharded:
                from blades_tpu.parallel.dsharded import dsharded_step

                # Width-sharded giant-federation round: per-device memory
                # is n*d/n_dev — the (n, d) matrix never exists anywhere.
                self._step = dsharded_step(self.fed_round, self.mesh,
                                           malicious_prefix=mal_prefix)
            else:
                self._step = sharded_step(self.fed_round, self.mesh, donate=False)
            self._evaluate = sharded_evaluate(self.fed_round, self.mesh)
        elif self._use_streamed():
            if (self.fed_round.packing is not None
                    and cfg.client_packing == "auto"):
                # resolve_client_packing can only veto EXPLICIT streamed/
                # dsharded requests; when execution='auto' resolves to
                # streaming here (HBM-driven), the advisory request keeps
                # its loud-fallback contract instead of hard-failing.
                reason = ("'auto' execution resolved to streaming at "
                          f"num_clients={cfg.num_clients} (dense (n, d) "
                          "matrix would strain HBM); lane packing needs "
                          "the dense round")
                warnings.warn(
                    f"client_packing='auto' falling back to unpacked "
                    f"execution: {reason}", RuntimeWarning, stacklevel=2)
                self.fed_round = _dc_replace(self.fed_round, packing=None)
                cfg._packing_decision = {
                    "requested": "auto", "pack_factor": 1,
                    "packed_lanes": cfg.num_clients, "fallback": reason}
            if (cfg.forensics or cfg.fault_config or cfg.codec_config
                    or self.fed_round.packing is not None):
                what = ("forensics" if cfg.forensics
                        else "fault injection" if cfg.fault_config
                        else "the update codec" if cfg.codec_config
                        else "client lane-packing")
                raise ValueError(
                    f"{what} needs the dense round but 'auto' execution "
                    "resolved to streaming (the dense (n, d) matrix would "
                    f"strain HBM at num_clients={cfg.num_clients}); shrink "
                    f"the federation for this pass or disable {what}"
                )
            from blades_tpu.parallel.streamed import streamed_step

            # With bf16 compute the loss casts inputs down anyway — store
            # the resident training images in bf16 and halve their HBM
            # footprint (2.4 GB -> 1.2 GB at 1000 CIFAR clients), which
            # the giant bf16 update matrix needs back.
            cd = self.fed_round.task.spec.compute_dtype
            if cd is not None and jnp.issubdtype(
                    self._train_arrays[0].dtype, jnp.floating):
                # (Token ids are integers and stay as they are.)
                with self.timers.span("blades/setup/data"):
                    x, y, ln = self._train_arrays
                    self._train_arrays = (x.astype(jnp.dtype(cd)), y, ln)
            self._step = streamed_step(
                self.fed_round,
                client_block=cfg.client_block,
                d_chunk=cfg.d_chunk,
                mxu_finish=getattr(cfg, "mxu_finish", None),
                update_dtype=getattr(jnp, str(cfg.update_dtype)),
                # self.malicious IS the canonical prefix mask (built via
                # make_malicious_mask above) — lets forged-update rounds
                # skip the dead malicious-lane training blocks.
                malicious_prefix=cfg.num_malicious_clients,
            )
            self._evaluate = jax.jit(self.fed_round.evaluate)
        else:
            self._setup_dense_pipeline()

    def _setup_bookkeeping(self) -> None:
        cfg = self.config
        # Client-lifetime ledger (obs/ledger.py): one longitudinal
        # record per REGISTERED client, folded host-side in
        # _fill_round_metrics from the already-fetched row and the
        # round's cohort id-vector — zero extra device syncs.
        self._ledger = None
        if getattr(cfg, "ledger_backend", None):
            from blades_tpu.obs.ledger import make_ledger

            self._ledger = make_ledger(
                cfg.ledger_backend, cfg.num_clients,
                directory=getattr(cfg, "ledger_dir", None))

        # Closed-loop control plane (blades_tpu/control): the driver
        # owns its OWN watchdog over finalized rows (stamping
        # watchdog_events itself; the sweep's post-hoc watchdog defers
        # to rows already stamped) and applies the controller's
        # journaled actions back to the engine after every round.
        self._controller = None
        self._watchdog = None
        if getattr(cfg, "control_enabled", False):
            from blades_tpu.control import Controller
            from blades_tpu.obs.watchdog import Watchdog

            self._watchdog = Watchdog(cfg.get_watchdog_rules())
            if self._async is not None:
                self._controller = Controller(
                    cfg.get_control_policy(), num_clients=cfg.num_clients,
                    agg_every=int(self._async.agg_every),
                    buffer_capacity=int(self._async.buffer.capacity),
                    weight_cutoff=int(self._async.weight_cutoff),
                    # Out-of-core window actuator: under a state store
                    # the event-cohort size IS the participation
                    # window, and `window` is the one journaled move
                    # allowed to shrink it (agg_every/buffer moves are
                    # validate()-rejected there — see config.py).
                    window=(int(self._async.agg_every)
                            if self._state_store is not None else None),
                    allow_replan=False,  # async × autotune is forbidden
                )
            else:
                # Sync driver: none of the three async actuators exist;
                # a replan is the one live response (dense/windowed
                # single-chip only — the windowed store/prefetcher must
                # not be rebuilt mid-run).
                self._controller = Controller(
                    cfg.get_control_policy(), num_clients=cfg.num_clients,
                    allow_replan=bool(getattr(cfg, "autotune_mode", None)
                                      and self._state_pf is None
                                      and self.mesh is None),
                )

        self._iteration = 0
        self._rounds_since_eval = 0
        self._last_eval: Dict = {}
        # Model width, pinned at setup: the codec's host-side byte
        # accounting must not touch self.state later (whose buffers a
        # donated dispatch deletes).
        self._num_params = sum(
            p.size for p in jax.tree.leaves(self.state.server.params))

    def _setup_dense_pipeline(self) -> None:
        """Single-chip dense path with the perf layer (blades_tpu/perf):
        the round program is AOT-compiled through the process-wide
        executable cache (identically-shaped sweep trials compile once),
        the incoming :class:`RoundState` is DONATED into each dispatch
        (the stacked client opt states — the largest tensors on this
        path — are reused in place instead of copied), and with
        ``prefetch`` on, the next round's per-client batches are staged
        by a separately-dispatched sampling program while the current
        round computes.  All three are bit-transparent: aggregates and
        round metrics match the eager ``jax.jit(fr.step)`` path exactly
        (tests/test_perf.py)."""
        from blades_tpu.perf import cached_jit

        cfg = self.config
        donate = (0,) if getattr(cfg, "donate_buffers", True) else ()
        fp = self._program_fingerprint()
        self._prefetcher = None
        if self._resolve_prefetch():
            from blades_tpu.data.prefetch import BatchPrefetcher

            sample = (cached_jit(self.fed_round.sample_round_batches,
                                 key=("sample", fp))
                      if fp else jax.jit(self.fed_round.sample_round_batches))
            self._sample = lambda k: sample(*self._train_arrays, k)
            self._prefetcher = BatchPrefetcher(self._sample)
            if fp:
                self._cache_wrappers = [sample]
            step_fn = self.fed_round.step_prebatched
            key = ("step", "prebatched", fp)
        else:
            step_fn = self.fed_round.step
            key = ("step", "fused", fp)
        if fp:
            self._step = cached_jit(step_fn, key=key, donate_argnums=donate)
            self._evaluate = cached_jit(self.fed_round.evaluate,
                                        key=("evaluate", fp))
            self._cache_wrappers = ([self._step, self._evaluate]
                                    + self._cache_wrappers)
        else:
            # Un-fingerprintable config (callable model/config values):
            # the executable cannot be safely shared across trials, but
            # donation still applies per-trial.
            self._step = jax.jit(step_fn, donate_argnums=donate)
            self._evaluate = jax.jit(self.fed_round.evaluate)

    def _setup_windowed_pipeline(self) -> None:
        """Single-chip participation-window path (blades_tpu/state):
        each round gathers the sampled cohort's state/data rows from
        the store, runs the SAME fused round program the dense path
        jits (at cohort geometry, AOT-cached + donated), and scatters
        the updated rows back; a :class:`~blades_tpu.state.prefetch.
        StatePrefetcher` stages round ``r+1``'s cohort while round
        ``r`` computes (``prefetch`` semantics as on the dense path:
        "auto" = on for accelerator backends, forced either way is
        bit-transparent)."""
        from blades_tpu.perf import cached_jit
        from blades_tpu.state import StatePrefetcher, make_store, sample_cohort

        cfg = self.config
        n, w = cfg.num_clients, int(cfg.state_window)
        self._state_store = make_store(
            cfg.state_store, n, self._row_template,
            directory=getattr(cfg, "state_dir", None))
        self._state_pf = StatePrefetcher(
            self._state_store,
            # Out-of-core data plane: cohort shards ride the state
            # worker through the DataPrefetcher (always built on the
            # windowed path; `resident` reproduces the host-array ops).
            self._data_pf if self._data_pf is not None else self._host_train,
            np.asarray(self.malicious),
            lambda k: sample_cohort(k, n, w),
            async_staging=self._resolve_prefetch(),
        )
        donate = (0,) if getattr(cfg, "donate_buffers", True) else ()
        fp = self._program_fingerprint()
        if fp:
            self._step = cached_jit(self.fed_round.step,
                                    key=("step", "windowed", fp),
                                    donate_argnums=donate)
            self._evaluate = cached_jit(self.fed_round.evaluate,
                                        key=("evaluate", fp))
            self._cache_wrappers = [self._step, self._evaluate]
        else:
            self._step = jax.jit(self.fed_round.step, donate_argnums=donate)
            self._evaluate = jax.jit(self.fed_round.evaluate)

    def _resolve_prefetch(self) -> bool:
        """``prefetch='auto'`` resolves to ON for the dense dispatch
        (the path with a per-round sampling stage to overlap) on an
        accelerator backend; the single-threaded CPU backend has no
        transfer/compute overlap to win — there 'auto' skips the second
        program's compile.  ``True`` forces it anywhere (the
        bit-identity tests do)."""
        want = getattr(self.config, "prefetch", "auto")
        if want in (False, "off"):
            return False
        if want in (True, "on"):
            return True
        return jax.default_backend() != "cpu"

    def _program_fingerprint(self) -> Optional[str]:
        """Static-config fingerprint for the AOT executable cache
        (:mod:`blades_tpu.perf.compile_cache`).

        Must cover every value the traced round program bakes in as a
        constant.  ``seed`` is excluded on purpose — it only steers data
        values and PRNG key values, both runtime arguments — which is
        exactly what lets a seed grid share one executable.  Dataset
        objects contribute their name only (their arrays are arguments
        too), EXCEPT FLTrust's root data, which the program closes over
        and is therefore digested by value.  Returns ``None`` when the
        config holds values a stable fingerprint cannot capture
        (callables), disabling cross-trial sharing for that trial.
        """
        from blades_tpu.perf import fingerprint

        def plain(v) -> bool:
            # Recursive: a nested custom object (e.g. a callback INSTANCE
            # in client_callbacks) would stringify to a memory-address
            # repr — which a recycled allocation could collide on,
            # silently serving another trial's executable.  Only plainly
            # JSON-able values may enter the fingerprint.
            if isinstance(v, (str, int, float, bool, type(None))):
                return True
            if isinstance(v, (list, tuple)):
                return all(plain(x) for x in v)
            if isinstance(v, dict):
                return all(isinstance(k, str) and plain(x)
                           for k, x in v.items())
            return False

        items: Dict = {"__class__": type(self).__name__,
                       "__augment__": str(self.fed_round.task.spec.augment)}
        for k, v in self.config.items():
            if k == "seed":
                continue
            if k in ("autotune", "autotune_cache_dir", "tuned_plan"):
                # The autotune REQUEST steers nothing in the traced
                # program — the knobs a resolved plan materialises
                # (execution, d_chunk, ...) are ordinary config fields
                # already in this fingerprint.  Excluding the request
                # lets the tuner's measurement candidates share their
                # compiled executables with the winning plan's real run,
                # and gives the plan cache a pre-resolution key.
                continue
            if k == "dataset" and not isinstance(v, (str, dict)):
                v = f"<dataset:{getattr(v, 'name', type(v).__name__)}>"
            if not plain(v):
                return None
            items[k] = v
        td = self.fed_round.trusted_data
        if td is not None:
            import hashlib

            h = hashlib.sha1()
            for a in td:
                h.update(np.asarray(a).tobytes())
            items["__trusted_digest__"] = h.hexdigest()
        return fingerprint(items)

    # Dense-matrix budget for a device that reports no memory statistics
    # (the CPU backend): a dense f32 (n, d) update matrix past this
    # strains one 16 GB chip once training temps and data join it — the
    # giant-federation regime both memory-economical paths exist for.
    _DENSE_MATRIX_HBM_LIMIT = 6 * (1 << 30)
    # Fraction of the device's reported HBM granted to the dense matrix
    # (6 GB / 16 GB, the tuned operating point).
    _DENSE_MATRIX_HBM_FRACTION = 3 / 8

    @classmethod
    def dense_matrix_hbm_limit_source(cls) -> Tuple[int, str]:
        """``(bytes, source)`` of the 'auto'-execution dense budget.

        Resolution order: the ``BLADES_TPU_DENSE_MATRIX_LIMIT_GB`` env
        override (``"env"``) -> 3/8 of ``jax.devices()[0]
        .memory_stats()``'s ``bytes_limit`` (``"memory_stats"``) -> the
        16 GB-chip constant when the device reports no statistics, as
        the CPU backend does (``"default"``).  A device whose
        ``memory_stats()`` raises is an error, not a default.
        """
        import os

        env = os.environ.get("BLADES_TPU_DENSE_MATRIX_LIMIT_GB")
        if env:
            return int(float(env) * (1 << 30)), "env"
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        if limit:
            return int(limit * cls._DENSE_MATRIX_HBM_FRACTION), "memory_stats"
        return cls._DENSE_MATRIX_HBM_LIMIT, "default"

    @classmethod
    def dense_matrix_hbm_limit(cls) -> int:
        """The 'auto'-execution dense budget in bytes."""
        return cls.dense_matrix_hbm_limit_source()[0]

    def _dense_matrix_bytes(self) -> int:
        d = sum(p.size for p in jax.tree.leaves(self.state.server.params))
        return self.config.num_clients * d * 4

    def _dsharded_elision_prefix(self):
        """Malicious-lane training elision on the d-sharded path: sound
        exactly when every malicious lane's update is REPLACED by a
        forge computed from benign statistics (update-forging
        adversaries; training-side attacks train for real), and the
        counts divide the mesh so the strided layout is uniform."""
        from blades_tpu.parallel.streamed import _adv_forges

        cfg = self.config
        f = int(cfg.num_malicious_clients or 0)
        if not f or not _adv_forges(self.fed_round.adversary):
            return None
        # floor(f/n_dev) lanes elide per chip; below one per chip there
        # is nothing to skip, and an all-malicious federation has no
        # benign lanes to train (elision_client_order requires f < n).
        if (cfg.num_clients % cfg.num_devices or f < cfg.num_devices
                or f >= cfg.num_clients):
            return None
        return f

    def _dsharded_auto(self) -> bool:
        """On a mesh, pick the width-sharded round when the replicated
        (n, d) matrix the gather formulations materialise per device
        would strain HBM."""
        return self._dense_matrix_bytes() > self.dense_matrix_hbm_limit()

    def _streamed_supported(self) -> bool:
        """The static half of the streamed-execution gate: does this
        round's aggregator/forger pair have a streamed formulation at
        all?  (Feasibility only — the HBM trigger that makes ``'auto'``
        actually pick it lives in :meth:`_use_streamed`.)"""
        from blades_tpu.parallel.streamed import (
            _COORDWISE_AGGREGATORS,
            _COORDWISE_FORGERS,
            _adv_forges,
        )
        from blades_tpu.parallel.streamed_geometry import (
            STREAMED_ROW_AGGREGATORS,
            streamed_row_forgers,
        )

        fr = self.fed_round
        if not isinstance(
            fr.server.aggregator,
            _COORDWISE_AGGREGATORS + STREAMED_ROW_AGGREGATORS,
        ):
            return False
        if _adv_forges(fr.adversary) and not isinstance(
            fr.adversary, _COORDWISE_FORGERS + streamed_row_forgers()
        ):
            return False
        return True

    def _use_streamed(self) -> bool:
        """Pick the single-chip streaming round (parallel/streamed.py).

        Explicit ``execution='streamed'`` always; ``'auto'`` when the
        dense f32 ``(n, d)`` update matrix would strain a 16 GB chip's
        HBM (> ~6 GB) — the giant-federation regime the streamed path
        exists for."""
        cfg = self.config
        if cfg.execution == "dense":
            return False
        if cfg.execution == "streamed":
            return True
        if getattr(self, "_windowed", False):
            # Participation-window runs compute over the (window, d)
            # cohort matrix — the registered population never strains
            # HBM, so 'auto' must not stream on its account.
            return False
        if getattr(self.fed_round, "stateless_clients", False):
            # window=0 stateless clients are formulated in
            # step_prebatched; the streamed path threads client_opt
            # through its own block loop and would silently train
            # STATEFUL clients — 'auto' must stay dense.
            return False
        if not self._streamed_supported():
            return False
        return self._dense_matrix_bytes() > self.dense_matrix_hbm_limit()

    # -- execution autotuner (perf/autotune.py) ------------------------------

    def _d_chunk_exact(self) -> bool:
        """Whether the streamed finish's output is invariant to the
        ``d_chunk`` partition, bit for bit — the gate that keeps the
        chunk ladder in the autotuner's numerics-preserving tier.

        Chunk-size changes are exact for coordinate-wise aggregators on
        deterministic coordinate-wise forges (every statistic is
        per-column).  They are NOT for: DP (noise keys fold the chunk
        index), Noise/Adaptive forges (per-chunk key folds / draws),
        health checks (chunk-local sanitize keeps different slices of a
        partially-non-finite lane), and the row-geometry aggregators
        (row statistics accumulate in chunk order).  Those rounds keep
        the configured chunk."""
        from blades_tpu.adversaries.update_attacks import (AdaptiveAdversary,
                                                           NoiseAdversary)
        from blades_tpu.parallel.streamed import (_COORDWISE_AGGREGATORS,
                                                  _COORDWISE_FORGERS,
                                                  _adv_forges)

        fr = self.fed_round
        if fr.dp_clip_threshold is not None or fr.health_check:
            return False
        if not isinstance(fr.server.aggregator, _COORDWISE_AGGREGATORS):
            return False
        adv = fr.adversary
        if _adv_forges(adv):
            if isinstance(adv, (AdaptiveAdversary, NoiseAdversary)):
                return False
            if not isinstance(adv, _COORDWISE_FORGERS):
                return False
        return True

    def _plan_space(self, allow_reassociating: bool):
        """Enumerate this trial's legal execution plans (see
        :func:`blades_tpu.perf.autotune.enumerate_plans`).  Every
        per-knob candidate list is ordered current-resolution-first and
        collapses to one entry when the user set the knob explicitly —
        the composition contract ``--autotune`` documents."""
        import os

        from blades_tpu.perf import autotune as at

        cfg = self.config
        explicit = getattr(cfg, "_explicit", set()) or set()
        baseline_streamed = self._use_streamed()
        windowed = getattr(self, "_windowed", False)
        stateless = getattr(self.fed_round, "stateless_clients", False)
        dense_features = (cfg.forensics or cfg.fault_config
                          or cfg.codec_config or windowed or stateless)
        packing = getattr(self.fed_round, "packing", None)
        base_pack = int(packing.pack) if packing is not None else 1

        # Execution paths: forced values pin the list; under "auto" the
        # alternate path is reassociating-tier and only legal when its
        # own constraints hold (dense must fit HBM; streamed needs a
        # formulation and none of the dense-only features).
        if cfg.execution in ("dense", "streamed"):
            execs = [cfg.execution]
        else:
            execs = ["streamed" if baseline_streamed else "dense"]
            if allow_reassociating:
                if (baseline_streamed and not dense_features
                        and self._dense_matrix_bytes()
                        <= self.dense_matrix_hbm_limit()):
                    execs.append("dense")
                elif (not baseline_streamed and self._streamed_supported()
                      and not dense_features and base_pack == 1
                      and not (cfg.num_devices and cfg.num_devices > 1)):
                    execs.append("streamed")
        streamed_in_space = "streamed" in execs

        # Streamed chunk ladder (default tier; exact only when the
        # finish is chunk-invariant, see _d_chunk_exact).
        d_chunks = [int(cfg.d_chunk)]
        if (streamed_in_space and "d_chunk" not in explicit
                and self._d_chunk_exact()):
            d_model = self._num_params if hasattr(self, "_num_params") else \
                sum(p.size for p in jax.tree.leaves(self.state.server.params))
            seen = {min(int(cfg.d_chunk), d_model)}
            for c in at.D_CHUNK_LADDER:
                eff = min(int(c), d_model)
                if eff not in seen:
                    seen.add(eff)
                    d_chunks.append(int(c))

        # MXU finish: the env var is an explicit per-process override,
        # an explicit config value pins it; otherwise the tuner varies
        # it ("counts" is bit-exact — default tier; "all" reassociates
        # the forged-row stats — opt-in tier).
        env_mxu = os.environ.get("BLADES_TPU_MXU_FINISH")
        if env_mxu is not None:
            mxu_modes = [env_mxu]
        elif cfg.mxu_finish is not None:
            mxu_modes = [cfg.mxu_finish]
        else:
            mxu_modes = ["", "counts", "all"]

        # Pack factors (dense only; packing reassociates the per-client
        # convolutions).  The resolved baseline comes first; alternates
        # {2, 4, 8} are probed through resolve_client_packing itself —
        # the SAME resolver the static "auto" heuristic uses, so only
        # structurally-possible factors enter the space (impossible ones
        # drop at enumeration, never at apply time) and the measured
        # tier can out-vote the heuristic's fixed P=2.  Composition
        # contract: a forced int pins trivially, and an EXPLICIT "off"
        # pins too — only "auto" (a standing request to resolve) or the
        # untouched default may be varied.
        packs = [base_pack]
        if (allow_reassociating and "dense" in execs and not windowed
                and not isinstance(cfg.client_packing, int)
                and (cfg.client_packing == "auto"
                     or "client_packing" not in explicit)):
            from blades_tpu.parallel.packed import resolve_client_packing

            for p in (1, 2, 4, 8):
                if p in packs or cfg.num_clients % p:
                    continue
                if p == 1:
                    packs.append(1)
                    continue
                try:
                    stripped = _dc_replace(self.fed_round, packing=None)
                    _, dec = resolve_client_packing(
                        stripped, p, num_clients=cfg.num_clients,
                        num_devices=cfg.num_devices, execution="dense")
                except Exception:
                    continue
                if dec and int(dec.get("pack_factor", 1)) == p:
                    packs.append(p)

        # Prefetch (dense batch staging, bit-transparent):
        # resolved default first, the flip offered only when left "auto".
        base_pre = (False if cfg.prefetch in (False, "off")
                    else True if cfg.prefetch in (True, "on")
                    else jax.default_backend() != "cpu")
        prefetch_options = [base_pre]
        if cfg.prefetch == "auto" and "prefetch" not in explicit:
            prefetch_options.append(not base_pre)

        # Aggregation domain (dense + codec only): the configured value
        # is the baseline; the reassociating tier additionally offers
        # the wire domain when the codec can defer (quant int8/int4 —
        # identity's wire IS f32, so there is nothing to time) and no
        # f32-domain-only stage (faults/health/forensics/DP) is
        # configured.  Explicit agg_domain pins the list — the standard
        # composition contract.
        agg_domains = [cfg.agg_domain]
        if (allow_reassociating and "dense" in execs and not windowed
                and "agg_domain" not in explicit
                and cfg.agg_domain == "f32" and cfg.codec_config
                and not (cfg.fault_config or cfg.health_check
                         or cfg.forensics or cfg.dp_clip_threshold)):
            from blades_tpu.parallel.streamed_geometry import WIRE_AGGREGATORS

            codec = cfg.get_codec()
            if (codec is not None and codec.supports_deferred
                    and codec.name != "identity"
                    and isinstance(self.fed_round.server.aggregator,
                                   WIRE_AGGREGATORS)):
                agg_domains.append("wire")

        # Participation-window store knobs (blades_tpu/state): the
        # window size is PINNED (varying it changes which cohorts — and
        # therefore which data — each round trains on; that is a
        # different experiment, not a reassociation, and a speed-only
        # tuner would always shrink it).  The store BACKEND is
        # bit-identical by contract but changes the staging pipeline,
        # so the reassociating tier may probe the alternates when the
        # user left it defaulted; an explicit backend pins the list —
        # the standard composition contract.
        state_stores = [cfg.state_store]
        if (allow_reassociating and windowed
                and "state_store" not in explicit):
            for alt in ("host", "resident"):
                if alt not in state_stores:
                    state_stores.append(alt)
        state_windows = [getattr(cfg, "state_window", None)]

        # Pod-scale mesh knobs (ISSUE 18): multi-chip tuning keeps the
        # config's own mesh resolution as candidates[0] — a
        # mesh_shape=None plan never touches the device layout, so every
        # pre-pod plan_id stays byte-identical — and the reassociating
        # tier offers the hierarchical collective (and the 2-D torus
        # that carries it).  The d-sharded formulation has no plan
        # vocabulary: an explicit pin is rejected at validate() time,
        # and an 'auto' resolution to it must fail loudly here rather
        # than be silently retuned onto the flat dense mesh.
        nd = int(cfg.num_devices or 1)
        if nd > 1 and cfg.execution == "auto" and self._dsharded_auto():
            raise ValueError(
                "autotune × execution='auto'-resolved-to-dsharded is an "
                "unsupported pair: the plan space has no d-sharded "
                "vocabulary — pin .resources(execution='dsharded') "
                "without autotune, or shrink the federation into the "
                "dense budget")
        base_ms = getattr(cfg, "mesh_shape", None)
        mesh_shapes = [tuple(base_ms) if base_ms else None]
        collectives = ["ring"]
        if nd > 1 and allow_reassociating:
            hier_ms = tuple(base_ms) if base_ms else (nd, 1)
            if hier_ms not in mesh_shapes:
                mesh_shapes.append(hier_ms)
            collectives.append("hier")

        return at.enumerate_plans(
            executions=execs, d_chunks=d_chunks, mxu_modes=mxu_modes,
            pack_factors=packs,
            prefetch_options=prefetch_options, agg_domains=agg_domains,
            state_stores=state_stores, state_windows=state_windows,
            mesh_shapes=mesh_shapes, collectives=collectives,
            num_devices=nd,
            allow_reassociating=allow_reassociating,
        )

    def _resolve_autotune_plan(self):
        """Resolve this trial's execution plan: the explicit
        ``tuned_plan`` pin, the on-disk plan-cache winner, a measured
        selection (TPU), or the deterministic ranked heuristic (CPU /
        timing unavailable) — in that order.  Returns
        ``(Plan, provenance dict)``; the provenance flows into sweep
        summaries and the schema-registered round fields."""
        from blades_tpu.perf import autotune as at

        cfg = self.config
        mode = cfg.autotune_mode
        pinned = getattr(cfg, "tuned_plan", None)
        if pinned:
            plan = at.Plan.from_dict(pinned)
            return plan, {
                "mode": "pinned", "timed": False, "cache_hit": False,
                "winner": plan.as_dict(), "winner_id": plan.plan_id,
                "candidates": [], "truncated": 0,
            }
        space = self._plan_space(
            allow_reassociating=(mode == "reassociating"))
        cache = at.PlanCache(getattr(cfg, "autotune_cache_dir", None))
        fp = self._program_fingerprint()
        key = at.cache_key(fp, tier=mode) if fp else None
        cache_stale = False
        if key is not None:
            entry = cache.get(key)
            if entry is not None:
                plan = at.Plan.from_dict(entry["plan"])
                if plan in space.candidates:
                    prov = dict(entry.get("provenance") or {})
                    prov.update({"mode": "cache", "cache_hit": True,
                                 "winner": plan.as_dict(),
                                 "winner_id": plan.plan_id})
                    return plan, prov
                # The cached winner is not in THIS run's legal space
                # (the fingerprint does not see everything that shapes
                # it, e.g. which knobs were set explicitly): re-tune,
                # and overwrite below, rather than apply a plan the
                # current constraints forbid.
                cache_stale = True
        measure = (at.timed_measure_fn(cfg) if at.timing_available()
                   else None)
        plan, prov = at.select_plan(space, measure_fn=measure)
        if cache_stale:
            prov["cache_stale"] = True  # surfaced in sweep summaries
        if key is not None:
            cache.put(key, plan, prov)
        return plan, prov

    def _apply_plan(self, plan) -> None:
        """Materialise the resolved plan into the config knobs the
        pipeline setup below reads, and re-resolve lane packing when the
        plan's pack factor differs from what ``get_fed_round`` built."""
        from blades_tpu.perf.autotune import apply_plan

        cfg = self.config
        apply_plan(cfg, plan)
        packing = getattr(self.fed_round, "packing", None)
        cur = int(packing.pack) if packing is not None else 1
        want = int(plan.client_packing or 1)
        if want == cur:
            return
        fr = _dc_replace(self.fed_round, packing=None)
        if want >= 2:
            from blades_tpu.parallel.packed import resolve_client_packing

            fr, decision = resolve_client_packing(
                fr, want, num_clients=cfg.num_clients,
                num_devices=cfg.num_devices, execution=plan.execution)
            cfg._packing_decision = decision
        else:
            cfg._packing_decision = {
                "requested": cfg.client_packing, "pack_factor": 1,
                "packed_lanes": cfg.num_clients,
                "fallback": "autotune plan selected unpacked execution",
            }
        self.fed_round = fr

    def _attach_root_data(self, fed_round: FedRound) -> FedRound:
        """Carve a clean server root dataset for FLTrust (Cao et al.): a few
        rows from every client's training shard, round-robin, up to
        ``fltrust_root_size`` samples."""
        import dataclasses

        import numpy as np

        part = self.dataset.train
        per = max(1, -(-self.config.fltrust_root_size // part.num_clients))
        take = [min(per, int(part.lengths[i])) for i in range(part.num_clients)]
        tx = np.concatenate([part.x[i, : take[i]] for i in range(part.num_clients)])
        ty = np.concatenate([part.y[i, : take[i]] for i in range(part.num_clients)])
        tx = tx[: self.config.fltrust_root_size]
        ty = ty[: self.config.fltrust_root_size]
        return dataclasses.replace(
            fed_round, trusted_data=(jnp.asarray(tx), jnp.asarray(ty))
        )

    # -- Trainable surface (ref: algorithm.py:102-119) ----------------------

    @property
    def iteration(self) -> int:
        return self._iteration

    def adopt_tracer(self, tracer) -> None:
        """Observability layer (obs/trace.py): replace this instance's
        phase timers with the caller's span tracer, so the
        ``blades/round`` phases and ``evaluate`` nest inside the
        caller's trial/round spans (ONE tree per trial in the
        ``--trace-dir`` export).  What this instance has timed so far
        (its build) moves into the adopted tracer's aggregates, so the
        per-row ``timers`` field keeps it."""
        tracer.absorb(self.timers)
        self.timers = tracer

    @property
    def plan(self):
        """The resolved execution :class:`~blades_tpu.perf.autotune.Plan`
        this instance runs under, or ``None`` when autotune is off."""
        return self._plan

    @property
    def plan_summary(self) -> Optional[Dict]:
        """Autotune provenance for sweep summaries: selection mode
        (measured / heuristic / cache / pinned), per-candidate timings,
        winner and cache hit/miss.  ``None`` when autotune is off."""
        return self._plan_provenance

    @property
    def state_summary(self) -> Optional[Dict]:
        """Out-of-core client-state digest for sweep summaries (backend,
        window, row/total bytes, staging peak), or ``None`` when no
        store is configured."""
        if self._state_store is None:
            return None
        stats = (self._state_pf.stats if self._state_pf is not None
                 else self._async.store_stats)
        return {
            "backend": self._state_store.backend,
            "window": (int(self.config.state_window)
                       if self._state_pf is not None
                       else int(self._async.agg_every)),
            "n_registered": self._state_store.n_registered,
            "row_bytes": int(self._state_store.row_bytes),
            "total_bytes": int(self._state_store.total_bytes()),
            "peak_hbm_bytes": int(stats.peak_hbm_bytes),
        }

    @property
    def data_summary(self) -> Optional[Dict]:
        """Out-of-core training-data digest for sweep summaries
        (backend, population/row bytes, last staging cost, eval
        chunking), or ``None`` when the data plane is the legacy dense
        one."""
        if self._data_store is None:
            return None
        stats = self._data_pf.stats
        return {
            "backend": self._data_store.backend,
            "n_clients": int(self._data_store.n_clients),
            "row_bytes": int(self._data_store.row_bytes),
            "total_bytes": int(self._data_store.total_bytes()),
            "last_stage_ms": round(stats.last_stage_ms, 3),
            "last_bytes_staged": int(stats.last_bytes_staged),
            "eval_chunks": int(self._eval_chunks),
        }

    @property
    def client_ledger(self):
        """The live :class:`~blades_tpu.obs.ledger.ClientLedger`, or
        ``None`` when the ledger is off — the sweep attaches it to the
        flight recorder so dumps carry the fleet fingerprint."""
        return self._ledger

    @property
    def ledger_summary(self) -> Optional[Dict]:
        """Client-ledger fleet digest for sweep summaries (backend,
        clients seen, suspected fraction, reputation percentiles), or
        ``None`` when the ledger is off."""
        if self._ledger is None:
            return None
        return self._ledger.summary()

    @property
    def control_summary(self) -> Optional[Dict]:
        """Closed-loop controller digest for sweep summaries (actions
        journaled, live actuator view, quarantine/probation sets,
        driver-watchdog event count), or ``None`` when control is
        off."""
        if self._controller is None:
            return None
        out = self._controller.summary()
        out["watchdog_events"] = len(self._watchdog.events)
        return out

    @property
    def packing_summary(self) -> Optional[Dict]:
        """The lane-packing decision get_fed_round() resolved for this
        trial (requested/pack_factor/packed_lanes/fallback reason), or
        None when packing was never requested — the sweep mirrors it
        into trial summaries."""
        return getattr(self.config, "_packing_decision", None)

    def _windowed_round(self):
        """One participation-window round: take the staged cohort
        (state rows, data shards, malicious mask), run the fused round
        program over it, then hand the updated rows to the prefetcher
        — the NEXT round's stage job first (it excludes this cohort's
        ids, so it overlaps this round's compute), the write-back
        second (FIFO ordering guarantees any later stage revisiting
        these ids sees it).  Returns the device metrics dict."""
        round_key, self._key = jax.random.split(self._key)
        ids, rows, data, mal = self._state_pf.take(
            self._iteration, round_key, self._window_prev)
        state_in = _dc_replace(
            self.state, client_opt=rows["client_opt"],
            residual=rows.get("residual"), cohort=jnp.asarray(ids))
        new_state, raw_metrics = self._step(state_in, *data, mal, round_key)
        self.state = new_state
        out_rows = {"client_opt": new_state.client_opt}
        if new_state.residual is not None:
            out_rows["residual"] = new_state.residual
        self._state_pf.stage(self._iteration + 1,
                             jax.random.split(self._key)[0], prev_ids=ids)
        self._state_pf.writeback(ids, out_rows)
        self._window_prev = (ids, out_rows)
        return raw_metrics

    def train(self) -> Dict:
        """One FL round: dispatch it, fetch its metrics, evaluate where
        the cadence fires, return its result row.

        The call is the ``blades/round`` span; inside it
        ``training_step`` (dispatch + fetch) holds the round body's
        ``blades/prepare|block|finish`` (parallel/streamed.py) and
        ``blades/fetch`` (the host's wait for the device), and
        ``blades/row`` the row work after them (``evaluate`` nests in
        it on an evaluation round).  The row's ``timers`` is taken
        after the round's spans close, so a row's ``timers`` minus the
        previous row's is that round's phase times."""
        with self.timers.span("blades/round", step=self._iteration):
            row = self._train_raw()
        row["timers"] = self.timers.summary()
        return row

    def _train_raw(self) -> Dict:
        cycle_t0 = now() if self._async is not None else None
        with self.timers.span("training_step"):
            if self._async is not None:
                # One buffered-async cycle: the engine advances the
                # virtual clock to the next full buffer and fires ONE
                # aggregation dispatch.  The training key chain is
                # untouched — per-event keys are pure in (seed, tick,
                # client), so resume re-derives them from the
                # checkpointed tick alone.
                self.state, raw_metrics = self._async.run_cycle(
                    self.state, self._train_arrays, self.malicious)
            elif self._state_pf is not None:
                raw_metrics = self._windowed_round()
            elif self._prefetcher is not None:
                round_key, self._key = jax.random.split(self._key)
                # Staged last dispatch (or drawn now on the first); the
                # NEXT round's batches are dispatched right behind this
                # round's step, overlapping its compute.  The peeked key
                # equals the round key the next train() will split off.
                bx, by = self._prefetcher.take(self._iteration, round_key)
                self.state, raw_metrics = self._step(
                    self.state, bx, by, self.malicious, round_key
                )
                self._prefetcher.stage(self._iteration + 1,
                                       jax.random.split(self._key)[0])
            else:
                round_key, self._key = jax.random.split(self._key)
                self.state, raw_metrics = self._step(
                    self.state, *self._train_arrays, self.malicious, round_key
                )
            # The fetch sits inside the timer: the dispatch is
            # asynchronous, so the span otherwise times the enqueue.
            with self.timers.span("blades/fetch"):
                raw_metrics = jax.device_get(raw_metrics)
        self._iteration += 1
        self._rounds_since_eval += 1
        with self.timers.span("blades/row"):
            row = self._new_row(cycle_t0)
            self._fill_round_metrics(row, raw_metrics)
        return row

    def _new_row(self, cycle_t0) -> Dict:
        """The row before the fetched metrics fill it: host counters,
        and the evaluation where its cadence fires."""
        # "timers" as of the dispatch and fetch: what the control
        # driver's watchdog reads; train() takes it again once the
        # round's spans have closed.
        row = {
            "training_iteration": self._iteration,
            "timers": self.timers.summary(),
        }
        if self._async is not None:
            # Host-side ingest digest (blades_tpu/arrivals): host ints
            # the engine already holds.  updates_per_sec is the
            # one wall-clock field (the ingest rate), measured
            # through the span layer's sanctioned clock; everything else
            # is deterministic and replay-comparable.
            info = self._async.last_info
            elapsed = max(now() - cycle_t0, 1e-9)
            row["tick"] = int(info["tick"])
            row["staleness_mean"] = float(info["staleness_mean"])
            row["staleness_max"] = int(info["staleness_max"])
            row["staleness_hist"] = [int(v) for v in info["staleness_hist"]]
            row["buffer_fill"] = int(info["buffer_fill"])
            row["arrivals_dropped"] = int(info["arrivals_dropped"])
            row["buffer_overflow"] = int(info["buffer_overflow"])
            row["arrival_seed"] = int(info["arrival_seed"])
            row["updates_per_sec"] = round(info["events"] / elapsed, 3)
            # Control-plane sensors (blades_tpu/control): virtual ticks
            # this cycle spent ingesting (the deterministic twin of
            # updates_per_sec) and the cumulative quarantine-filtered
            # arrival count — host ints, replay-comparable.
            row["cycle_ticks"] = int(info["cycle_ticks"])
            row["arrivals_quarantined"] = int(info["arrivals_quarantined"])
        if self._state_store is not None:
            # Participation-window staging digest (blades_tpu/state):
            # host counters the staging layer already holds.
            # state_peak_hbm_bytes is the analytic
            # ceiling on device-resident per-client state (store-held
            # bytes + the staged/live/write-back cohort slots) — the
            # number the memory-ceiling acceptance test pins against a
            # window-proportional bound.
            stats = (self._state_pf.stats if self._state_pf is not None
                     else self._async.store_stats)
            row["state_store"] = self._state_store.backend
            row["cohort_size"] = (int(self.config.state_window)
                                  if self._state_pf is not None
                                  else int(self._async.agg_every))
            row["state_stage_ms"] = round(stats.last_stage_ms, 3)
            row["state_bytes_staged"] = int(stats.last_bytes_staged)
            row["state_peak_hbm_bytes"] = int(stats.peak_hbm_bytes)
        if self._data_store is not None:
            # Out-of-core data staging digest (blades_tpu/data): host
            # counters the DataPrefetcher already holds.
            # data_bytes_staged is the LAST cohort/
            # event gather's device-put volume, the number the 1M
            # acceptance test pins against a cohort-proportional bound.
            dstats = self._data_pf.stats
            row["data_store"] = self._data_store.backend
            row["data_stage_ms"] = round(dstats.last_stage_ms, 3)
            row["data_bytes_staged"] = int(dstats.last_bytes_staged)
        if self._cache_wrappers:
            # Per-trial AOT compile-cache counters (obs schema fields):
            # cumulative over this trial's dispatches, so the first row
            # already says whether the round program was a hit or a miss.
            row["compile_cache_hits"] = sum(
                w.stats["hits"] for w in self._cache_wrappers)
            row["compile_cache_misses"] = sum(
                w.stats["misses"] for w in self._cache_wrappers)
        # Rounds since the last evaluation, not a modulo of the round
        # number: a restored trial keeps its cadence (the counter is
        # checkpointed).
        if self.config.evaluation_interval and (
            self._rounds_since_eval >= self.config.evaluation_interval
        ):
            self._rounds_since_eval = 0
            row.update(self.evaluate())
        elif self._last_eval:
            row.update(self._last_eval)
        return row

    def _round_cohort(self):
        """The cohort of the round that just ran: ``(ids, staleness)``,
        lane ``i`` of its diag/metrics lanes being registered client
        ``ids[i]``; ``(None, None)`` on the full-participation round
        (the identity arange)."""
        if self._async is not None:
            return (np.asarray(self._async.last_clients, np.int64),
                    np.asarray(self._async.last_staleness, np.int64))
        if self._state_pf is not None and self._window_prev is not None:
            return np.asarray(self._window_prev[0], np.int64), None
        return None, None

    def _fill_round_metrics(self, row: Dict, raw: Dict) -> None:
        """Fill ``row`` with the host form of the round's fetched
        metrics dict.  "lane_" keys are per-lane forensics vectors
        (``(n,)``), kept whole."""
        cohort_ids, cohort_staleness = self._round_cohort()
        metrics, lanes, counters = {}, {}, {}
        for k, v in raw.items():
            a = np.asarray(v)
            if k.startswith("lane_"):
                lanes[k[len("lane_"):]] = a
            elif k.startswith("counter_"):
                # A task's own row counters (parallel/streamed.py), under
                # their schema-registered names: an int stays an int.
                counters[k[len("counter_"):]] = a.item()
            else:
                metrics[k] = float(a)
        row["train_loss"] = metrics["train_loss"]
        row["agg_norm"] = metrics["agg_norm"]
        row["update_norm_mean"] = metrics["update_norm_mean"]
        codec = self.fed_round.codec  # comm subsystem (blades_tpu/comm)
        if codec is not None:
            # Static per-round byte accounting, stamped host-side so the
            # device program carries no extra outputs.  Under a
            # participation window only the sampled cohort transmits —
            # the uplink is window rows, not the registered population.
            uplink_rows = (int(self.config.state_window)
                           if self._state_pf is not None
                           else self.config.num_clients)
            row.update(codec.round_metrics(uplink_rows, self._num_params))
            # Aggregation-domain provenance (wire-domain aggregation):
            # which domain the defenses ran in and the storage width of
            # the matrix they traversed (8 = packed int8 wire payload,
            # 32 = dense f32), so A/B rows are separable in telemetry.
            # Static per round, stamped host-side like the bytes above.
            domain = getattr(self.fed_round, "agg_domain", "f32")
            row["agg_domain"] = domain
            row["agg_domain_bits"] = (codec.storage_bits
                                      if domain == "wire" else 32)
        if "dequant_rows" in metrics:
            # Wire-domain decode accounting: full-width f32 rows
            # materialized from the packed payload this round (selected
            # slices + the forge's sanctioned full read) — the honesty
            # counter next to the 1-byte hbm traversals.
            row["dequant_rows"] = int(metrics["dequant_rows"])
        packing = getattr(self.fed_round, "packing", None)
        if packing is not None:
            # Lane-packing provenance (parallel/packed.py): static per
            # round, stamped host-side like the codec accounting so
            # operators can tell packed from unpacked rows.
            row["pack_factor"] = int(packing.pack)
            row["packed_lanes"] = int(self.config.num_clients
                                      // packing.pack)
        if self._plan is not None:
            # Execution-autotuner provenance (perf/autotune.py): static
            # per trial, stamped host-side so every row names the plan
            # it ran under and how that plan was selected.  The full
            # candidate/timing breakdown rides the sweep summary
            # (plan_summary); rows carry the scalar slice.
            prov = self._plan_provenance or {}
            row["plan_id"] = self._plan.plan_id
            row["autotune_cache_hit"] = bool(prov.get("cache_hit"))
            row["autotune_timed"] = bool(prov.get("timed"))
            row["autotune_candidates"] = len(prov.get("candidates") or [])
        if "hbm_passes" in metrics:
            # Row-geometry pass-fusion accounting (streamed path): planned
            # full-matrix traversals per finish, fused plan vs the
            # per-statistic baseline (parallel/streamed_geometry.py).
            row["hbm_passes"] = int(metrics["hbm_passes"])
            row["hbm_passes_unfused"] = int(metrics["hbm_passes_unfused"])
        if self._hier_recorder is not None:
            # Pod-scale ICI accounting (parallel/hier.py): per-round wire
            # bytes counted at trace time on the PassRecorder (a host
            # int: it outgrows int32 at ResNet width), plus the
            # pre-aggregated matrix height and the engaged device layout
            # — host-side stamps, the hbm_passes pattern.
            row["ici_bytes"] = int(self._hier_recorder.ici_bytes)
            row["preagg_kept"] = int(metrics["preagg_kept"])
            ms = getattr(self.config, "mesh_shape", None) or \
                (int(self.config.num_devices or 1), 1)
            row["mesh_shape"] = f"{int(ms[0])}x{int(ms[1])}"
        if self._gossip_recorder is not None:
            # Decentralized gossip accounting (blades_tpu/topology): the
            # neighborhood-exchange wire bytes counted at trace time on
            # the PassRecorder (a host int), the consensus diameter over
            # round-input replicas, and the graph provenance (static per
            # run, stamped host-side so every row names the topology it
            # gossiped over).
            row["gossip_ici_bytes"] = int(self._gossip_recorder.ici_bytes)
            row["num_partitioned_nodes"] = int(
                metrics["num_partitioned_nodes"])
            row["consensus_dist"] = float(metrics["consensus_dist"])
            prov = self._topology.provenance()
            row["topology"] = str(prov["topology"])
            row["graph_seed"] = int(prov["graph_seed"])
            row["spectral_gap"] = float(prov["spectral_gap"])
        if "elided_lanes" in metrics:
            # Malicious-lane training elision engaged (streamed/d-sharded
            # paths): surfaces the optimistic num_unhealthy basis — an
            # elided lane never trains, so it can never trip the health
            # counters (see parallel/dsharded.py caveats).
            row["elided_lanes"] = int(metrics["elided_lanes"])
        if "store_blocks" in metrics:
            # Streamed path (parallel/streamed.py::block_plan): the
            # round's matrix stores, how many were whole storage tiles at
            # a tile-aligned row, and the lanes its padded last block
            # trained again and dropped.
            for name in ("store_blocks", "store_blocks_aligned",
                         "surplus_lanes"):
                row[name] = int(metrics[name])
        if "finish_stripe_cols" in metrics:
            # A fused finish served the round: its stripe's width, and the
            # rows its Median ordered by a sorting network (0: radix).
            for name in ("finish_stripe_cols", "finish_network_rows"):
                row[name] = int(metrics[name])
        row.update(counters)
        if self.config.fault_config:  # chaos layer (blades_tpu/faults)
            # The round's participation counts, plus the static fault
            # seed so a chaos run's stream is replayable.
            # Async cycles carry no participation mask (dropped arrivals
            # never enter the buffer; the drop counter rides the
            # arrival stamps instead), so only the seed lands here.
            if "num_participating" in metrics:
                for k in ("num_participating", "num_straggled",
                          "num_dropped"):
                    row[k] = int(metrics[k])
            row["fault_seed"] = int(self.fed_round.faults.seed)
        if "staleness_mean" in metrics:
            # Sync straggler path's staleness summary (core/round.py) —
            # the same schema fields the async stamps above use, so
            # sync-vs-async staleness reads from one place.
            row["staleness_mean"] = float(metrics["staleness_mean"])
            row["staleness_max"] = int(metrics["staleness_max"])
        if self.config.health_check or self.config.forensics:
            row["num_unhealthy"] = int(raw["num_unhealthy"])
        if self.config.health_check:  # failure-detection metrics (health.py)
            row["round_ok"] = bool(raw["round_ok"])
        if self.config.forensics:  # defense forensics (obs subsystem)
            for k in ("byz_precision", "byz_recall", "byz_fpr"):
                row[k] = metrics[k]
            row["num_flagged"] = int(metrics["num_flagged"])
            if cohort_ids is None:
                cohort_ids = np.arange(len(lanes["benign_mask"]),
                                       dtype=np.int64)
            # Cohort-shaped bundle: lane i diagnoses registered client
            # clients[i] (the identity arange on dense rounds, so
            # pre-cohort consumers read unchanged).
            row["lane_forensics"] = {
                "benign_mask": [bool(b > 0.5) for b in lanes["benign_mask"]],
                "healthy": [bool(h > 0.5) for h in lanes["healthy"]],
                "scores": [float(s) for s in lanes["scores"]],
                "clients": [int(c) for c in cohort_ids],
                "update_norms": [float(x)
                                 for x in lanes["update_norms"]],
            }
        if self._ledger is not None:
            # Client-lifetime ledger (obs/ledger.py): fold the round's
            # cohort into the longitudinal records — host-side over the
            # already-fetched lanes — then stamp the schema-registered
            # fleet fields into the row.  Without forensics only
            # participation/recency accrue (no diagnosis to fold).
            if self.config.forensics:
                flagged = np.asarray(lanes["benign_mask"]) <= 0.5
                scores = np.asarray(lanes["scores"], np.float64)
                norms = np.asarray(lanes["update_norms"], np.float64)
            else:
                flagged = scores = norms = None
            if cohort_ids is None:
                cohort_ids = np.arange(self.config.num_clients,
                                       dtype=np.int64)
            self._ledger.observe(
                cohort_ids, round=int(row["training_iteration"]),
                tick=row.get("tick"), flagged=flagged, scores=scores,
                staleness=cohort_staleness, norms=norms)
            row.update(self._ledger.round_fields())
        if self._controller is not None:
            # Closed-loop control (blades_tpu/control): runs LAST so the
            # watchdog and policy see the fully-stamped row (ledger
            # fleet fields included).
            self._control_round(row, lanes, cohort_ids)

    def _control_round(self, row: Dict, lanes: Dict, cohort_ids) -> None:
        """One control step over the finalized row: observe the driver's
        watchdog, stamp ``watchdog_events``, let the controller journal
        its policy decisions, apply them to the engine, and stamp the
        action fields the flight recorder replays bit-for-bit."""
        events = [e.as_dict() for e in self._watchdog.observe(row)]
        row["watchdog_events"] = events
        participants: tuple = ()
        flagged_ids: tuple = ()
        if cohort_ids is not None and "benign_mask" in lanes:
            ids = np.asarray(cohort_ids, np.int64)
            bad = np.asarray(lanes["benign_mask"]) <= 0.5
            participants = tuple(int(c) for c in ids)
            flagged_ids = tuple(int(c) for c in ids[bad])
        actions = self._controller.step(
            round_idx=int(row["training_iteration"]),
            tick=int(row.get("tick", row["training_iteration"])),
            events=events,
            suspects=row.get("ledger_top_suspects") or (),
            participants=participants, flagged=flagged_ids)
        for act in actions:
            self._apply_control_action(act)
        row["control_actions"] = [a.as_dict() for a in actions]
        row["control_actions_total"] = int(self._controller.actions_total)
        row["quarantine_size"] = len(self._controller.quarantine)

    def _apply_control_action(self, act) -> None:
        """Actuate one journaled decision.  A rejected engine move is a
        LOUD warning, never a crash — the journal records the intent
        either way, and view/engine divergence must be visible."""
        eng = self._async
        try:
            if act.actuator == "agg_every" and eng is not None:
                eng.set_agg_every(int(act.new))
            elif act.actuator == "buffer_capacity" and eng is not None:
                eng.set_buffer_capacity(int(act.new))
            elif act.actuator == "weight_cutoff" and eng is not None:
                eng.set_weight_cutoff(int(act.new))
            elif act.actuator == "window" and eng is not None:
                # Out-of-core participation window: the event-cohort
                # size under a state store IS the engine's agg_every —
                # a window shrink re-geometries the cycle (and the
                # store gathers) without touching the store itself.
                eng.set_agg_every(int(act.new))
            elif act.actuator in ("quarantine", "probe", "readmit",
                                  "requarantine"):
                if eng is not None:
                    eng.set_quarantine(
                        self._controller.quarantined_clients())
            elif act.actuator == "replan":
                self._replan_runtime()
        except ValueError as exc:
            warnings.warn(
                f"control action {act.actuator} (seq {act.seq}) was "
                f"journaled but the engine rejected it: {exc}",
                RuntimeWarning, stacklevel=2)

    def _replan_runtime(self) -> None:
        """Re-run the execution autotuner against current geometry and
        rebuild the round pipeline when the winner changed (sync
        dense path only — async × autotune is a forbidden config pair,
        and the windowed store must not be rebuilt mid-run)."""
        cfg = self.config
        if (not getattr(cfg, "autotune_mode", None) or self._async is not None
                or self._state_pf is not None or self.mesh is not None):
            return
        from blades_tpu.perf import autotune as at

        mode = cfg.autotune_mode
        space = self._plan_space(
            allow_reassociating=(mode == "reassociating"))
        measure = (at.timed_measure_fn(cfg) if at.timing_available()
                   else None)
        plan, prov = at.select_plan(space, measure_fn=measure)
        prov["mode"] = "replan"
        self._plan_provenance = prov
        if self._plan is not None and plan.as_dict() == self._plan.as_dict():
            return  # the standing plan won again — nothing to rebuild
        self._plan = plan
        self._apply_plan(plan)
        if self._use_streamed():
            # A replan is only offered within the dense plan space (the
            # controller gate above); a streamed resolution here would
            # mean the space drifted — refuse rather than rebuild wrong.
            warnings.warn("replan resolved a streamed plan mid-run; "
                          "keeping the standing pipeline",
                          RuntimeWarning, stacklevel=2)
            return
        self._setup_dense_pipeline()

    def evaluate(self) -> Dict:
        """Weighted per-client evaluation (ref: fedavg.py:247-279)."""
        with self.timers.span("evaluate"):
            if self._eval_chunk_fn is not None:
                # Streaming eval (blades_tpu/data/stream): walk the
                # host test stack in bounded device-sized chunks — the
                # full stack is never device-put.  Differs from the
                # monolithic reduction only in summation order.
                from blades_tpu.data.stream import streaming_evaluate

                ev, n_chunks = streaming_evaluate(
                    self._eval_chunk_fn, self.state.server.params,
                    self._test_arrays,
                    chunk_clients=int(getattr(
                        self.config, "eval_chunk_clients", 256) or 256))
                self._eval_chunks = int(n_chunks)
                self._last_eval = {
                    "test_loss": float(ev["test_loss"]),
                    "test_acc": float(ev["test_acc"]),
                    "test_acc_top3": float(ev["test_acc_top3"]),
                    "eval_chunks": int(n_chunks),
                }
            else:
                ev = self._evaluate(self.state, *self._test_arrays)
                self._last_eval = {
                    "test_loss": float(ev["test_loss"]),
                    "test_acc": float(ev["test_acc"]),
                    "test_acc_top3": float(ev["test_acc_top3"]),
                }
            if self.fed_round.task.sequence:
                # A sequence task's test_loss is the mean token loss.
                self._last_eval["test_perplexity"] = math.exp(
                    min(self._last_eval["test_loss"], 700.0))
        return dict(self._last_eval)

    # -- compiled-cost analysis (obs subsystem) ------------------------------

    _COST_KEYS = ("flops", "bytes accessed", "transcendentals")

    def cost_analysis(self) -> Optional[Dict]:
        """FLOPs / bytes of ONE compiled training dispatch, from XLA's own
        compiler estimate (``lower().compile().cost_analysis()``) — the
        hardware-speed denominator every BENCH MFU number needs.  Memoized
        (lowering re-traces; on backends without a shared AOT executable
        cache that is one extra compile per trial).  ``None`` when the
        executable or backend will not report costs — never raises.
        """
        if hasattr(self, "_cost_analysis"):
            return self._cost_analysis
        cost = None
        try:
            key = jax.random.PRNGKey(0)
            if self._prefetcher is not None:
                # The prebatched round program takes staged batches, not
                # the resident shards — lower it with matching arguments.
                bx, by = self._sample(key)
                args = (self.state, bx, by, self.malicious, key)
            else:
                args = (self.state, *self._train_arrays, self.malicious, key)
            lowered = self._step.lower(*args)
            ca = lowered.compile().cost_analysis()
            if ca:
                cost = {
                    k.replace(" ", "_"): float(ca[k])
                    for k in self._COST_KEYS
                    if isinstance(ca.get(k), (int, float))
                } or None
        except Exception:
            cost = None
        self._cost_analysis = cost
        return cost

    # -- checkpointing (full state; fixes ref gap SURVEY.md §5) --------------

    def save_checkpoint(self, checkpoint_dir: str) -> str:
        path = Path(checkpoint_dir)
        path.mkdir(parents=True, exist_ok=True)
        state_for_pickle = self.state
        if self._state_store is not None:
            # Out-of-core per-client state: drain pending write-backs so
            # the store is authoritative, then checkpoint it as
            # STREAMING per-shard files (ClientStateStore.save — atomic
            # per shard, bounded memory at any population size) instead
            # of pickling the stacks.  The pickled RoundState carries
            # the replicated server only; the disposable cohort copy is
            # reconstructed from the store on resume.
            if self._state_pf is not None:
                self._state_pf.flush()
            state_for_pickle = _dc_replace(
                self.state, client_opt=None, residual=None, cohort=None)
        payload = {
            "iteration": self._iteration,
            "rounds_since_eval": self._rounds_since_eval,
            "key": jax.device_get(self._key),
            "state": jax.device_get(state_for_pickle),
            # Participation-window store provenance (blades_tpu/state):
            # present iff the per-client rows live in the sharded
            # `client_state/` checkpoint next to this pickle.
            "state_store": ({
                "backend": self._state_store.backend,
                "window": (int(self.config.state_window)
                           if self._state_pf is not None else None),
                "n_registered": self.config.num_clients,
            } if self._state_store is not None else None),
            # Out-of-core data provenance (blades_tpu/data): training
            # data is immutable and rebuildable from the dataset, so
            # the shard manifest is REFERENCED, never copied — the
            # checkpoint records which backend/directory served the
            # run and its population; resume re-opens (or rebuilds)
            # the cache from source.
            "data_store": ({
                "backend": self._data_store.backend,
                "dir": getattr(self._data_store, "directory", None),
                "n_clients": int(self._data_store.n_clients),
            } if self._data_store is not None else None),
            # Which client sits in each stacked row (the d-sharded
            # elision layout permutes clients at setup): lets a resume
            # under a DIFFERENT execution mode realign per-client state
            # instead of silently pairing client i's optimizer with
            # client j's data.
            "client_order": (None if self._client_order is None
                             else list(map(int, self._client_order))),
            # Lane-packing provenance.  RoundState stays in the canonical
            # UNPACKED layout on every path (pack/unpack wrap only the
            # local round), so unlike client_order there is nothing to
            # remap on resume — any pack_factor restores any other; the
            # value is recorded so a checkpoint's execution mode is
            # auditable.
            "pack_factor": (int(self.fed_round.packing.pack)
                            if getattr(self.fed_round, "packing", None)
                            is not None else 1),
            # Resolved execution plan (perf/autotune.py), recorded so a
            # kill-and-resume replays the IDENTICAL plan instead of
            # silently re-tuning mid-trajectory: the sweep runner pins
            # it back via config.tuned_plan before rebuilding (see
            # tune/sweep.py _pin_checkpoint_plan); load_checkpoint
            # warns on a mismatch for direct-API resumes.
            "plan": (self._plan.as_dict() if self._plan is not None
                     else None),
            # Buffered-async host state (blades_tpu/arrivals): the
            # virtual tick, version vector, pending arrival buffer and
            # drop counters — with the params-history ring already in
            # `state`, everything kill-and-resume needs to replay the
            # buffered trajectory bit-identically.
            "arrivals": (self._async.host_state()
                         if self._async is not None else None),
            # Closed-loop control state (blades_tpu/control): watchdog
            # rolling windows + controller journal/cooldowns/quarantine
            # — with these a kill-and-resume continues the EXACT action
            # journal a straight-through run would produce (the engine's
            # live actuator values ride the arrivals payload above).
            "control": ({"watchdog": self._watchdog.state(),
                         "controller": self._controller.state()}
                        if self._controller is not None else None),
            "config_dict": {k: v for k, v in self.config.items()
                            if not callable(v)},
        }
        file = path / "algorithm_state.pkl"
        with open(file, "wb") as f:
            pickle.dump(payload, f)
        if self._state_store is not None:
            self._state_store.save(path / "client_state")
        if self._ledger is not None:
            # Streaming shard checkpoint (ClientLedger.save: atomic per
            # shard, manifest-last) — the same contract as client_state/.
            self._ledger.save(path / "ledger")
        return str(file)

    def load_checkpoint(self, checkpoint_path: str) -> None:
        p = Path(checkpoint_path)
        if p.is_dir():
            p = p / "algorithm_state.pkl"
        with open(p, "rb") as f:
            payload = pickle.load(f)
        self._iteration = payload["iteration"]
        self._rounds_since_eval = payload.get("rounds_since_eval", 0)
        saved_plan = payload.get("plan")
        if saved_plan is not None:
            # Through the parser: a plan this build cannot run (an old
            # checkpoint's dispatch window) is refused here, by name.
            from blades_tpu.perf.autotune import Plan

            saved_plan = Plan.from_dict(saved_plan).as_dict()
        cur_plan = self._plan.as_dict() if self._plan is not None else None
        if saved_plan is not None and saved_plan != cur_plan:
            # Plan drift on resume: this instance resolved a different
            # execution plan than the one the checkpoint was written
            # under (a re-tune picked a new winner, or the plan cache
            # moved).  Default-tier plans are bit-identical so the
            # trajectory is safe either way, but reassociating-tier
            # drift silently changes numerics mid-run — surface it and
            # point at the pin.  The sweep runner never hits this: it
            # pins config.tuned_plan from the checkpoint before build.
            warnings.warn(
                f"checkpoint was written under execution plan "
                f"{saved_plan} but this instance resolved {cur_plan}; "
                "pin the saved plan via "
                "FedavgConfig.resources(tuned_plan=...) to replay it "
                "identically", RuntimeWarning, stacklevel=2)
        self._key = jnp.asarray(payload["key"])
        state = jax.tree.map(jnp.asarray, payload["state"])
        # Realign per-client state when the saved client layout differs
        # from this instance's (e.g. a dense-run checkpoint resumed on
        # the d-sharded elision layout, or vice versa).  Saved row j
        # holds client saved_order[j]; this instance's row i must hold
        # client cur_order[i].
        import numpy as np

        n = self.config.num_clients
        saved = payload.get("client_order") or list(range(n))
        cur = (list(range(n)) if self._client_order is None
               else list(map(int, self._client_order)))
        if saved != cur:
            inv_saved = np.argsort(np.asarray(saved))
            remap = jnp.asarray(inv_saved[np.asarray(cur)])
            state = type(state)(
                server=state.server,
                client_opt=jax.tree.map(lambda a: a[remap],
                                        state.client_opt),
                # Stale-update buffer rows are per-client too (chaos
                # layer); remap along its client axis (axis 1).
                stale=(None if getattr(state, "stale", None) is None
                       else state.stale[:, remap]),
                # Error-feedback residual rows are per-client as well
                # (comm subsystem); client axis is axis 0.
                residual=(None if getattr(state, "residual", None) is None
                          else state.residual[remap]),
                # The params-history ring has no client axis — versions
                # are global — so it rides the remap unchanged.
                arrivals=getattr(state, "arrivals", None),
            )
        import dataclasses as _dc

        saved_store = payload.get("state_store")
        if self._state_store is not None:
            ckpt_dir = p.parent
            if saved_store:
                # Streaming shard restore: validates per-shard sizes +
                # CRCs, deletes orphaned .tmp files, fails fast on a
                # torn/corrupt shard (StateStoreError).
                self._state_store.load(ckpt_dir / "client_state")
            elif getattr(state, "client_opt", None) is not None:
                # Monolithic (pre-window / resident-stack) checkpoint
                # resumed under a windowed store: scatter the stacks in.
                rows = {"client_opt": state.client_opt}
                if "residual" in (self._row_template or {}):
                    res = getattr(state, "residual", None)
                    if res is None:
                        # No EF residual in the checkpoint: the store
                        # keeps its cold zeros (the codec cold-start
                        # discipline).
                        rows = {"client_opt": state.client_opt,
                                "residual": np.zeros(
                                    (self.config.num_clients,)
                                    + tuple(self._row_template[
                                        "residual"].shape),
                                    np.float32)}
                    else:
                        rows["residual"] = res
                self._state_store.scatter(
                    np.arange(self.config.num_clients), rows)
                warnings.warn(
                    "resumed a monolithic checkpoint under a windowed "
                    "state store: per-client rows were scattered into "
                    "the store, but the saved aggregator state was "
                    "sized for the full population — stateful "
                    "aggregators may not restore cleanly",
                    RuntimeWarning, stacklevel=2)
            state = _dc.replace(state, client_opt=None, residual=None,
                                cohort=None)
            self._window_prev = None
            if self._state_pf is not None:
                self._state_pf.invalidate()
        elif saved_store:
            # Windowed-store checkpoint resumed on the resident path:
            # materialise the stacks from the shard files (same
            # size/CRC validation as the windowed restore).
            from blades_tpu.state import (client_state_template,
                                          read_checkpoint_rows)

            template = client_state_template(self.fed_round,
                                             state.server.params)
            rows = read_checkpoint_rows(p.parent / "client_state",
                                        template, self.config.num_clients)
            state = _dc.replace(
                state,
                client_opt=jax.tree.map(jnp.asarray, rows["client_opt"]),
                residual=(jnp.asarray(rows["residual"])
                          if "residual" in rows
                          else getattr(state, "residual", None)),
                cohort=None)
            warnings.warn(
                "resumed a windowed-store checkpoint on the resident "
                "path: per-client stacks were rebuilt from the shard "
                "files, but the saved aggregator state was sized for "
                "the window — stateful aggregators may not restore "
                "cleanly", RuntimeWarning, stacklevel=2)

        saved_data = payload.get("data_store")
        if saved_data:
            cur_backend = (self._data_store.backend
                           if self._data_store is not None else "resident")
            if saved_data.get("backend") != cur_backend:
                # Data backends are bit-identical by contract, so this
                # is provenance drift, not a numeric fork — but a
                # resume that silently changed where training shards
                # live should be operator-visible.
                warnings.warn(
                    "checkpoint was written under data_store="
                    f"{saved_data.get('backend')!r}; resuming under "
                    f"{cur_backend!r} (values are unaffected — data "
                    "backends are bit-identical by contract)",
                    RuntimeWarning, stacklevel=2)

        faults = self.fed_round.faults
        if (self._state_store is None and faults is not None
                and faults.needs_stale_buffer
                and getattr(state, "stale", None) is None):
            # Checkpoint from a run without a straggler process resumed
            # under one: start the ring buffer cold (zeros), exactly like
            # a fresh init.
            from blades_tpu.utils.tree import ravel_fn

            _, _, d = ravel_fn(state.server.params)
            state = _dc.replace(state, stale=faults.init_stale_buffer(n, d))
        codec = self.fed_round.codec
        if (self._state_store is None and codec is not None
                and codec.needs_residual
                and getattr(state, "residual", None) is None):
            # Checkpoint from a run without error feedback resumed under
            # a top-k+EF codec: start the residual cold (zeros), exactly
            # like a fresh init.
            from blades_tpu.utils.tree import ravel_fn

            _, _, d = ravel_fn(state.server.params)
            state = _dc.replace(state, residual=codec.init_residual(n, d))
        if self._async is not None:
            arr = payload.get("arrivals")
            if arr:
                self._async.restore_host_state(arr)
            else:
                # Checkpoint from a synchronous run (or from before the
                # arrivals subsystem) resumed under execution='async':
                # the arrival clock starts cold with the version counter
                # synced to the restored round — a fresh traffic
                # trajectory, NOT a bit-identical continuation.
                warnings.warn(
                    "checkpoint carries no arrivals payload; restarting "
                    "the arrival process cold at version "
                    f"{self._iteration} (the traffic trajectory will "
                    "differ from the original run)", RuntimeWarning,
                    stacklevel=2)
                self._async.cold_reset(self._iteration)
            if getattr(state, "arrivals", None) is None:
                # No params-history ring in the checkpoint: seed every
                # retained version with the restored params, exactly
                # like a fresh init.
                state = _dc.replace(
                    state,
                    arrivals=self._async.init_history(state.server.params))
        if self._controller is not None:
            ctl = payload.get("control")
            if ctl:
                self._watchdog.restore_state(ctl.get("watchdog") or {})
                self._controller.restore(ctl.get("controller") or {})
                if self._async is not None:
                    # The engine's live actuator values rode the
                    # arrivals payload; re-assert from the controller's
                    # view only where an older payload left defaults.
                    v = self._controller.values
                    # Under an out-of-core store the `window` view is
                    # the live cohort size (window moves actuate
                    # set_agg_every); prefer it over the untouched
                    # agg_every view so a resumed shrink is kept.
                    want_k = v.get("window") or v.get("agg_every")
                    if want_k and int(want_k) != self._async.agg_every:
                        self._async.set_agg_every(int(want_k))
                    if (v.get("weight_cutoff") is not None
                            and int(v["weight_cutoff"])
                            != self._async.weight_cutoff):
                        self._async.set_weight_cutoff(
                            int(v["weight_cutoff"]))
                    held = self._controller.quarantined_clients()
                    if held != self._async.quarantine:
                        self._async.set_quarantine(held)
            else:
                # Checkpoint from an uncontrolled run resumed under
                # control: the controller starts cold at the restored
                # round — the journal before it is unrecoverable.
                warnings.warn(
                    "checkpoint carries no control payload; the "
                    "controller starts cold at round "
                    f"{self._iteration} (the action journal before it "
                    "is not recoverable)", RuntimeWarning, stacklevel=2)
        if self.mesh is not None:
            if self.config.execution == "gossip":
                # The checkpoint carries the (n_pad, ...) per-node params
                # stack verbatim; re-lay it on the gossip mesh without
                # re-broadcasting (kill-and-resume bit-identity).
                from blades_tpu.topology import reshard_gossip_state

                state = reshard_gossip_state(self.mesh, state)
            else:
                from blades_tpu.parallel import shard_federation

                state, _ = shard_federation(self.mesh, state, ())
        if self._ledger is not None:
            ledger_dir = p.parent / "ledger"
            if (ledger_dir / "manifest.json").exists():
                # Bit-identical longitudinal restore (sizes + CRCs
                # validated per shard; LedgerError on a torn file).
                self._ledger.load(ledger_dir)
            else:
                # Checkpoint from a ledger-less run: the records start
                # cold at the restored round — participation counts
                # before it are unrecoverable, and the warning says so.
                warnings.warn(
                    "checkpoint carries no ledger/ shards; the client "
                    "ledger starts cold at round "
                    f"{self._iteration} (longitudinal records before "
                    "it are not recoverable)", RuntimeWarning,
                    stacklevel=2)
        self.state = state
        if self._prefetcher is not None:
            # The key chain rewound: any staged batches belong to the
            # pre-restore timeline and must not feed a restored round.
            self._prefetcher.invalidate()

    # -- misc ---------------------------------------------------------------

    def stop(self) -> None:
        if self._state_pf is not None:
            self._state_pf.close()
        if self._state_store is not None:
            self._state_store.close()
        if self._data_pf is not None:
            self._data_pf.close()  # closes the DataStore behind it too
        if self._ledger is not None:
            self._ledger.close()

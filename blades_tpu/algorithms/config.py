"""Fluent algorithm config (ref: fllib/algorithms/algorithm_config.py).

Same builder surface as the reference — ``.data() .training() .client()
.adversary() .evaluation() .resources()`` each returning ``self``, a dict
shim (``__getitem__``/``get``/``items``/``update_from_dict``) so YAML
sweeps can treat configs as dicts, ``validate()`` + ``freeze()`` before
``build()`` — but the payload drives the TPU stack: TaskSpec, Server,
FedRound, mesh.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, Optional

from blades_tpu.adversaries import get_adversary
from blades_tpu.core import FedRound, Server, TaskSpec

_INPUT_SHAPES = {
    "mnist": (28, 28, 1),
    "fashionmnist": (28, 28, 1),
    "cifar10": (32, 32, 3),
    "cifar100": (32, 32, 3),
}
_NUM_CLASSES = {"mnist": 10, "fashionmnist": 10, "cifar10": 10, "cifar100": 100}


class FedavgConfig:
    """Builder for :class:`~blades_tpu.algorithms.fedavg.Fedavg`."""

    def __init__(self, algo_class=None):
        from blades_tpu.algorithms import fedavg as _fedavg

        self.algo_class = algo_class or _fedavg.Fedavg
        # data (ref: algorithm_config.py:54-96 defaults)
        self.dataset: Any = "mnist"
        self.num_clients: int = 10
        self.iid: bool = True
        self.dirichlet_alpha: float = 0.1
        self.seed: int = 122  # canonical seed (ref: fedavg_dp.yaml:7-9)
        # model/task
        self.global_model: Any = "mlp"
        self.num_classes: int = 10
        self.input_shape: Optional[tuple] = None
        # client training (ref: client_config.py)
        self.client_lr: float = 0.1
        self.client_momentum: float = 0.0
        self.num_batch_per_round: int = 1  # ref: algorithm_config.py:63
        self.train_batch_size: int = 32
        # benign grad-norm clipping callback (ref: blades/clients/
        # callbacks.py:10-15); None disables
        self.clip_gradient_norm: Optional[float] = None
        # generic client callback chain: list of {"type": ...} specs
        # (ref: fllib/clients/callbacks.py ClientCallbackList)
        self.client_callbacks: Optional[list] = None
        # server (ref: server_config.py)
        self.aggregator: Any = {"type": "Mean"}
        self.server_lr: float = 0.1
        self.server_momentum: float = 0.0
        self.server_dampening: float = 0.0
        self.server_weight_decay: float = 0.0
        self.lr_schedule: Optional[list] = None
        # adversary (ref: blades/algorithms/fedavg/fedavg.py:33-58)
        self.num_malicious_clients: int = 0
        self.adversary_config: Optional[Dict] = None
        # evaluation (ref: algorithm_config.py evaluation_interval)
        self.evaluation_interval: int = 50
        # cap on test rows evaluated PER CLIENT (None = the full per-client
        # test shard).  At 1000 clients the full sharded test set doubles
        # device memory and eval cost for little metric benefit.
        self.evaluation_num_samples: Optional[int] = None
        # dp (ref: blades/clients/dp_client.py) — set via FedavgDPConfig
        self.dp_clip_threshold: Optional[float] = None
        self.dp_noise_factor: Optional[float] = None
        # train-time augmentation; "auto" = by dataset (cifar10 -> crop+flip)
        self.augment: Any = "auto"
        # mixed-precision compute dtype (e.g. "bfloat16"); params stay f32
        self.compute_dtype: Any = None
        # round-pipeline perf layer (blades_tpu/perf):
        # donate_buffers: donate RoundState into each dense dispatch —
        # the stacked client opt states are updated in place instead of
        # copied (halves peak HBM for the largest tensors on that path).
        # Callers must then treat the pre-step state as consumed; see
        # README "Performance".  False restores copying semantics.
        self.donate_buffers: bool = True
        # prefetch: stage the next round's per-client batches while the
        # current round computes (data/prefetch.py).  "auto" (default)
        # = on for the dense single-round dispatch on an accelerator
        # backend (CPU has no overlap to win, so auto skips the second
        # program there); True forces, False disables.  Bit-transparent
        # either way.
        self.prefetch: Any = "auto"
        # execution path: "auto" | "dense" | "streamed" | "dsharded" |
        # "async".  "streamed" runs the single-chip streaming round
        # (parallel/streamed.py) whose bf16 (n, d) update matrix + block
        # dispatches fit giant federations in one chip's HBM; "auto"
        # picks it when the dense f32 matrix would strain HBM (> ~6 GB)
        # and no mesh is requested.  "async" replaces lockstep rounds
        # with buffered-async execution (blades_tpu/arrivals): a
        # deterministic Poisson arrival process drives clients that
        # compute against the global model version they last pulled, and
        # the server fires a staleness-weighted robust aggregation every
        # K buffered arrivals (configure via .arrivals()).
        self.execution: str = "auto"
        # Clients per streamed dispatch: an upper bound; the round takes
        # the largest whole number of storage tiles under it, and pads
        # the last block (parallel/streamed.py::block_plan).
        self.client_block: int = 50
        self.d_chunk: int = 1 << 17        # coords per streamed agg chunk
        self.update_dtype: str = "bfloat16"  # streamed matrix storage
        # MXU finish variant for the streamed pallas finish
        # (ops/pallas_round.py): None = defer to the
        # BLADES_TPU_MXU_FINISH env default, "" = VPU reductions,
        # "counts" = radix counts on the MXU (bit-exact), "all" = also
        # the forged-row stats (bf16-pass MXU precision, ~5e-3 relative
        # on a v5e).  The env var
        # remains an explicit per-process override over this field.
        self.mxu_finish: Optional[str] = None
        # Execution autotuner (perf/autotune.py): False/"off" disables;
        # True/"on" tunes over the numerics-preserving default tier
        # (bit-identical to the untuned path); "reassociating" also
        # offers dense<->streamed<->packed switches and the stats-MXU
        # finish (documented float-reassociation tolerances).  Winners
        # persist to the on-disk plan cache (autotune_cache_dir /
        # $BLADES_TPU_PLAN_CACHE_DIR).  Explicitly-set knobs (execution,
        # d_chunk, client_packing, mxu_finish, prefetch) are never
        # varied — the tuner only resolves what was
        # left at "auto"/default.
        self.autotune: Any = False
        self.autotune_cache_dir: Optional[str] = None
        # Explicit plan pin: a Plan dict (perf.autotune.Plan.as_dict)
        # applied verbatim instead of tuning — how a resumed sweep
        # replays the EXACT plan its checkpoints were written under
        # (no silent re-tune drift mid-trajectory), and how operators
        # pin a plan from tools/show_plan.py output.
        self.tuned_plan: Optional[Dict] = None
        # client lane-packing (parallel/packed.py): fold P clients into
        # one grouped-kernel vmap lane on the dense path.  "off" | "auto"
        # (pack_factor 2 iff the width/divisibility/hook heuristic passes,
        # LOUD warning + unpacked fallback otherwise) | int P >= 2
        # (forced; structural impossibilities raise).  Updates are
        # unpacked to the dense (n, d) matrix before forging/codecs/
        # faults/aggregation, and checkpoints stay layout-free.
        self.client_packing: Any = "off"
        # Out-of-core per-client state (blades_tpu/state): where the
        # persistent per-client rows (optimizer state, codec EF
        # residual) live.  "resident" (default) = today's dense device
        # stack — with state_window=None the round program, pytrees and
        # checkpoints are LITERALLY unchanged.  "host"/"disk" require a
        # participation window (state_window >= 1): only the sampled
        # cohort's rows are device-resident each round; the registered-
        # population remainder lives in pinned host arrays / a sharded
        # memory-mapped store, with the next cohort staged while the
        # current round computes.  All three backends are bit-identical
        # for the same (seed, cohort schedule).
        self.state_store: str = "resident"
        # Participation window: clients sampled (without replacement,
        # pure in the round key) into each round's cohort.  None = full
        # participation with resident stacks (the pre-window program);
        # 0 = STATELESS clients (full participation, per-client
        # optimizer state re-initialized every round — the degenerate
        # case where there is nothing to store); >= 1 = windowed cohort
        # execution (dense single-chip only).  Set via
        # .resources(window=...).
        self.state_window: Optional[int] = None
        # Directory for the "disk" backend's live sharded memmaps
        # (None = a private temp dir, removed when the trial stops).
        # Checkpoints stream their own per-shard files either way.
        self.state_dir: Optional[str] = None
        # Out-of-core TRAINING DATA (blades_tpu/data/store.py): where
        # the per-client (x, y, lengths) partition lives on the
        # windowed / out-of-core-async paths.  "resident" (default)
        # keeps the host numpy stacks and stages cohorts exactly as
        # before — bit-identical by construction.  "memmap" spills the
        # partition to sharded on-disk .npy files (CRC'd manifest,
        # ClientStateStore's shard discipline) and gathers only the
        # cohort's rows per round, so host RSS scales with the COHORT,
        # not the registered population; eval streams the test stack
        # through the device in bounded chunks.  Both backends are
        # bit-identical for the same (seed, cohort schedule).  Ignored
        # (must stay "resident") on the dense full-participation paths,
        # which never stage per-cohort data.
        self.data_store: str = "resident"
        # Directory for the memmap data store's live shards (None = a
        # private temp dir, removed when the trial stops).  A directory
        # whose manifest + CRCs match the partition is REUSED on
        # resume; any mismatch rebuilds the shards from source.
        self.data_dir: Optional[str] = None
        # Streaming-eval chunk size (clients per jitted eval dispatch)
        # when data_store="memmap" — the device holds one chunk of the
        # test stack at a time, never the full population.
        self.eval_chunk_clients: int = 256
        # failure detection / elastic recovery (core/health.py): zero
        # non-finite client lanes, skip non-finite server updates
        self.health_check: bool = False
        # chaos layer (blades_tpu/faults): deterministic fault-injection
        # spec, e.g. {"dropout_rate": 0.3, "num_stragglers": 1,
        # "staleness": 2, "corrupt_rate": 0.01, "corrupt_mode": "nan",
        # "seed": 7}.  Seed defaults to the trial seed.  None disables —
        # the round program is then bit-identical to a faultless build.
        self.fault_config: Optional[Dict] = None
        # comm subsystem (blades_tpu/comm): compressed-update codec spec,
        # e.g. {"type": "quant", "bits": 8} or {"type": "topk",
        # "topk_ratio": 0.01, "error_feedback": True}.  Encode->decode
        # runs inside the jitted round before robust aggregation; per-
        # round comm_bytes_up / codec_bits / compression-ratio metrics
        # are stamped into the obs stream.  None disables — the round
        # program is then bit-identical to a codec-free build (and
        # {"type": "identity"} is a regression-tested no-op).
        self.codec_config: Optional[Dict] = None
        # Aggregation domain under a codec: "f32" (default) decodes the
        # wire payload to dense f32 before the defenses — bit-identical
        # to the pre-wire-domain program; "wire" keeps quantized updates
        # packed (int8 + per-row scales) through the defense statistics
        # (Server.step_wire / streamed_geometry.aggregate_wire) — the
        # hottest traversals read 1 byte/coordinate instead of 4, per-row
        # scales apply algebraically, adversaries still forge post-codec
        # (in the quantized domain; their rows re-enter the same wire).
        # Requires a deferrable codec (identity/quant — identity is a
        # regression-tested bit-identical pass-through), dense
        # single-chip execution, and none of faults/health/forensics/DP.
        # The autotuner's reassociating tier probes this knob
        # (agg_domain in its plan space); the default tier never does.
        self.agg_domain: str = "f32"
        # buffered-async execution (blades_tpu/arrivals): the arrival /
        # buffering / staleness-weighting spec for execution="async",
        # e.g. {"rate": 0.25, "agg_every": 16, "staleness_cap": 8,
        # "weight_schedule": "polynomial"}.  The arrival seed defaults
        # to the trial seed; set an explicit "seed" to pin the traffic
        # realization across a training-seed grid.  None with
        # execution="async" runs the AsyncSpec defaults; setting it
        # WITHOUT execution="async" is a validate()-time error.
        self.async_config: Optional[Dict] = None
        # defense forensics (obs subsystem): per-lane aggregator telemetry
        # + Byzantine detection precision/recall/FPR emitted from inside
        # the jitted round.  Cohort-shaped: the dense round's lanes are
        # registered clients, the windowed round's lanes are the sampled
        # cohort, the async cycle's lanes are buffered arrival events —
        # each row's lane_forensics carries the cohort id-vector that
        # maps lanes back to registered ids.  Single-chip; the
        # streamed/d-sharded paths never materialise per-lane decisions.
        self.forensics: bool = False
        # Client-lifetime ledger (obs/ledger.py): one longitudinal
        # record per registered client (participation/flagged counts,
        # detection-score EWMA, staleness/norm running stats), updated
        # host-side per round.  False disables; True = the "resident"
        # host-RAM backend; "resident"|"disk" select explicitly ("disk"
        # memmaps the columns for 100k+ registered clients).
        self.ledger: Any = False
        # Directory for the disk ledger's live memmap columns (None = a
        # private temp dir, removed when the trial stops).
        self.ledger_dir: Optional[str] = None
        # Watchdog rule overrides (obs/watchdog.py): a list of rule
        # dicts ({"name", "kind", "field", + window/min_points/factor/
        # threshold}) REPLACING the built-in table — the
        # ``--watchdog-rules`` CLI surface.  Unknown keys, unknown
        # kinds and unknown fields fail at validate().  None keeps
        # ``default_rules()``.
        self.watchdog_rules: Optional[list] = None
        # Closed-loop control plane (blades_tpu/control): watchdog
        # events drive bounded, journaled actuator moves (shrink
        # agg_every, grow the arrival buffer / relax the staleness
        # cutoff, quarantine-and-probe ledger suspects, re-run the
        # autotuner).  A dict of ControlPolicy knobs + {"enabled":
        # bool, "rules": {rule-name: actuator-family | "off"}}; set via
        # .control(...).  None disables — rounds are then bit-identical
        # to an uncontrolled build.
        self.control_config: Optional[Dict] = None
        # server root-dataset size for trust-bootstrapped aggregators (FLTrust)
        self.fltrust_root_size: int = 100
        # resources
        self.num_devices: Optional[int] = None
        # Pod-scale 2-D device layout (parallel/mesh.py): a (clients, d)
        # axis pair tiling exactly num_devices chips — client blocks
        # shard along "clients", the hierarchical gather splits along
        # "d".  None keeps the canonical 1-D (clients,) mesh, so every
        # existing multi-chip config is unchanged.  Set via
        # .resources(mesh_shape=(c, dd)).
        self.mesh_shape: Optional[tuple] = None
        # Hierarchical pre-aggregation (execution="hier", ops/preagg.py):
        # the per-shard robust reduction flavor ("bucket" = s-bucketing
        # means, "nnm" = nearest-neighbor mixing) and its one size knob.
        # bucket_size=1 is the identity pre-agg for BOTH flavors — the
        # hierarchical round is then bit-identical to single-chip dense.
        self.preagg: str = "bucket"
        self.bucket_size: int = 1
        # Decentralized gossip federation (execution="gossip",
        # blades_tpu/topology): the peer-graph spec — a dict for
        # TopologyConfig (graph/k/p/graph_seed/mixing; num_nodes is
        # pinned to num_clients) or a bare graph name.  None with
        # execution="gossip" runs the TopologyConfig defaults (ring);
        # setting it WITHOUT execution="gossip" is a validate()-time
        # error.  Set via .topology(...).
        self.topology_config: Optional[Dict] = None
        self._frozen = False
        # Packing decision from the last get_fed_round() resolution
        # (requested/pack_factor/packed_lanes/fallback) — surfaced in
        # sweep trial summaries so operators can tell packed from
        # unpacked runs without reading logs.
        self._packing_decision = None
        # Names of fields whose values were INFERRED by validate() rather
        # than set by the user — retargeting the dataset resets them so a
        # copy()-then-rebuild re-infers instead of keeping stale values
        # (VERDICT r1: the reference freezes after validate for this).
        self._inferred: set = set()
        # Names of fields the USER set (fluent setters / dict merge),
        # as opposed to class defaults.  The execution autotuner's
        # composition contract keys off this: an explicitly-set knob is
        # pinned in the plan space, a defaulted one may be tuned.
        self._explicit: set = set()

    # -- fluent setters ------------------------------------------------------

    def _assign(self, k, v):
        """Single field-assignment point for every setter path (fluent
        and dict merge): explicit values beat inferred ones, and
        retargeting the dataset resets fields a previous validate()
        inferred from it (copy() a built cifar10 config, point it at
        mnist, rebuild — stale shape/classes must not survive)."""
        if k == "dataset":
            if "input_shape" in self._inferred:
                self.input_shape = None
                self._inferred.discard("input_shape")
            if "num_classes" in self._inferred:
                self.num_classes = 10
                self._inferred.discard("num_classes")
        setattr(self, k, v)
        self._inferred.discard(k)
        self._explicit.add(k)

    def _set(self, **kw):
        if self._frozen:
            raise RuntimeError("config is frozen (ref: algorithm_config.py freeze)")
        for k, v in kw.items():
            if v is not None:
                self._assign(k, v)
        return self

    def data(self, *, dataset=None, num_clients=None, iid=None,
             dirichlet_alpha=None, seed=None):
        return self._set(dataset=dataset, num_clients=num_clients, iid=iid,
                         dirichlet_alpha=dirichlet_alpha, seed=seed)

    def training(self, *, global_model=None, num_classes=None, input_shape=None,
                 aggregator=None, server_lr=None, server_momentum=None,
                 server_dampening=None, server_weight_decay=None,
                 lr_schedule=None, num_batch_per_round=None,
                 train_batch_size=None):
        return self._set(
            global_model=global_model, num_classes=num_classes,
            input_shape=input_shape, aggregator=aggregator,
            server_lr=server_lr, server_momentum=server_momentum,
            server_dampening=server_dampening,
            server_weight_decay=server_weight_decay, lr_schedule=lr_schedule,
            num_batch_per_round=num_batch_per_round,
            train_batch_size=train_batch_size,
        )

    def client(self, *, lr=None, momentum=None, clip_gradient_norm=None,
               callbacks=None):
        return self._set(client_lr=lr, client_momentum=momentum,
                         clip_gradient_norm=clip_gradient_norm,
                         client_callbacks=callbacks)

    def adversary(self, *, num_malicious_clients=None, adversary_config=None):
        return self._set(num_malicious_clients=num_malicious_clients,
                         adversary_config=adversary_config)

    def evaluation(self, *, evaluation_interval=None, num_samples=None):
        return self._set(evaluation_interval=evaluation_interval,
                         evaluation_num_samples=num_samples)

    def resources(self, *, num_devices=None, execution=None, client_block=None,
                  d_chunk=None, update_dtype=None, compute_dtype=None,
                  client_packing=None, mxu_finish=None, autotune=None,
                  autotune_cache_dir=None, tuned_plan=None,
                  state_store=None, window=None, state_dir=None,
                  data_store=None, data_dir=None, eval_chunk_clients=None,
                  mesh_shape=None, preagg=None, bucket_size=None):
        """``state_store=`` / ``window=`` / ``state_dir=`` configure the
        out-of-core participation-window store (blades_tpu/state):
        ``window`` is the per-round cohort size (``0`` = stateless
        clients, the degenerate case), ``state_store`` where the
        off-cohort rows live (``resident`` | ``host`` | ``disk``).
        ``window=0`` must be passed explicitly — ``_set`` drops
        ``None`` kwargs, so the sentinel distinction is deliberate.
        ``data_store=`` / ``data_dir=`` / ``eval_chunk_clients=`` are
        the TRAINING-DATA analogue (blades_tpu/data/store.py):
        ``memmap`` spills the partition to disk shards and streams
        eval in device-sized chunks."""
        if window is not None:
            self._set(state_window=int(window))
        return self._set(num_devices=num_devices, execution=execution,
                         client_block=client_block, d_chunk=d_chunk,
                         update_dtype=update_dtype,
                         compute_dtype=compute_dtype,
                         client_packing=client_packing,
                         mxu_finish=mxu_finish, autotune=autotune,
                         autotune_cache_dir=autotune_cache_dir,
                         tuned_plan=tuned_plan, state_store=state_store,
                         state_dir=state_dir, data_store=data_store,
                         data_dir=data_dir,
                         eval_chunk_clients=eval_chunk_clients,
                         mesh_shape=mesh_shape,
                         preagg=preagg, bucket_size=bucket_size)

    def fault_tolerance(self, *, health_check=None, faults=None):
        """In-round failure detection / elastic recovery (core/health.py)
        and the chaos layer's fault-injection spec (``faults=`` a dict for
        :class:`blades_tpu.faults.FaultInjector`); the trial-level
        analogue is ``run_experiments(max_failures=)``."""
        return self._set(health_check=health_check, fault_config=faults)

    def arrivals(self, *, rate=None, rate_schedule=None, slow_fraction=None,
                 slow_factor=None, agg_every=None, buffer_capacity=None,
                 staleness_cap=None, weight_schedule=None, weight_power=None,
                 weight_cutoff=None, seed=None, max_ticks_per_cycle=None,
                 ticks_per_sec=None):
        """Buffered-async arrival spec (:class:`blades_tpu.arrivals.
        AsyncSpec`) for ``execution="async"``: the Poisson arrival rate
        (+ schedule / slow-cohort knobs), the FedBuff buffer geometry
        (``agg_every`` K, bounded ``buffer_capacity``), the params-
        history depth (``staleness_cap`` H) and the staleness weight
        schedule.  ``ticks_per_sec`` is a pure CALIBRATION label (virtual
        ticks per wall second) that lets ``updates_per_sec`` targets
        drive buffer/agg_every sizing via
        :func:`blades_tpu.arrivals.size_for_target`; it never enters the
        arrival realization, which stays pure in ``(seed, tick)``.
        Merges into ``async_config``; see the README "Async buffered
        execution" section."""
        spec = dict(self.async_config or {})
        for k, v in (("rate", rate), ("rate_schedule", rate_schedule),
                     ("slow_fraction", slow_fraction),
                     ("slow_factor", slow_factor), ("agg_every", agg_every),
                     ("buffer_capacity", buffer_capacity),
                     ("staleness_cap", staleness_cap),
                     ("weight_schedule", weight_schedule),
                     ("weight_power", weight_power),
                     ("weight_cutoff", weight_cutoff), ("seed", seed),
                     ("max_ticks_per_cycle", max_ticks_per_cycle),
                     ("ticks_per_sec", ticks_per_sec)):
            if v is not None:
                spec[k] = v
        return self._set(async_config=spec or None)

    def observability(self, *, forensics=None, ledger=None, ledger_dir=None,
                      watchdog_rules=None):
        """Defense forensics (per-lane aggregator diagnostics + Byzantine
        detection precision/recall/FPR per round), the client-lifetime
        ledger (``ledger=True`` for the resident backend, ``"disk"`` to
        memmap the columns; ``ledger_dir=`` the disk backend's live
        directory) and the watchdog rule table (``watchdog_rules=`` a
        list of rule dicts replacing ``default_rules()``; the
        ``--watchdog-rules`` CLI flag routes here) — the obs
        subsystem."""
        return self._set(forensics=forensics, ledger=ledger,
                         ledger_dir=ledger_dir,
                         watchdog_rules=watchdog_rules)

    def control(self, *, enabled=None, rules=None, cooldown_rounds=None,
                quarantine_rounds=None, quarantine_max=None,
                max_quarantine_fraction=None, min_agg_every=None,
                agg_every_factor=None, buffer_factor=None,
                max_buffer_capacity=None, cutoff_factor=None,
                max_weight_cutoff=None, min_window=None,
                window_factor=None):
        """Closed-loop control plane (:mod:`blades_tpu.control`):
        watchdog events drive bounded, rate-limited, journaled actuator
        moves.  ``rules=`` maps watchdog rule NAMES to actuator families
        (``agg_every`` | ``buffer`` | ``quarantine`` | ``replan`` |
        ``window`` | ``"off"``), merged over the default table; the
        remaining knobs are :class:`~blades_tpu.control.ControlPolicy`
        bounds and rate limits (``min_window``/``window_factor`` bound
        the out-of-core shrink-only ``window`` family).  Merges into
        ``control_config`` (the ``.arrivals()`` pattern); a bare
        ``.control()`` arms the defaults.  See the README "Control
        plane" section."""
        spec = dict(self.control_config or {})
        for k, v in (("enabled", enabled), ("rules", rules),
                     ("cooldown_rounds", cooldown_rounds),
                     ("quarantine_rounds", quarantine_rounds),
                     ("quarantine_max", quarantine_max),
                     ("max_quarantine_fraction", max_quarantine_fraction),
                     ("min_agg_every", min_agg_every),
                     ("agg_every_factor", agg_every_factor),
                     ("buffer_factor", buffer_factor),
                     ("max_buffer_capacity", max_buffer_capacity),
                     ("cutoff_factor", cutoff_factor),
                     ("max_weight_cutoff", max_weight_cutoff),
                     ("min_window", min_window),
                     ("window_factor", window_factor)):
            if v is not None:
                spec[k] = v
        if not spec:
            spec = {"enabled": True}  # bare .control() arms the defaults
        return self._set(control_config=spec)

    def topology(self, *, graph=None, k=None, p=None, graph_seed=None,
                 mixing=None):
        """Peer-graph spec for ``execution="gossip"``
        (:class:`blades_tpu.topology.TopologyConfig`): the named graph
        family (``ring`` | ``torus`` | ``kregular`` | ``erdos`` |
        ``complete``), its one size knob (``k`` for kregular, ``p`` for
        erdos), the Erdős–Rényi draw seed and the doubly-stochastic
        mixing scheme (``metropolis`` | ``uniform``).  Merges into
        ``topology_config`` (the ``.arrivals()`` pattern); see the
        README "Decentralized gossip federation" section."""
        spec = dict(self.topology_config or {})
        for key, v in (("graph", graph), ("k", k), ("p", p),
                       ("graph_seed", graph_seed), ("mixing", mixing)):
            if v is not None:
                spec[key] = v
        return self._set(topology_config=spec or None)

    def communication(self, *, codec=None, agg_domain=None):
        """Compressed-update codec on the client->server uplink
        (``codec=`` a dict for :class:`blades_tpu.comm.CodecConfig`,
        e.g. ``{"type": "topk", "topk_ratio": 0.01}``) and the
        aggregation domain (``agg_domain="f32"|"wire"`` — "wire" keeps
        quantized payloads packed through the defense statistics); see
        the README "Communication codecs" section for the interaction
        matrix."""
        return self._set(codec_config=codec, agg_domain=agg_domain)

    # -- dict shim (ref: algorithm_config.py:253-293,360-379) ----------------

    _KEYS = None

    def keys(self):
        return [k for k in vars(self) if not k.startswith("_") and k != "algo_class"]

    def __getitem__(self, k):
        return getattr(self, k)

    def get(self, k, default=None):
        return getattr(self, k, default)

    def items(self):
        return [(k, getattr(self, k)) for k in self.keys()]

    def update_from_dict(self, d: Dict[str, Any]) -> "FedavgConfig":
        """Partial-dict merge (ref: algorithm_config.py:397-453).

        Accepts both flat keys and the reference's YAML nesting
        (``dataset_config``, ``client_config``, ``server_config``,
        ``adversary_config``).
        """
        d = copy.deepcopy(dict(d))
        nested_maps = {
            "dataset_config": {"type": "dataset", "num_clients": "num_clients",
                               "iid": "iid", "alpha": "dirichlet_alpha",
                               "train_bs": "train_batch_size",
                               "num_classes": "num_classes", "seed": "seed"},
            "client_config": {"lr": "client_lr", "momentum": "client_momentum",
                              "num_batch_per_round": "num_batch_per_round",
                              "clip_gradient_norm": "clip_gradient_norm",
                              "callbacks": "client_callbacks"},
            "server_config": {"lr": "server_lr", "momentum": "server_momentum",
                              "dampening": "server_dampening",
                              "weight_decay": "server_weight_decay",
                              "aggregator": "aggregator",
                              "lr_schedule": "lr_schedule"},
        }
        for nk, mapping in nested_maps.items():
            sub = d.pop(nk, None)
            if sub:
                for sk, sv in sub.items():
                    if sk in mapping:
                        self._assign(mapping[sk], sv)
                    else:
                        raise KeyError(f"unknown {nk} key {sk!r}")
        if "adversary_config" in d:
            self.adversary_config = d.pop("adversary_config")
        for k, v in d.items():
            if k in self.keys():
                self._assign(k, v)
            else:
                raise KeyError(f"unknown config key {k!r}")
        return self

    # -- validation / build --------------------------------------------------

    def validate(self) -> None:
        """(ref: algorithm_config.py:295-315)"""
        if self.num_malicious_clients > self.num_clients // 2:
            raise ValueError(
                f"num_malicious_clients={self.num_malicious_clients} is a "
                f"majority of num_clients={self.num_clients}; Byzantine "
                "robustness is undefined past 50%"
            )
        if self.num_malicious_clients > 0 and not self.adversary_config:
            raise ValueError("num_malicious_clients > 0 requires adversary_config")
        if isinstance(self.dataset, str):
            name = self.dataset
        elif isinstance(self.dataset, dict):
            name = self.dataset.get("type")  # catalog dict spec
        else:
            name = getattr(self.dataset, "name", None)
        name = name.lower() if isinstance(name, str) else None
        if self.input_shape is None:
            if name in _INPUT_SHAPES:
                self.input_shape = _INPUT_SHAPES[name]
                self._inferred.add("input_shape")
            else:
                raise ValueError(
                    "input_shape could not be inferred; set "
                    ".training(input_shape=...)"
                )
        # A known dataset with a non-10-class label space overrides the
        # default num_classes (a 10-way head on CIFAR-100 is never right).
        if name in _NUM_CLASSES and self.num_classes == 10:
            self.num_classes = _NUM_CLASSES[name]
            self._inferred.add("num_classes")
        if self.execution not in ("auto", "dense", "streamed", "dsharded",
                                  "async", "hier", "gossip"):
            raise ValueError(
                "execution must be auto|dense|streamed|dsharded|async|hier"
                f"|gossip, got {self.execution!r}"
            )
        if self.topology_config and self.execution != "gossip":
            raise ValueError(
                "topology_config is set but execution="
                f"{self.execution!r}: the peer-graph spec only drives the "
                "decentralized gossip path — set "
                ".resources(execution='gossip') or drop .topology(...)"
            )
        if self.execution == "gossip":
            # Build the topology now so a bad (graph, knob) pair fails at
            # validate() time (TopologyConfig.__post_init__ builds the
            # adjacency) — the faults/codec fail-fast discipline.
            self.get_topology()
            for knob, why, flip in (
                (self.codec_config, "update codecs",
                 ".communication(codec=None)"),
                (self.agg_domain != "f32", "wire-domain aggregation",
                 ".communication(agg_domain='f32')"),
                (self.client_packing not in ("off", None),
                 "client lane-packing",
                 ".resources(client_packing='off')"),
                (self.state_window is not None,
                 "the participation-window store",
                 ".resources(window=None)"),
                (self.state_store != "resident",
                 "out-of-core client state",
                 ".resources(state_store='resident')"),
                (self.forensics, "defense forensics",
                 ".observability(forensics=False)"),
                (self.ledger_backend, "the client ledger",
                 ".observability(ledger=False)"),
                (self.control_config, "the control plane",
                 "drop .control()"),
                (self.autotune_mode, "the execution autotuner",
                 ".resources(autotune='off')"),
                (self.mesh_shape is not None, "2-D mesh_shape",
                 ".resources(mesh_shape=None)"),
            ):
                if knob:
                    raise ValueError(
                        f"execution='gossip' × {why} is an unsupported "
                        "pair: the decentralized round has no central "
                        "server matrix for that stage to rewrite — set "
                        f"{flip}, or use a server execution path"
                    )
            injector = self.get_fault_injector()
            if injector is not None:
                if injector.needs_stale_buffer:
                    raise ValueError(
                        "execution='gossip' × straggler faults is an "
                        "unsupported pair: the stale ring buffer is a "
                        "server-path process — gossip faults are EDGE "
                        "dropout (dropout_rate/dropout_schedule); set "
                        "num_stragglers=0"
                    )
                if injector.corrupt_rate > 0.0:
                    raise ValueError(
                        "execution='gossip' × corruption faults is an "
                        "unsupported pair: lane corruption models "
                        "server-bound transfers — gossip faults are EDGE "
                        "dropout; set corrupt_rate=0"
                    )
        if self.async_config and self.execution != "async":
            raise ValueError(
                "async_config is set but execution="
                f"{self.execution!r}: the arrival spec only drives the "
                "buffered-async path — set .resources(execution='async') "
                "or drop .arrivals(...)"
            )
        if self.execution == "async":
            # Build the spec now so a bad arrival/buffer/weight knob
            # fails at validate() time (AsyncSpec.__post_init__ range-
            # checks everything) — the faults/codec fail-fast discipline.
            spec = self.get_async_spec()
            if spec.agg_every > self.num_clients:
                raise ValueError(
                    f"async agg_every={spec.agg_every} > num_clients="
                    f"{self.num_clients}: a cycle aggregates at most one "
                    "event per client"
                )
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "execution='async' × num_devices>1 is an unsupported "
                    "pair: the buffered cycle program has no mesh "
                    "formulation — set .resources(num_devices=None), or "
                    "use a synchronous execution path on the mesh"
                )
            # Defense forensics COMPOSES with async since the cohort-
            # shaped forensics work: the cycle diagnoses the (K, d)
            # event matrix and lanes are re-indexed by the event
            # id-vector (Server.step_buffered_diag).  The remaining
            # gates name the exact pair and the knob that flips it.
            for knob, why, flip in (
                (self.codec_config, "update codecs",
                 ".communication(codec=None)"),
                (self.agg_domain != "f32", "wire-domain aggregation",
                 ".communication(agg_domain='f32')"),
                (self.client_packing not in ("off", None),
                 "client lane-packing",
                 ".resources(client_packing='off')"),
                (self.autotune_mode, "the execution autotuner",
                 ".resources(autotune='off')"),
                (self.health_check, "the in-round health check",
                 ".fault_tolerance(health_check=False)"),
                (self.dp_clip_threshold, "client DP",
                 "dp_clip_threshold=None"),
            ):
                if knob:
                    raise ValueError(
                        f"execution='async' × {why} is an unsupported "
                        "pair: the buffered cycle aggregates arrival "
                        "EVENTS, not the lockstep (n, d) round that "
                        f"stage is formulated over — set {flip}, or use "
                        "a synchronous execution path"
                    )
            injector = self.get_fault_injector()
            if injector is not None and injector.num_stragglers:
                raise ValueError(
                    "execution='async' subsumes the straggler fault "
                    "process (staleness is first-class in the arrival "
                    "model); set num_stragglers=0 — dropout and "
                    "corruption compose with async arrivals as-is"
                )
        if self.execution == "dsharded":
            if not self.num_devices or self.num_devices < 2:
                raise ValueError(
                    "execution='dsharded' width-shards the update matrix "
                    "over a mesh; set .resources(num_devices=...) > 1"
                )
        # Pod-scale knobs (parallel/hier.py): fail-fast on every
        # structural impossibility, naming the exact pair and the knob
        # that flips it.
        from blades_tpu.ops.preagg import PREAGG_FLAVORS

        if self.preagg not in PREAGG_FLAVORS:
            raise ValueError(
                f"preagg must be one of {PREAGG_FLAVORS}, got "
                f"{self.preagg!r}")
        if not isinstance(self.bucket_size, int) or self.bucket_size < 1:
            raise ValueError(
                f"bucket_size must be an int >= 1, got {self.bucket_size!r}")
        if self.mesh_shape is not None:
            ms = tuple(int(v) for v in self.mesh_shape)
            if len(ms) != 2 or min(ms) < 1:
                raise ValueError(
                    f"mesh_shape must be a (clients, d) pair of positive "
                    f"ints, got {self.mesh_shape!r}")
            self.mesh_shape = ms
            if not self.num_devices or self.num_devices < 2:
                raise ValueError(
                    "mesh_shape × single-chip is an unsupported pair: the "
                    "2-D (clients, d) layout tiles a multi-chip mesh — "
                    "set .resources(num_devices=...) > 1, or drop "
                    "mesh_shape")
            if ms[0] * ms[1] != self.num_devices:
                raise ValueError(
                    f"mesh_shape {ms[0]}x{ms[1]} must tile exactly "
                    f"num_devices={self.num_devices} chips — fix one of "
                    "the two in .resources(...)")
        if self.execution == "hier":
            if not self.num_devices or self.num_devices < 2:
                raise ValueError(
                    "execution='hier' pre-aggregates per chip and gathers "
                    "representatives over a mesh; set "
                    ".resources(num_devices=...) > 1"
                )
        if self.execution == "streamed":
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "execution='streamed' × num_devices>1 is an unsupported "
                    "pair: streamed is the single-chip giant-federation "
                    "path — set .resources(num_devices=None), or use a "
                    "mesh execution (dsharded/hier) for multi-chip"
                )
        if self.forensics:
            if self.execution in ("streamed", "dsharded"):
                raise ValueError(
                    f"forensics × execution={self.execution!r} is an "
                    "unsupported pair: the streamed/d-sharded paths never "
                    "materialise the per-lane decisions forensics reports "
                    "— set .resources(execution='dense') (or 'auto' "
                    "within the dense budget), or flip "
                    ".observability(forensics=False)"
                )
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "forensics × num_devices>1 is an unsupported pair: "
                    "per-lane diagnostics under shard_map would shard "
                    "the lane axis — set .resources(num_devices=None), "
                    "or flip .observability(forensics=False)"
                )
        if self.fault_config:
            # Build the injector now so a bad spec fails at validate()
            # time (FaultInjector.__post_init__ range-checks every knob).
            self.get_fault_injector()
            if self.execution in ("streamed", "dsharded"):
                raise ValueError(
                    "fault injection (fault_config) is only formulated for "
                    "the dense round — the streamed/d-sharded paths never "
                    "materialise the participation mask the masked "
                    "aggregators consume; use execution='dense' (or 'auto' "
                    "within the dense budget) or disable faults"
                )
            if self.num_devices and self.num_devices > 1:
                # The hierarchical path gathers the full update matrix
                # replicated before injection, so the chaos layer
                # composes there — as long as the pre-aggregation keeps
                # matrix height (kept == n) and no straggler ring is
                # configured (the stale buffer is sized per LANE).  The
                # gossip path composes too, with its OWN edge-dropout
                # realization (gated above, not injector.inject).
                if self.execution not in ("hier", "gossip"):
                    raise ValueError(
                        "fault injection × num_devices>1 is an "
                        "unsupported pair on the flat mesh paths: the "
                        "participation mask under shard_map would shard "
                        "the lane axis — set .resources(num_devices=None) "
                        "or .resources(execution='hier'), or drop faults"
                    )
                if self.execution == "hier":
                    injector = self.get_fault_injector()
                    if injector is not None and injector.needs_stale_buffer:
                        raise ValueError(
                            "execution='hier' × straggler faults is an "
                            "unsupported pair: the stale ring buffer is "
                            "sized per lane and has no hierarchical "
                            "formulation — set num_stragglers=0, or run "
                            "single-chip"
                        )
                    if self.preagg == "bucket" and self.bucket_size != 1:
                        raise ValueError(
                            "execution='hier' × fault injection needs an "
                            "identity-height pre-aggregation (bucketing "
                            f"with bucket_size={self.bucket_size} shrinks "
                            "the matrix) — set .resources(bucket_size=1) "
                            "or preagg='nnm', or drop faults"
                        )
        if self.codec_config:
            # Build the codec now so a bad spec fails at validate() time
            # (CodecConfig.__post_init__ range-checks every knob).
            self.get_codec()
            if self.execution in ("streamed", "dsharded"):
                raise ValueError(
                    "update codecs (codec_config) are only formulated for "
                    "the dense round — the streamed/d-sharded paths never "
                    "materialise the full (n, d) matrix the encode->decode "
                    "transform consumes; use execution='dense' (or 'auto' "
                    "within the dense budget) or disable the codec"
                )
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "update codecs are single-chip for now: top-k selection "
                    "and per-row scales under shard_map would shard the "
                    "lane axis — run the compressed pass without "
                    "num_devices, or disable the codec"
                )
        if self.agg_domain not in ("f32", "wire"):
            raise ValueError(
                f"agg_domain must be 'f32' or 'wire', got "
                f"{self.agg_domain!r}"
            )
        if self.agg_domain == "wire":
            # Fail-fast discipline of faults/codecs: every structural
            # impossibility surfaces here, not at trace time.
            codec = self.get_codec()
            if codec is None or not codec.supports_deferred:
                raise ValueError(
                    "agg_domain='wire' needs a deferrable codec "
                    "(identity or quant int8/int4): the defense "
                    "statistics traverse the PACKED wire payload, and "
                    f"{'no codec' if codec is None else codec.name!r} has "
                    "no packed-integer matrix to aggregate — set "
                    ".communication(codec={'type': 'quant', ...}) or "
                    "keep agg_domain='f32'"
                )
            for knob, why, flip in (
                (self.fault_config, "fault injection",
                 ".fault_tolerance(faults=None)"),
                (self.health_check, "the in-round health check",
                 ".fault_tolerance(health_check=False)"),
                (self.forensics, "defense forensics",
                 ".observability(forensics=False)"),
                (self.dp_clip_threshold, "client DP",
                 "dp_clip_threshold=None"),
            ):
                if knob:
                    raise ValueError(
                        f"agg_domain='wire' × {why} is an unsupported "
                        "pair: that stage rewrites/inspects dense f32 "
                        "rows the wire domain never materializes — set "
                        f"{flip}, or run under "
                        ".communication(agg_domain='f32')"
                    )
            from blades_tpu.parallel.streamed_geometry import (
                WIRE_AGGREGATORS,
            )

            agg = self.get_server().aggregator
            if not isinstance(agg, WIRE_AGGREGATORS):
                raise ValueError(
                    f"aggregator {type(agg).__name__} has no wire-domain "
                    "formulation (aggregate_wire covers "
                    f"{sorted(c.__name__ for c in WIRE_AGGREGATORS)}); "
                    "use agg_domain='f32'"
                )
        # Out-of-core participation-window store (blades_tpu/state):
        # every structural impossibility fails here, never at trace
        # time — the faults/codecs fail-fast discipline.
        from blades_tpu.state.store import STORE_BACKENDS

        if self.state_store not in STORE_BACKENDS:
            raise ValueError(
                f"state_store must be one of {STORE_BACKENDS}, got "
                f"{self.state_store!r}")
        w = self.state_window
        if w is not None and (not isinstance(w, int) or w < 0):
            raise ValueError(
                f"state_window must be None, 0 (stateless) or a positive "
                f"cohort size, got {w!r}")
        if w is None and self.state_store != "resident":
            if self.execution != "async":
                raise ValueError(
                    f"state_store={self.state_store!r} needs a "
                    "participation window: set .resources(window=...) — "
                    "without one there is no cohort to stage (the async "
                    "path alone windows by its event batch instead)")
        if w == 0:
            if self.state_store != "resident":
                raise ValueError(
                    "window=0 is the STATELESS degenerate case — clients "
                    "keep no state, so there is nothing for a "
                    f"{self.state_store!r} store to hold; drop "
                    "state_store or use window >= 1")
            codec = self.get_codec()
            if codec is not None and codec.needs_residual:
                raise ValueError(
                    "window=0 (stateless clients) cannot compose with a "
                    "top-k error-feedback codec: the EF residual is "
                    "persistent per-client state by definition")
            if self.execution not in ("auto", "dense"):
                raise ValueError(
                    "window=0 (stateless clients) is formulated for the "
                    f"dense round only; execution={self.execution!r} "
                    "carries its own per-client state threading")
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "window=0 (stateless clients) × num_devices>1 is an "
                    "unsupported pair: the mesh rounds thread per-client "
                    "state through their own bodies — set "
                    ".resources(num_devices=None), or drop window=0")
        if w is not None and w >= 1:
            if w > self.num_clients:
                raise ValueError(
                    f"window={w} > num_clients={self.num_clients}: the "
                    "cohort samples without replacement from the "
                    "registered population")
            if self.execution not in ("auto", "dense"):
                raise ValueError(
                    "the participation-window store is formulated for "
                    "the dense single-chip round (the cohort matrix is "
                    f"(window, d)); execution={self.execution!r} has no "
                    "windowed formulation — drop the window or use "
                    "execution='dense'")
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    f"state_window={w} × num_devices>1 is an unsupported "
                    "pair: cohort gather/scatter has no mesh formulation "
                    "— set .resources(num_devices=None), or drop the "
                    "window")
            # Forensics COMPOSES with the window since the cohort-shaped
            # forensics work: the windowed round diagnoses the
            # (window, d) cohort matrix against the cohort-gathered
            # malicious mask, and the driver stamps the cohort
            # id-vector that maps lanes back to registered ids.  The
            # remaining gates name the exact pair and the knob that
            # flips it.
            for knob, why, flip in (
                (self.fault_config, "fault injection (the straggler "
                 "ring and participation mask are keyed by lane, not "
                 "registered id)", ".fault_tolerance(faults=None)"),
                (self.client_packing not in ("off", None),
                 "client lane-packing",
                 ".resources(client_packing='off')"),
                (self.agg_domain != "f32", "wire-domain aggregation",
                 ".communication(agg_domain='f32')"),
            ):
                if knob:
                    raise ValueError(
                        f"state_window={w} × {why} is an unsupported "
                        f"pair — set {flip}, or run without the "
                        "participation window")
        # Out-of-core TRAINING DATA (blades_tpu/data/store.py): the
        # memmap backend only engages on the paths that stage per-cohort
        # data — windowed dense, or async × out-of-core state.  Same
        # fail-fast discipline as the state store above.
        from blades_tpu.data.store import DATA_STORE_BACKENDS

        if self.data_store not in DATA_STORE_BACKENDS:
            raise ValueError(
                f"data_store must be one of {DATA_STORE_BACKENDS}, got "
                f"{self.data_store!r}")
        if self.data_store == "memmap":
            ooc_async = (self.execution == "async"
                         and self.state_store != "resident")
            if not ((w is not None and w >= 1) or ooc_async):
                raise ValueError(
                    "data_store='memmap' needs a per-cohort staging path: "
                    "set .resources(window=...) >= 1 (windowed dense) or "
                    "execution='async' with an out-of-core state_store — "
                    "the full-participation rounds hold the whole "
                    "partition on device and never stage cohort data")
        elif self.data_dir:
            raise ValueError(
                "data_dir is set but data_store='resident' — set "
                ".resources(data_store='memmap') (data_dir names the "
                "memmap backend's live shard directory) or drop data_dir"
            )
        if not isinstance(self.eval_chunk_clients, int) \
                or self.eval_chunk_clients < 1:
            raise ValueError(
                f"eval_chunk_clients must be an int >= 1, got "
                f"{self.eval_chunk_clients!r}")
        # Client-lifetime ledger (obs/ledger.py): fail-fast on a bad
        # backend value, and name the one structurally impossible pair.
        self.ledger_backend
        if self.ledger_backend:
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "ledger × num_devices>1 is an unsupported pair: the "
                    "ledger folds per-lane diagnosis host-side and the "
                    "mesh paths never materialise per-lane decisions — "
                    "set .resources(num_devices=None), or flip "
                    ".observability(ledger=False)"
                )
        elif self.ledger_dir:
            raise ValueError(
                "ledger_dir is set but the ledger is disabled — set "
                ".observability(ledger='disk') (ledger_dir names the "
                "disk backend's live directory) or drop ledger_dir"
            )
        # Watchdog rule overrides: build the table now so an unknown
        # key / kind / field fails at validate() time — the
        # faults/codecs fail-fast discipline.
        if self.watchdog_rules is not None:
            self.get_watchdog_rules()
        # Campaign adversaries (adversaries/campaigns.py) schedule their
        # attack over VIRTUAL TIME — only the async engine has a tick
        # clock to ride.
        if self.adversary_config:
            adv = self.get_adversary()
            if getattr(adv, "requires_virtual_time", False) \
                    and self.execution != "async":
                raise ValueError(
                    f"adversary {self.adversary_config.get('type')!r} is a "
                    "campaign attack scheduled over virtual arrival time; "
                    f"execution={self.execution!r} has no tick clock — set "
                    ".resources(execution='async')"
                )
            # Topology-scoped attacks poison per-RECEIVER over the peer
            # graph — only the gossip round has receivers to scope.
            if getattr(adv, "topology_scoped", False) \
                    and self.execution != "gossip":
                raise ValueError(
                    f"adversary {self.adversary_config.get('type')!r} is "
                    "topology-scoped (per-receiver poisoning over the "
                    f"peer graph); execution={self.execution!r} has no "
                    "peer graph — set .resources(execution='gossip')"
                )
        # Closed-loop control plane: build the policy now (unknown keys
        # / bad bounds fail here), then gate the structurally impossible
        # pairs with the exact knob that flips each one.
        policy = self.get_control_policy()
        if policy is not None:
            if self.execution in ("streamed", "dsharded"):
                raise ValueError(
                    f"control × execution={self.execution!r} is an "
                    "unsupported pair: the controller's sensors ride "
                    "forensics/ledger row fields those paths never "
                    "produce — use execution='dense'/'async', or drop "
                    ".control()"
                )
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "control × num_devices>1 is an unsupported pair "
                    "(same lane-axis constraint as forensics/ledger) — "
                    "set .resources(num_devices=None), or drop .control()"
                )
            quarantine_armed = policy.quarantine_rounds > 0 and any(
                fam == "quarantine" for _, fam in policy.rule_table)
            if quarantine_armed:
                # Quarantine moves mask clients at the async ingest
                # filter and pick targets from the ledger's reputation
                # ranking over forensics diagnoses — all three are load-
                # bearing.
                for missing, why, flip in (
                    (self.execution != "async",
                     "an async ingest path to mask clients at",
                     ".resources(execution='async')"),
                    (not self.forensics,
                     "per-lane diagnoses to probe against",
                     ".observability(forensics=True)"),
                    (not self.ledger_backend,
                     "the ledger's reputation ranking to pick suspects",
                     ".observability(ledger=True)"),
                ):
                    if missing:
                        raise ValueError(
                            "control quarantine moves need " + why +
                            f" — set {flip}, or disable them with "
                            ".control(quarantine_rounds=0) or "
                            ".control(rules={'fpr_collapse': 'off', "
                            "'reputation_collapse': 'off'})"
                        )
                spec = self.get_async_spec()
                ceiling = int(policy.max_quarantine_fraction
                              * self.num_clients)
                if self.num_clients - ceiling < spec.agg_every:
                    raise ValueError(
                        f"control max_quarantine_fraction="
                        f"{policy.max_quarantine_fraction} could "
                        f"quarantine {ceiling} of {self.num_clients} "
                        f"clients, starving agg_every={spec.agg_every} "
                        "(a cycle buffers at most one event per free "
                        "client) — lower the fraction or agg_every"
                    )
            if self.execution == "async" and self.state_store != "resident" \
                    and any(fam in ("agg_every", "buffer")
                            for _, fam in policy.rule_table):
                raise ValueError(
                    f"control agg_every/buffer moves × state_store="
                    f"{self.state_store!r} is an unsupported pair: the "
                    "out-of-core store sizes its staging rows by the "
                    "initial agg_every, and both families can GROW the "
                    "staged set — map those rules to the shrink-only "
                    "'window' family in .control(rules=...) (bounded by "
                    "min_window/window_factor), map them 'off', or set "
                    "state_store='resident'"
                )
        if self.client_packing not in ("off", "auto", None):
            # Forced int P: structural impossibilities fail at validate()
            # time, the same fail-fast discipline as faults/codecs.  The
            # full model-aware resolution (width heuristic, hook gates)
            # runs in get_fed_round() via resolve_client_packing.
            try:
                p = int(self.client_packing)
            except (TypeError, ValueError):
                raise ValueError(
                    "client_packing must be 'off', 'auto' or an int >= 2, "
                    f"got {self.client_packing!r}"
                )
            if p < 2:
                raise ValueError(
                    f"client_packing int must be >= 2, got {p}"
                )
            if self.num_clients % p:
                raise ValueError(
                    f"client_packing={p} does not divide num_clients="
                    f"{self.num_clients}"
                )
            if self.num_devices and self.num_devices > 1:
                raise ValueError(
                    "client_packing × num_devices>1 is an unsupported "
                    "pair: the grouped-kernel lanes have no mesh "
                    "formulation — set .resources(num_devices=None), or "
                    ".resources(client_packing='off')"
                )
            if self.execution in ("streamed", "dsharded"):
                raise ValueError(
                    "client_packing needs the dense round; execution="
                    f"{self.execution!r} never runs the packed local round"
                )
        if str(self.update_dtype) not in ("bfloat16", "float32"):
            raise ValueError(
                f"update_dtype must be 'bfloat16' or 'float32', got "
                f"{self.update_dtype!r}"
            )
        if self.mxu_finish not in (None, "", "counts", "all"):
            raise ValueError(
                "mxu_finish must be None (env default), '', 'counts' or "
                f"'all', got {self.mxu_finish!r}"
            )
        self.autotune_mode  # fail-fast on a bad autotune value
        if self.autotune_mode:
            # Multi-chip tuning is legal (ISSUE 18): the plan space keeps
            # the config's own mesh resolution as candidates[0] and the
            # reassociating tier offers mesh_shape/collective switches.
            # Only an EXPLICIT execution='hier' pin conflicts — there the
            # path is already chosen and the tuner has nothing mesh-free
            # to baseline against.
            if self.execution == "hier":
                raise ValueError(
                    "autotune × execution='hier' is an unsupported pair: "
                    "the tuner selects INTO the hierarchical path via its "
                    "collective knob (reassociating tier) — set "
                    ".resources(execution='auto') to let it, pin the plan "
                    "via tuned_plan, or disable autotune"
                )
            if self.execution == "dsharded":
                raise ValueError(
                    "autotune × execution='dsharded' is an unsupported "
                    "pair: the plan space has no d-sharded vocabulary (a "
                    "plan would silently rewrite the pin) — set "
                    ".resources(autotune='off'), or drop the explicit "
                    "execution pin"
                )
        if self.tuned_plan is not None:
            # Parse the pin now so a bad plan dict fails at validate()
            # time (same fail-fast discipline as faults/codecs).
            from blades_tpu.perf.autotune import Plan

            Plan.from_dict(self.tuned_plan)
        if self.prefetch not in ("auto", "on", "off", True, False):
            raise ValueError(
                f"prefetch must be 'auto', True or False, got "
                f"{self.prefetch!r}"
            )
        if self.d_chunk < 1024:
            raise ValueError(f"d_chunk must be >= 1024, got {self.d_chunk}")
        if self.client_block < 1:
            raise ValueError(f"client_block must be >= 1, got {self.client_block}")
        if self.evaluation_num_samples is not None and self.evaluation_num_samples < 1:
            raise ValueError(
                f"evaluation_num_samples must be >= 1 (or None for the full "
                f"per-client shard), got {self.evaluation_num_samples}"
            )

    @property
    def ledger_backend(self) -> Optional[str]:
        """Normalized client-ledger request: ``None`` (off),
        ``"resident"`` (host-RAM columns; also what ``ledger=True``
        means) or ``"disk"`` (memmapped columns)."""
        v = self.ledger
        if v in (False, None, 0, "off", ""):
            return None
        if v in (True, 1, "on", "resident"):
            return "resident"
        if v == "disk":
            return "disk"
        raise ValueError(
            f"ledger must be off|resident|disk (or bool), got {v!r}"
        )

    @property
    def autotune_mode(self) -> Optional[str]:
        """Normalized autotune request: ``None`` (off), ``"default"``
        (numerics-preserving tier only) or ``"reassociating"`` (opt-in
        tier included)."""
        v = self.autotune
        if v in (False, None, 0, "off", ""):
            return None
        if v in (True, 1, "on", "default"):
            return "default"
        if v == "reassociating":
            return "reassociating"
        raise ValueError(
            f"autotune must be off|on|reassociating (or bool), got {v!r}"
        )

    def freeze(self) -> None:
        self._frozen = True

    def copy(self) -> "FedavgConfig":
        c = copy.deepcopy(self)
        c._frozen = False
        return c

    # sub-config factories (ref: algorithm_config.py:157-208)

    def get_task_spec(self) -> TaskSpec:
        augment = self.augment
        if augment == "auto":
            # Resolve the dataset NAME the same way validate() does — a
            # catalog dict spec (e.g. {"type": "cifar10",
            # "synthetic_noise": ...}) must still enable cifar crop+flip.
            if isinstance(self.dataset, str):
                name = self.dataset
            elif isinstance(self.dataset, dict):
                name = self.dataset.get("type") or ""
            else:
                name = getattr(self.dataset, "name", "") or ""
            augment = "cifar" if str(name).lower() in ("cifar10", "cifar100") else None
        return TaskSpec(
            model=self.global_model, num_classes=self.num_classes,
            input_shape=tuple(self.input_shape), lr=self.client_lr,
            momentum=self.client_momentum, augment=augment,
            compute_dtype=self.compute_dtype,
        )

    def get_server(self) -> Server:
        return Server.from_config(
            aggregator=self.aggregator,
            num_byzantine=self.num_malicious_clients,
            lr=self.server_lr, momentum=self.server_momentum,
            dampening=self.server_dampening,
            weight_decay=self.server_weight_decay,
            lr_schedule_points=self.lr_schedule,
        )

    def get_adversary(self):
        return get_adversary(
            self.adversary_config,
            num_clients=self.num_clients,
            num_byzantine=self.num_malicious_clients,
            num_classes=self.num_classes,
        )

    def get_fault_injector(self):
        """Build the chaos layer's :class:`~blades_tpu.faults.FaultInjector`
        from ``fault_config`` (None when disabled).  The fault-process
        seed defaults to the trial seed so a seed grid sweeps the failure
        realizations too; set an explicit ``seed`` in the spec to pin the
        failure process across a training-seed grid."""
        if not self.fault_config:
            return None
        from blades_tpu.faults import FaultInjector

        spec = dict(self.fault_config)
        spec.setdefault("seed", int(self.seed))
        # YAML-style dropout_schedule lists are normalized (sorted tuple of
        # (int, float) pairs) by FaultInjector.__post_init__ itself.
        return FaultInjector(**spec)

    def get_async_spec(self):
        """Build the buffered-async
        :class:`~blades_tpu.arrivals.AsyncSpec` from ``async_config``
        (None unless ``execution="async"``).  The arrival seed defaults
        to the trial seed so a seed grid sweeps the traffic realizations
        too; set an explicit ``seed`` in the spec to pin the arrival
        process across a training-seed grid."""
        if self.execution != "async":
            return None
        from blades_tpu.arrivals import AsyncSpec

        spec = dict(self.async_config or {})
        spec.setdefault("seed", int(self.seed))
        if spec.get("rate_schedule") is not None:
            spec["rate_schedule"] = tuple(
                tuple(p) for p in spec["rate_schedule"])
        return AsyncSpec(**spec)

    @property
    def control_enabled(self) -> bool:
        """Whether the closed-loop control plane is armed: a
        ``control_config`` dict whose ``enabled`` (default True when the
        dict exists) is truthy."""
        cfg = self.control_config
        if cfg is None:
            return False
        if not isinstance(cfg, dict):
            raise ValueError(
                f"control_config must be a dict, got {type(cfg).__name__}")
        return bool(cfg.get("enabled", True))

    def get_watchdog_rules(self) -> tuple:
        """The watchdog rule table the trial runs under:
        ``watchdog_rules`` overrides resolved through
        :func:`blades_tpu.obs.watchdog.rules_from_config` (which
        fail-fasts on unknown keys/kinds/fields), or the built-in
        ``default_rules()``."""
        from blades_tpu.obs.watchdog import rules_from_config

        return rules_from_config(self.watchdog_rules)

    def get_control_policy(self):
        """Build the control plane's
        :class:`~blades_tpu.control.ControlPolicy` from
        ``control_config`` (None when disarmed)."""
        if not self.control_enabled:
            return None
        from blades_tpu.control import ControlPolicy

        return ControlPolicy.from_config(self.control_config)

    def get_topology(self):
        """Build the gossip path's
        :class:`~blades_tpu.topology.TopologyConfig` from
        ``topology_config`` (None unless ``execution="gossip"``), with
        ``num_nodes`` pinned to ``num_clients`` — on the gossip path
        every client IS a node."""
        if self.execution != "gossip":
            return None
        from blades_tpu.topology import get_topology

        return get_topology(self.topology_config, int(self.num_clients))

    def get_codec(self):
        """Build the comm subsystem's
        :class:`~blades_tpu.comm.CodecConfig` from ``codec_config``
        (None when disabled)."""
        if not self.codec_config:
            return None
        from blades_tpu.comm import get_codec

        return get_codec(self.codec_config)

    def get_client_callbacks(self) -> tuple:
        from blades_tpu.core.callbacks import ClippingCallback, get_callback

        cbs = [get_callback(s) for s in (self.client_callbacks or [])]
        if self.clip_gradient_norm:
            cbs.append(ClippingCallback(float(self.clip_gradient_norm)))
        return tuple(cbs)

    def resolve_augment_for_data(self, fed_round, dataset):
        """'auto' augmentation means "the dataset's canonical train
        transforms" (cifar crop+flip).  The SYNTHETIC fallback is not an
        image distribution — random crops of its Gaussian class patterns
        destroy the signal (measured: benign CIFAR ResNet accuracy
        0.93 -> 0.19) — so auto resolves to none there.  An explicit
        augment= request is honored as given.  Shared by every driver
        that builds a FedRound and then loads data (Fedavg._setup, the
        lane sweeps) — the dataset's synthetic flag is only known after
        loading, which is why this cannot live in get_task_spec().
        """
        if not (getattr(dataset, "synthetic", False)
                and self.augment == "auto"):
            return fed_round
        import dataclasses as _dc

        task = fed_round.task
        task = _dc.replace(task, spec=_dc.replace(task.spec, augment=None))
        return _dc.replace(fed_round, task=task)

    def get_fed_round(self) -> FedRound:
        fr = FedRound(
            task=self.get_task_spec().build(),
            server=self.get_server(),
            adversary=self.get_adversary(),
            batch_size=self.train_batch_size,
            num_batches_per_round=self.num_batch_per_round,
            dp_clip_threshold=self.dp_clip_threshold,
            dp_noise_factor=self.dp_noise_factor,
            client_callbacks=self.get_client_callbacks(),
            # True federation size: ghost lanes from mesh padding (see
            # shard_federation) are sliced out of forging/aggregation.
            num_clients=self.num_clients,
            health_check=self.health_check,
            forensics=self.forensics,
            faults=self.get_fault_injector(),
            codec=self.get_codec(),
            agg_domain=self.agg_domain,
            agg_d_chunk=self.d_chunk,
            # window=0 stateless degenerate case (blades_tpu/state):
            # fresh per-client optimizer state every round.
            stateless_clients=self.state_window == 0,
        )
        # Client lane-packing: resolve "auto"/forced requests against the
        # built model (width heuristic, hook gates) — LOUD fallback under
        # "auto", hard error for an impossible forced P.  The decision is
        # cached for sweep summaries / laned rows (private attr: frozen
        # configs only guard the public fluent setters).
        from blades_tpu.parallel.packed import resolve_client_packing

        fr, self._packing_decision = resolve_client_packing(
            fr, self.client_packing, num_clients=self.num_clients,
            num_devices=self.num_devices, execution=self.execution,
        )
        return fr

    def build(self):
        """(ref: algorithm_config.py:222-251)"""
        self.validate()
        self.freeze()
        return self.algo_class(self)

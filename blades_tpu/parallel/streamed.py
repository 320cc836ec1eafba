"""Single-chip streaming round for federations whose update matrix
strains HBM.

The giant-federation memory problem (SURVEY.md §7.3) has two TPU-native
answers:

- **multi-chip**: width-shard the ``(n, d)`` matrix over the mesh
  (:mod:`blades_tpu.parallel.dsharded`) — the production path on a pod
  slice, e.g. 1000 clients x ResNet-18 (45 GB f32) across a v5e-8.
- **single-chip** (this module): when only one chip is available, the
  matrix fits only by (a) storing updates in ``bfloat16`` (the robust
  aggregators are order statistics and norm filters — bf16's 8-bit
  exponent preserves ordering; VERDICT r1 explicitly flags the f32->bf16
  update matrix as headroom) and (b) never holding a second copy or a
  giant fused program: the round is a SEQUENCE of small dispatches —
  per-client-block training programs (ONE compiled shape: tile-sized
  blocks whose last block is padded, :func:`block_plan`) that write rows
  into a DONATED ``(n, d)`` buffer, then one donated finish program that
  forges and
  aggregates in d-chunks under ``lax.scan`` (sort workspace lives
  per-chunk).  A previous single-program formulation planned ~2x the
  matrix in HLO temps from allocator fragmentation and OOM'd at the
  1000-client scale; buffer donation across dispatches is what makes the
  matrix + workspace fit in 16 GB.

The whole aggregator suite runs here.  The coordinate-wise slice —
Mean / Median / Trimmedmean, exactly the BASELINE.json headline workload
(FedAvg + ALIE + Median) — aggregates inside the chunked (or fused
pallas) finish.  The row-geometry aggregators (GeoMed, Multikrum, DnC,
Centeredclipping, Signguard, Clippedclustering, FLTrust) run as fused
full-matrix pass bundles over the stored buffer — statistics requested
through the pass planner
(:mod:`blades_tpu.parallel.streamed_geometry`), executed one HBM
traversal per bundle (the pallas row-stats kernel,
:mod:`blades_tpu.ops.pallas_rowstats`, on eligible TPU shapes; a
``lax.scan`` chunk loop otherwise), with planned traversal counts
stamped per round as ``hbm_passes``/``hbm_passes_unfused`` — after a
materialization scan writes sanitize/DP back into it.  Update-forging adversaries run
either fused into the finish (coordinate-wise: ALIE, IPM, Noise,
Adaptive) or — for the row-geometry attacks MinMax, SignGuard-attack
and Attackclippedclustering — as stats passes producing one forged
``(d,)`` row scattered into the malicious lanes before aggregation, so
EVERY registry attack x defense pair runs at giant scale on one chip.
Per-row DP (clip + Gaussian noise) IS supported: full-row norms are taken at train time (on the f32 updates,
before storage rounding) and the chunked finish clips/noises with them —
with f32 storage the clipping matches the dense path exactly; with bf16
storage the clip is tightened by a half-ulp factor so the post-rounding
row norm still respects the DP sensitivity bound.  Noise keys fold in
the chunk index, so noise DRAWS differ from the dense path's single
(n, d) draw (both are valid iid streams).

On a TPU backend, rounds whose forge is coordinate-wise
(ALIE/IPM/Adaptive), whose aggregator is Mean/Median/Trimmedmean, and that run
without DP skip the chunked ``lax.scan`` finish entirely: the whole
finish (sanitize + forge + aggregate + row norms) runs as ONE fused
pallas kernel in a single HBM pass over the stored matrix
(:mod:`blades_tpu.ops.pallas_round`), with a 16-step radix select in
bf16 key space when storage is bf16 — ~3.5x the chunked finish at
n=1000 x d=4.9M.  When the malicious prefix is elided
(``malicious_prefix``), the matrix is further COMPACTED to the benign
rows only and the forged row enters the order statistics as a virtual
row of multiplicity f (``fused_finish_compact``) — per-row kernel work
and matrix HBM shrink by the byzantine fraction (9.8 -> 7.4 GB at the
benchmark scale, and ResNet-18 fits n=768 on one chip).  Every other
configuration falls back to the chunked path.  Where the compact matrix's
blocks lie under a storage tile (a language model a lane, 8 rows in
blocks of one) it keeps a row a PLANE, ``(rows, d_alloc // 128, 128)``
(:func:`block_plan`, :func:`compact_matrix`): a row is then whole tiles,
so its store is a contiguous copy and not a rewrite of the matrix, and
the finish counts over whole vregs (ops/pallas_store.py,
ops/pallas_round.py).  Every other path keeps ``(rows, d)``.

1000 clients x ResNet-10 (d=4.9M) in bf16 = 9.8 GB: fits a single 16 GB
v5e chip with ~1 GB chunk workspace.  ResNet-18 at n=1000 (22.3 GB bf16)
does NOT fit one chip — that is what the mesh is for.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from blades_tpu.adversaries.base import Adversary
from blades_tpu.adversaries.update_attacks import (
    AdaptiveAdversary,
    ALIEAdversary,
    IPMAdversary,
    NoiseAdversary,
)
from blades_tpu.core.round import FedRound, RoundState
from blades_tpu.core.task import identity_round_end_hook
from blades_tpu.data.sampler import sample_client_batches_with_keys
from blades_tpu.obs import device_phases
from blades_tpu.obs.trace import span
# Imported here and not inside ``_train_block``: ``pallas_store`` binds the
# kernels' gate by name when it is first imported, and a first import
# inside a traced function would bind whatever a caller had put in the
# gate's place at that moment (the tests' monkeypatches).
from blades_tpu.ops import pallas_store
from blades_tpu.ops.aggregators import Mean, Median, Trimmedmean

_COORDWISE_FORGERS = (ALIEAdversary, IPMAdversary, NoiseAdversary,
                      AdaptiveAdversary)
_COORDWISE_AGGREGATORS = (Mean, Median, Trimmedmean)

# Canonical streamed-finish chunk width (the historical hard-coded
# value, now named).  The config default (algorithms/config.py) and the
# center of the autotuner's candidate ladder (perf/autotune.py
# D_CHUNK_LADDER — stdlib-only by design, so it repeats the literal)
# pin the same 1 << 17; the autotuner's chunk tests assert the agreement.
DEFAULT_D_CHUNK = 1 << 17


class BlockPlan(NamedTuple):
    """How one round's trained lanes ``[first_lane, n)`` split into
    ``blocks`` dispatches of ``_train_block``, each of ``block`` lanes:
    ONE compiled shape.  Block ``i`` trains client lanes from
    ``first_lane + i * block`` and stores its rows from matrix row
    ``first_row + i * block``.  Where the trained lanes are no whole
    number of blocks, the last block starts ``surplus`` lanes early (at
    lane ``n - block``): its first ``surplus`` lanes are clients that the
    block before it already trained (or elided ones).  They train again
    and all they produce is dropped."""

    first_lane: int
    first_row: int
    block: int
    blocks: int
    surplus: int
    tile: int
    # Every store is a whole number of storage tiles at a whole-block
    # row of a matrix a whole number of blocks high: the tile copy's
    # geometry (ops/pallas_store.py), whatever the backend.  Or every
    # store is a row's whole plane (``planes``).
    whole_tiles: bool
    # Blocks under a storage tile, stored for the compact fused finish:
    # the matrix keeps a row a plane, ``(rows, d_alloc // 128, 128)``
    # (:func:`compact_matrix`), where any number of rows is a whole
    # number of tiles at an aligned offset.
    planes: bool = False

    @property
    def aligned_stores(self) -> int:
        """Dispatches whose store is a whole number of storage tiles at
        a tile-aligned matrix row.  All or none: one compiled block
        stores all of a round's blocks the same way."""
        return self.blocks if self.whole_tiles else 0


def block_plan(n: int, prefix: int, client_block: int, dtype, *,
               compact: bool) -> BlockPlan:
    """Block geometry from the storage type.  TPU HBM holds a matrix in
    tiles of 8 sublanes of 32-bit words, so a storage tile is 8 rows of
    a 4-byte ``dtype`` and 16 of a 2-byte one; a block stores as a plain
    copy (ops/pallas_store.py) only where it is a whole number of tiles
    at a tile boundary.  A block UNDER a tile is a part of every tile it
    touches in a ``(rows, d)`` matrix, where no store can write it alone
    (a DMA moves whole tiles; XLA reads and rewrites the matrix's every
    tile for it).  So the compact matrix of such blocks keeps a row a
    plane (``planes``; :func:`compact_matrix`): the rows lie on the major
    axis, a row is whole tiles of its own, and the store is a contiguous
    copy.  A rule of the shapes alone: ``block < tile and compact``.
    The dispatch size is the largest multiple of the tile's rows under
    ``client_block`` (and under the lanes there are), or that bound
    itself where it is under one tile.  Every dispatch has that size:
    the last one is padded with ``surplus`` lanes, not cut short.

    ``prefix`` lanes are elidable (0: train everyone).  The compact
    matrix stores benign rows only, padded with ``+inf`` rows to a whole
    number of blocks, so training starts at ``prefix`` and row 0;
    otherwise rows are lanes, and training starts at the block boundary
    under ``prefix``: the malicious lanes in between train harmlessly."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    cap = max(1, min(client_block, n - prefix if compact else n))
    block = cap // tile * tile or cap
    if compact:
        first_lane, first_row = prefix, 0
    else:
        first_lane = first_row = prefix // block * block
    blocks = -(-(n - first_lane) // block)
    surplus = blocks * block - (n - first_lane)
    planes = compact and block < tile
    return BlockPlan(first_lane, first_row, block, blocks, surplus, tile,
                     planes or (block % tile == 0
                                and (compact or not surplus)), planes)


def compact_matrix(plan: BlockPlan, nb: int, d: int):
    """``(shape, cols)``: the benign-compacted update matrix of ``nb``
    rows of a ``d``-wide model under ``plan``, and the columns one grid
    step of its fused finish takes (ops/pallas_round.py), of which the
    matrix is allocated a whole number wide so that no pad inside the
    call copies it.  Whoever builds that matrix (``step``, the tools'
    compile-only twins, ``chip_smoke.py``) builds it here.

    Blocks of whole tiles: ``(rows, d_alloc)``, a whole number of blocks
    and of sublanes high, the rows past ``nb`` holding ``+inf``.  Blocks
    under a tile (``plan.planes``): ``(nb, d_alloc // 128, 128)``, a row
    a plane; the rows lie on no sublane and need no padding."""
    from blades_tpu.ops.pallas_select import (
        plane_cols,
        stripe_cols,
        stripe_padded,
    )

    if plan.planes:
        cols = plane_cols(nb)
        return (nb, -(-d // cols) * (cols // 128), 128), cols
    rows = -(-(plan.blocks * plan.block) // 8) * 8
    return (rows, stripe_padded(d, rows)), stripe_cols(rows)


def _fused_spec(fr: FedRound):
    """(forge, agg) tuples for the one-pass pallas finish
    (:func:`blades_tpu.ops.pallas_round.fused_finish`), or ``None`` when
    this round needs the general chunked path (DP, keyed/row-geometry
    forges, non-order-statistic aggregators)."""
    if fr.dp_clip_threshold is not None:
        return None
    agg = fr.server.aggregator
    if isinstance(agg, Median):
        aspec = ("median",)
    elif isinstance(agg, Trimmedmean):
        aspec = ("trimmed", agg.num_excluded)
    elif isinstance(agg, Mean):
        aspec = ("mean",)
    else:
        return None
    adv = fr.adversary
    if not _adv_forges(adv):
        fspec = None
    elif isinstance(adv, ALIEAdversary):
        fspec = ("alie", float(adv.z_max))
    elif isinstance(adv, IPMAdversary):
        fspec = ("ipm", float(adv.scale))
    elif isinstance(adv, AdaptiveAdversary):
        fspec = ("adaptive", float(adv.b))
    else:
        return None
    return fspec, aspec


def _adv_forges(adv) -> bool:
    return adv is not None and type(adv).on_updates_ready is not Adversary.on_updates_ready


def _dispatch(fn, *args, **kwargs):
    """Run one of the round's programs, noted for the device trace's
    phases (obs/device_phases.py: its first dispatch keeps the executable,
    a later one costs a set lookup)."""
    device_phases.note(fn, args, kwargs)
    return fn(*args, **kwargs)


def streamed_step(
    fr: FedRound,
    *,
    client_block: int = 50,
    d_chunk: int = DEFAULT_D_CHUNK,
    update_dtype=jnp.bfloat16,
    donate: bool = True,
    malicious_prefix: int | None = None,
    fuse_rowgeom: bool = True,
    mxu_finish: str | None = None,
) -> Callable:
    """Build the streaming round (a host-side callable over jitted parts).

    Same signature and RNG stream as ``jax.jit(fr.step)``:
    ``step(state, x, y, lengths, malicious, key) -> (state, metrics)`` —
    with f32 storage and a deterministic coordinate-wise adversary the
    CHUNKED finish is bit-identical to the dense round.  On a TPU
    backend eligible rounds take the fused pallas finish instead, whose
    in-kernel reduction order can differ in the last ulp — set
    ``BLADES_TPU_NO_PALLAS=1`` to force the chunked path when bitwise
    reproduction against the dense round matters.  Exception: the
    Adaptive (Fang) forge draws per-coordinate uniforms, and there the
    FUSED path reproduces the dense round's single ``(d,)`` draw exactly
    while the chunked path folds the key per d-chunk — different (but
    equally valid) forged rows; see :mod:`blades_tpu.ops.pallas_round`.

    Args:
        client_block: upper bound on the clients trained per dispatch
            (bounds activation memory).  The round takes the largest
            whole number of storage tiles under it, and pads the last
            block (:func:`block_plan`); any ``num_clients`` runs.
        d_chunk: coordinates forged+aggregated per ``lax.scan`` iteration
            (bounds the f32 chunk + sort workspace).
        update_dtype: storage dtype of the ``(n, d)`` update matrix.
        donate: when True (default), the caller's ``state.client_opt``
            buffers are DONATED into the first training block — the memory
            economy that lets the giant matrix fit, but the passed-in
            state must not be reused afterwards (unlike
            ``jax.jit(fr.step)``, which copies).  Pass False to keep the
            caller's state alive at the cost of one opt-state copy per
            round.
        malicious_prefix: the caller's PROMISE that ``malicious`` equals
            ``arange(n) < malicious_prefix`` (the canonical
            :func:`~blades_tpu.adversaries.make_malicious_mask` layout,
            which marks the first ``num_byzantine`` lanes like the
            reference, ref: blades/algorithms/fedavg/fedavg.py:160-167).
            When the round's adversary FORGES updates (every
            coordinate-wise and row-geometry update attack), the forged
            rows are computed purely from benign statistics and replace
            whatever the malicious clients trained — their local training
            is dead computation, and the prefix's lanes are skipped: all
            of them where the matrix is compacted to the benign rows,
            otherwise down to the block boundary under the prefix
            (:func:`block_plan`; ~25% of the round at the 1/4-byzantine
            benchmark scale).  Exact: the post-forge
            matrix, aggregate, server state and all benign-side metrics
            are unchanged (train_loss already averages benign lanes
            only).  Observable differences: skipped lanes keep their
            incoming optimizer state (the reference evolves state the
            forge then discards — unobservable unless an adversary stops
            forging mid-run, which no registry attack does), and a
            malicious client that would have trained to NaN no longer
            trips ``num_unhealthy``.  ``None`` (default) trains every
            lane.
        fuse_rowgeom: run the row-geometry finish through the fused pass
            planner (default).  ``False`` executes one traversal per
            accumulator request — the pre-fusion baseline the
            equivalence tests compare against.  Row-geometry rounds
            stamp ``hbm_passes`` / ``hbm_passes_unfused`` (planned full-matrix traversals,
            fused plan vs per-request baseline) into the round metrics.
        mxu_finish: config-resolved MXU finish variant for the compact
            fused pallas finish (``""``/``"counts"``/``"all"``; see
            :func:`blades_tpu.ops.pallas_round.parse_mxu_mode`).
            ``None`` defers to the per-call env default; the
            ``BLADES_TPU_MXU_FINISH`` env var, when SET, overrides this
            value either way.  Pinned at this build's trace time like
            every other static knob here.
    """
    from blades_tpu.parallel.streamed_geometry import (
        STREAMED_ROW_AGGREGATORS,
        PassRecorder,
    )

    agg = fr.server.aggregator
    row_geom = isinstance(agg, STREAMED_ROW_AGGREGATORS)
    if (getattr(agg, "expects_trusted_row", False)
            and fr.trusted_data is None):
        raise ValueError(
            f"{type(agg).__name__} requires FedRound.trusted_data (the "
            "server's root data) — without it the defense has no root of "
            "trust"
        )
    if not row_geom and not isinstance(agg, _COORDWISE_AGGREGATORS):
        raise NotImplementedError(
            f"{type(agg).__name__} has no streamed formulation; "
            "use dsharded_step on a multi-chip mesh for giant federations"
        )
    from blades_tpu.parallel.streamed_geometry import streamed_row_forgers

    _ROWGEOM_FORGERS = streamed_row_forgers()
    dp = fr.dp_clip_threshold is not None
    # Coordinate-wise forgers fuse into the finish programs; row-geometry
    # forgers run as stats passes + a scatter over the materialized
    # buffer BEFORE aggregation (streamed_geometry.forge_streamed).
    coord_forges = _adv_forges(fr.adversary) and isinstance(
        fr.adversary, _COORDWISE_FORGERS
    )
    row_forges = _adv_forges(fr.adversary) and isinstance(
        fr.adversary, _ROWGEOM_FORGERS
    )
    if _adv_forges(fr.adversary) and not (coord_forges or row_forges):
        raise NotImplementedError(
            f"{type(fr.adversary).__name__} has no streamed forge "
            "formulation; use dsharded_step on a multi-chip mesh"
        )
    forges = coord_forges
    hooks = fr._hooks()
    # Planned-traversal accounting for the row-geometry finish: fills at
    # trace time (first round), frozen after the first stamp.
    _pass_recorder = PassRecorder()

    def _dp_chunk(chunk, row_norms, k_dp, i):
        """Per-chunk DP clip + noise against the train-time full-row
        norms — the streamed fixed point of FedRound.apply_dp (see the
        module docstring for the bf16 clip tightening and the per-chunk
        noise keys)."""
        thr = fr.dp_clip_threshold
        if update_dtype != jnp.float32:
            thr = thr / (1.0 + 2.0 ** -8)
        scale = jnp.where(
            jnp.isfinite(row_norms),
            jnp.minimum(1.0, thr / jnp.maximum(row_norms, 1e-12)),
            0.0,
        )
        chunk = chunk * scale[:, None]
        # `is not None` (not truthiness): a traced per-lane scalar can't
        # be bool()ed — same guard as FedRound.apply_dp (round.py).
        if fr.dp_noise_factor is not None:
            sigma = fr.dp_noise_factor * fr.dp_clip_threshold
            chunk = chunk + sigma * jax.random.normal(
                jax.random.fold_in(k_dp, i), chunk.shape, chunk.dtype
            )
        return chunk

    @partial(jax.jit, donate_argnums=(0, 1), static_argnames=("plan",))
    def _train_block(updates_buf, client_opt, params, x, y, lengths,
                     malicious, sample_keys, train_keys, index, *, plan):
        """Train block ``index`` of ``plan`` and store its rows: one
        compiled program for all of a round's blocks.  The client offset
        and the matrix row are computed here, from an UNSIGNED index
        times the static block size: no eager scalar program on the
        host, and no ``select`` for a negative index in front of every
        slice.  The two offsets differ only on the benign-compacted
        path, where the matrix stores no malicious-prefix rows.

        In a round with a short last block (``plan.surplus``) the slices
        start at ``min(row0, n - block)``, and the ``head`` lanes by
        which the last block starts early are surplus: their
        ``client_opt`` lanes are written back as read and their rows are
        not stored (the host drops their losses and norms).

        Blocks of whole storage tiles are stored by the aliased tile
        copy (ops/pallas_store.py) on a TPU.  Into a matrix of row planes
        (three-dimensional: ``compact_matrix``) the rows are padded to
        its width here and written by ``lax.dynamic_update_slice`` at
        ``(row, 0, 0)``: whole tiles at an aligned offset, a contiguous
        copy in place.  Blocks under a tile of a ``(rows, d)`` matrix, a
        full matrix with a short last block and other backends take the
        same operation, whose TPU emitter there reads and rewrites every
        tile the rows touch (all of the matrix, for one row), at any
        runtime offset, aligned or not.

        Device scopes (trace-time metadata, the dense body's names):
        ``blades/sample``, ``blades/step``, and ``blades/store`` for the
        norms and the writes into the matrix and ``client_opt``."""
        block = plan.block
        index = index.astype(jnp.uint32)
        row0 = index * jnp.uint32(block) + jnp.uint32(plan.first_lane)
        if plan.surplus:
            # What lax.dynamic_slice would clamp to anyway, made explicit.
            lane0 = jnp.minimum(row0, jnp.uint32(x.shape[0] - block))
            head = row0 - lane0
            kept = lax.iota(jnp.uint32, block) >= head
        else:
            lane0 = row0

        def sl(a):
            return lax.dynamic_slice_in_dim(a, lane0, block, axis=0)

        def keep(new, old):
            """``new``, but ``old`` in the surplus lanes."""
            if not plan.surplus:
                return new
            return jnp.where(
                kept.reshape((block,) + (1,) * (new.ndim - 1)), new, old)

        opt_b = jax.tree.map(sl, client_opt)
        with jax.named_scope("blades/sample"):
            bx, by = sample_client_batches_with_keys(
                sl(sample_keys), sl(x), sl(y), sl(lengths), fr.batch_size,
                fr.num_batches_per_round,
            )

        # Non-DP rounds cast per leaf inside the block (same bf16 bits,
        # half the assembly traffic); DP needs the f32 row norms BEFORE
        # storage rounding, so there the cast stays at the buffer write.
        # Into a matrix of row planes ONE lane's update stays a pytree of
        # leaves, which the store lays out in one dimension: a (1, d) row
        # of a 2-byte type would be half padding (row_planes).
        planes = updates_buf.ndim == 3
        as_leaves = (planes and block == 1 and not dp
                     and hooks.round_end is identity_round_end_hook)
        with jax.named_scope("blades/step"):
            upd, opt2, loss, stats = fr.task.local_round_batched(
                params, opt_b, bx, by, sl(train_keys), sl(malicious), *hooks,
                out_dtype=None if dp else update_dtype,
                ravel_update=not as_leaves,
            )
        with jax.named_scope("blades/store"):
            # Full-row L2 norms, taken on the f32 updates BEFORE
            # storage-dtype rounding — what chunked DP clipping needs and
            # cannot recover from the matrix later.  Gated: the O(n*d)
            # reduction is pure waste on non-DP rounds.
            norms = (jnp.linalg.norm(upd, axis=1) if dp
                     else jnp.zeros((block,), jnp.float32))
            if planes:
                upd = pallas_store.row_planes(upd, updates_buf.shape[1:])
            upd = upd.astype(update_dtype)
            if not planes and pallas_store.store_applicable(
                    *updates_buf.shape, block, plan.first_row, plan.tile):
                # Whole tiles at a tile boundary: a plain aliased copy of
                # block `index`, the padded last block's included (its
                # surplus rows dropped inside the copy).
                updates_buf = pallas_store.store_row_block(
                    updates_buf, upd,
                    index + jnp.uint32(plan.first_row // block),
                    head if plan.surplus else None, surplus=plan.surplus)
            else:
                buf_row0 = lane0 - jnp.uint32(
                    plan.first_lane - plan.first_row)
                at = (buf_row0,) + (jnp.uint32(0),) * (upd.ndim - 1)
                if plan.surplus:
                    upd = keep(upd, lax.dynamic_slice(
                        updates_buf, at, upd.shape))
                updates_buf = lax.dynamic_update_slice(updates_buf, upd, at)
            client_opt = jax.tree.map(
                lambda full, new, old: lax.dynamic_update_slice_in_dim(
                    full, keep(new, old), lane0, 0),
                client_opt, opt2, opt_b,
            )
        # ``stats``: what the model's layers sowed, a lane a row; ``{}``
        # (no output at all) for a model that sows nothing.
        return updates_buf, client_opt, loss, norms, stats

    @jax.jit
    def _finish(server_state, updates_buf, malicious, losses, row_norms,
                k_adv, k_dp):
        n = updates_buf.shape[0]
        k = fr.num_clients
        if k is not None and k < n:  # drop ghost (padding) lanes
            updates_buf, losses, malicious, row_norms = (
                updates_buf[:k], losses[:k], malicious[:k], row_norms[:k]
            )
        n_eff, d = updates_buf.shape
        c = min(d_chunk, d)
        k_chunks = -(-d // c)
        starts = jnp.minimum(jnp.arange(k_chunks) * c, d - c)

        def chunk_body(carry, inp):
            agg_vec, sq_acc, bad_acc = carry
            i, start = inp
            chunk = lax.dynamic_slice(
                updates_buf, (0, start), (n_eff, c)
            ).astype(jnp.float32)
            if fr.health_check:
                from blades_tpu.core.health import sanitize_updates

                # Chunk-local detection: a lane non-finite only in LATER
                # chunks keeps its earlier finite chunk parts (zeroing
                # them would need a second full pass over the matrix).
                # num_unhealthy still counts the lane; the kept parts are
                # finite, so the aggregate guard semantics are unchanged.
                chunk, chunk_healthy = sanitize_updates(chunk)
                bad_acc = bad_acc | ~chunk_healthy
            if dp:
                # Same fixed point as FedRound.apply_dp: clip each row to
                # the threshold using its FULL-row norm (precomputed at
                # train time), then Gaussian noise.  Noise keys fold in
                # the chunk index, so draws differ from the dense path's
                # single (n, d) draw (both are valid iid streams).
                chunk = _dp_chunk(chunk, row_norms, k_dp, i)
            if forges:
                with jax.named_scope("blades/forge"):
                    chunk = fr.adversary.on_updates_ready(
                        chunk, malicious, jax.random.fold_in(k_adv, i),
                        aggregator=agg, global_params=None,
                    )
            with jax.named_scope("blades/aggregate"):
                a, _ = agg(chunk, ())
                agg_vec = lax.dynamic_update_slice(agg_vec, a, (start,))
            # Row-norm accumulation over not-yet-covered coordinates only.
            new = (start + jnp.arange(c)) >= i * c
            sq_acc = sq_acc + jnp.where(new[None, :], chunk**2, 0.0).sum(axis=1)
            return (agg_vec, sq_acc, bad_acc), None

        (agg_vec, sq_norms, bad_rows), _ = lax.scan(
            chunk_body,
            (jnp.zeros((d,), jnp.float32), jnp.zeros((n_eff,), jnp.float32),
             jnp.zeros((n_eff,), bool)),
            (jnp.arange(k_chunks), starts),
        )
        return _serve_aggregate(server_state, agg_vec, malicious, losses,
                                sq_norms, bad_rows)

    def _serve_aggregate(server_state, agg_vec, malicious, losses, sq_norms,
                         bad_rows, agg_state=None):
        """Shared finish tail: server step + round metrics + health guard
        (identical for the chunked, fused, and row-geometry finishes).
        The server step wears ``blades/aggregate``, as in the dense body
        (core/round.py)."""
        with jax.named_scope("blades/aggregate"):
            server = fr.server.apply_aggregate(server_state, agg_vec,
                                               agg_state)
        benign = (~malicious).astype(jnp.float32)
        train_loss = (losses * benign).sum() / jnp.maximum(benign.sum(), 1.0)
        metrics = {
            "train_loss": train_loss,
            "update_norm_mean": jnp.sqrt(jnp.maximum(sq_norms, 0.0)).mean(),
            "agg_norm": jnp.linalg.norm(agg_vec),
            "round": server.round,
        }
        if fr.health_check:
            from blades_tpu.core.health import guard_server_state

            ok = jnp.isfinite(agg_vec).all()
            server = guard_server_state(ok, server, server_state)
            metrics["num_unhealthy"] = bad_rows.sum()
            metrics["round_ok"] = ok
        return server, metrics

    @partial(jax.jit, static_argnames=("surplus",))
    def _round_counters(blocks, surplus):
        """The task's row counters (``Task.round_counters``) from the
        blocks' stats, the padded last block's ``surplus`` lanes dropped:
        one tiny program, fetched with the round's metrics."""
        return fr.task.round_counters(jax.tree.map(
            lambda *parts: jnp.concatenate(
                parts[:-1] + (parts[-1][surplus:],)), *blocks))

    spec = _fused_spec(fr)

    def _model_d_and_noise(server_state, updates_buf, k_adv):
        """Model width from the server params themselves (the fused
        programs are self-contained; buffer columns are stripe-padded
        past d) + the adaptive forge's pre-drawn uniforms: the dense
        round's exact per-coordinate draw
        (AdaptiveAdversary.on_updates_ready with shard=None),
        zero-extended over the stripe-padding columns (whose all-zero
        stats forge to 0 regardless of r).  Shared by the full and
        compact fused finishes, which tests assert equivalent."""
        d = sum(p.size for p in jax.tree.leaves(server_state.params))
        noise = None
        if spec[0] is not None and spec[0][0] == "adaptive":
            with jax.named_scope("blades/forge"):
                noise = jax.random.uniform(k_adv, (d,), jnp.float32)
                d_alloc = math.prod(updates_buf.shape[1:])
                if d_alloc != d:
                    noise = jnp.pad(noise, (0, d_alloc - d))
        return d, noise

    @jax.jit
    def _finish_fused(server_state, updates_buf, malicious, losses, k_adv):
        from blades_tpu.ops.pallas_round import fused_finish

        # No ghost-lane slice here: the fused path is only selected when
        # num_clients == n (a row slice feeding pallas_call would
        # materialize a second near-full copy of the giant matrix).
        d, noise = _model_d_and_noise(server_state, updates_buf, k_adv)
        forge, aspec = spec
        # Forge and aggregate are ONE Mosaic call: it sits under
        # blades/aggregate, and blades/forge holds only the adaptive
        # forge's uniforms.
        with jax.named_scope("blades/aggregate"):
            agg_vec, sq_norms, bad_rows = fused_finish(
                updates_buf, malicious, noise, forge=forge, agg=aspec,
                sanitize=fr.health_check,
            )
            agg_vec = agg_vec[:d]  # drop stripe-alignment padding columns
        return _serve_aggregate(server_state, agg_vec, malicious, losses,
                                sq_norms, bad_rows)

    @partial(jax.jit, static_argnames=("nb_real",))
    def _finish_fused_compact(server_state, updates_buf, malicious, losses,
                              k_adv, nb_real):
        """Fused finish over the benign-compacted matrix: the forged row
        participates as a virtual row of multiplicity ``malicious_prefix``
        (ops/pallas_round.fused_finish_compact) — per-row kernel work and
        matrix HBM both shrink by the byzantine fraction.  ``nb_real`` is
        the benign row count; rows past it are the caller's +inf sublane
        padding.  Forge and aggregate are ONE Mosaic call here: it sits
        under ``blades/aggregate``, and ``blades/forge`` holds only the
        adaptive forge's uniforms."""
        from blades_tpu.ops.pallas_round import fused_finish_compact

        d, noise = _model_d_and_noise(server_state, updates_buf, k_adv)
        forge, aspec = spec
        with jax.named_scope("blades/aggregate"):
            agg_vec, sq_b, bad_b, forged = fused_finish_compact(
                updates_buf, noise, forged_mult=malicious_prefix,
                forge=forge, agg=aspec, sanitize=fr.health_check,
                num_real=nb_real, mxu_finish=mxu_finish,
            )
            agg_vec, forged = agg_vec[:d], forged[:d]
        fsq = forged @ forged
        sq = jnp.concatenate(
            [jnp.full((malicious_prefix,), fsq, jnp.float32), sq_b])
        bad = jnp.concatenate(
            [jnp.zeros((malicious_prefix,), bool), bad_b])
        return _serve_aggregate(server_state, agg_vec, malicious, losses,
                                sq, bad)

    # Whether the row-geometry materialization rewrites the buffer at all
    # (when not, the buffer is read-only and one stats pass suffices).
    _rowgeom_rewrites = forges or dp or fr.health_check

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def _rowgeom_mat_chunk(updates_buf, sq_acc, bad_acc, malicious,
                           row_norms, k_adv, k_dp, i, start):
        """One chunk of the row-geometry materialization: sanitize/DP/
        forge the chunk and write it back into the DONATED buffer.

        A host loop of donated dispatches, not a ``lax.scan`` — a giant
        scan carry double-buffers the matrix in HLO and OOMs at the
        1000-client scale (the same reason training runs as per-block
        dispatches).  Forgers receive a
        :class:`~blades_tpu.ops.layout.ChunkInfo` and the UNFOLDED round
        key, so coordinate-position logic and global draws match the
        dense round exactly (NoiseAdversary folds the chunk index itself
        via ``shard.fold``).
        """
        from blades_tpu.ops.layout import ChunkInfo

        from blades_tpu.parallel.streamed_geometry import new_cols

        n = updates_buf.shape[0]
        # d_model, not the buffer width: rowgeom buffers may carry
        # stripe-alignment padding columns (zeros) the materialization
        # must never rewrite — a forged/noised padding column would
        # corrupt the kernel's whole-stripe statistics.
        d = d_model
        c = min(d_chunk, d)
        raw = lax.dynamic_slice(updates_buf, (0, start), (n, c))
        chunk = raw.astype(jnp.float32)
        if fr.health_check:
            from blades_tpu.core.health import sanitize_updates

            chunk, chunk_healthy = sanitize_updates(chunk)
            bad_acc = bad_acc | ~chunk_healthy
        if dp:
            chunk = _dp_chunk(chunk, row_norms, k_dp, i)
        if forges:
            with jax.named_scope("blades/forge"):
                chunk = fr.adversary.on_updates_ready(
                    chunk, malicious, k_adv, aggregator=agg,
                    global_params=None,
                    shard=ChunkInfo(global_d=d, width=c, start=start,
                                    index=i),
                )
        new = new_cols(start, i, c)
        sq_acc = sq_acc + jnp.where(new[None, :], chunk**2, 0.0).sum(axis=1)
        # Write back ONLY this chunk's not-yet-covered columns: the tail
        # chunk overlaps its predecessor, and DP clip/noise (and Noise
        # forging) are not idempotent — reprocessing the overlap would
        # double-clip and double-noise it.
        updates_buf = lax.dynamic_update_slice(
            updates_buf,
            jnp.where(new[None, :], chunk.astype(update_dtype), raw),
            (0, start),
        )
        return updates_buf, sq_acc, bad_acc

    @jax.jit
    def _rowgeom_aggregate(server_state, updates_buf, malicious, losses,
                           sq, bad_rows, k_agg):
        """Fused aggregator bundles over the (read-only,
        post-materialization) buffer + the shared serve tail.  ``sq`` is
        ``None`` on the read-only path — the row-norm request then fuses
        into the aggregator's first statistics traversal instead of
        costing its own pass."""
        from blades_tpu.parallel.streamed_geometry import aggregate_streamed

        trusted = fr.compute_trusted_update(
            server_state.params, jax.random.fold_in(k_agg, 1)
        )
        with jax.named_scope("blades/aggregate"):
            agg_vec, agg_state, sq = aggregate_streamed(
                agg, updates_buf, sq, server_state.agg_state, key=k_agg,
                trusted=trusted, d_chunk=d_chunk, d=d_model,
                recorder=_pass_recorder, fuse=fuse_rowgeom,
            )
        return _serve_aggregate(server_state, agg_vec, malicious, losses,
                                sq, bad_rows, agg_state=agg_state)

    @jax.jit
    def _forge_row(updates_buf, malicious, sq, k_adv):
        """Fused stats bundles of a row-geometry forge -> the forged
        (d,) row and the post-forge row squared norms.  ``sq`` may be
        ``None`` (read-only buffer): the row-norm request fuses into the
        forge's first bundle."""
        from blades_tpu.parallel.streamed_geometry import (
            PassPlanner,
            forge_streamed,
        )

        planner = PassPlanner(updates_buf, d_chunk, d=d_model,
                              recorder=_pass_recorder, fuse=fuse_rowgeom)
        with jax.named_scope("blades/forge"):
            forged, sq = forge_streamed(
                fr.adversary, updates_buf, malicious, sq, k_adv, agg,
                planner,
            )
            sq = jnp.where(malicious, forged @ forged, sq)
        return forged, sq

    @partial(jax.jit, donate_argnums=(0,))
    def _scatter_chunk(updates_buf, forged, malicious, start):
        """Write the forged row's columns into the malicious lanes of one
        chunk of the DONATED buffer (idempotent on the overlap tail;
        padding columns past d_model are never touched)."""
        n = updates_buf.shape[0]
        c = min(d_chunk, d_model)
        with jax.named_scope("blades/forge"):
            fs = lax.dynamic_slice(forged, (start,), (c,))
            chunk = lax.dynamic_slice(updates_buf, (0, start), (n, c))
            chunk = jnp.where(malicious[:, None],
                              fs[None, :].astype(chunk.dtype), chunk)
            return lax.dynamic_update_slice(updates_buf, chunk, (0, start))

    @jax.jit
    def _coordwise_after_forge(server_state, updates_buf, malicious, losses,
                               sq, bad_rows):
        """Coordinate-wise aggregation over a buffer whose forge was
        already materialized (row-geometry attacker + Mean/Median/
        Trimmedmean)."""
        from blades_tpu.parallel.streamed_geometry import aggregate_coordwise

        with jax.named_scope("blades/aggregate"):
            agg_vec = aggregate_coordwise(
                agg, updates_buf, min(d_chunk, d_model), d=d_model,
                recorder=_pass_recorder,
            )
        return _serve_aggregate(server_state, agg_vec, malicious, losses,
                                sq, bad_rows)

    d_model = None  # resolved from params on first call
    # Single-slot cache holding the LAST validated mask object.  The
    # strong reference pins it so its id cannot be recycled; a bare
    # id-set would let a freed-and-reallocated DIFFERENT mask at the
    # same address silently skip validation (ADVICE r4), and an
    # unbounded dict would pin every mask a fresh-mask-per-round caller
    # ever passed.  The identity compare keeps the steady-state cost at
    # nothing (a content digest would fetch the mask from the device
    # every round); callers alternating between two mask
    # objects re-pay validation, which no current caller does (Fedavg
    # passes one cached mask for the run).
    _checked_mask = [None]

    @partial(jax.jit, static_argnames=("rows", "nb", "d"))
    def _alloc_row_padded(rows, nb, d):
        """The compact matrix with its +inf sublane-padding rows built in
        ONE program (zeros-then-set would transiently hold two copies of
        a near-HBM-sized buffer)."""
        col = jnp.where(jnp.arange(rows) >= nb,
                        jnp.inf, 0.0).astype(update_dtype)
        return jnp.broadcast_to(col[:, None], (rows, d))

    def step(state: RoundState, data_x, data_y, lengths, malicious, key):
        """One streamed round.  Three host spans (obs/trace.py) bound its
        phases on the calling thread: ``blades/prepare`` (key splits,
        mask check, geometry, allocation of the update matrix: all that
        precedes the first block), one ``blades/block`` per trained
        block and ``blades/finish`` (whichever finish runs, its chunk
        loops included).  ``blades/block`` and ``blades/finish`` time the
        ENQUEUE of their programs, not their device time: dispatch is
        asynchronous, and the host runs ahead of the device."""
        nonlocal d_model
        with span("blades/prepare"):
            n = data_x.shape[0]
            if row_geom or row_forges:
                # Checked BEFORE training: the round below donates the
                # caller's opt state and burns a full training pass.
                if fr.num_clients is not None and fr.num_clients != n:
                    raise ValueError(
                        f"the streamed row-geometry finish needs num_clients "
                        f"({fr.num_clients}) == data rows ({n}): ghost lanes "
                        "would enter the row geometry"
                    )
                from blades_tpu.parallel.streamed_geometry import (
                    check_applicable,
                )

                check_applicable(agg, n)
            if d_model is None:
                d_model = sum(
                    p.size for p in jax.tree.leaves(state.server.params))
            from blades_tpu.ops.pallas_round import should_use

            # Per-call (n can differ between calls): ghost (padding) lanes
            # force the chunked path — slicing them off before a pallas_call
            # would materialize a second copy of the giant matrix, and the
            # kernel has no lane-validity input.
            no_ghosts = fr.num_clients is None or fr.num_clients == n
            use_fused = (spec is not None and no_ghosts
                         and should_use(n, d_model))
            # Same RNG stream as FedRound.step.
            k_sample, k_train, k_adv, k_agg, k_dp = jax.random.split(key, 5)
            sample_keys = jax.random.split(k_sample, n)
            train_keys = jax.random.split(k_train, n)
            # Malicious-lane training elision (see malicious_prefix above):
            # lanes of the forged prefix never train — their rows stay zero
            # (finite, benign-invisible) and the forge overwrites them
            # before any aggregator reads them.
            elide = (malicious_prefix
                     if malicious_prefix and (coord_forges or row_forges)
                     else 0)
            # Benign-compacted fused finish: with the prefix elided, the
            # matrix stores ONLY the benign rows and the forged row enters
            # the order statistics as a virtual row of multiplicity
            # `malicious_prefix` (fused_finish_compact) — matrix HBM and
            # per-row kernel work shrink by the byzantine fraction.
            from blades_tpu.ops.pallas_select import kernel_applicable

            nb = n - (malicious_prefix or 0)
            compact = (spec is not None and no_ghosts and coord_forges
                       and elide > 0 and kernel_applicable(nb, d_model))
            use_fused = use_fused or compact
            # Off the compact path rows are lanes, and training starts at
            # the block boundary under the prefix (block_plan): the
            # malicious lanes from there on train harmlessly.
            plan = block_plan(n, elide, client_block, update_dtype,
                              compact=compact)
            if plan.first_lane and _checked_mask[0] is not malicious:
                # Validate the caller's promise ONCE per mask object — a
                # wrong mask would silently aggregate zero rows for
                # benign clients.  Per-round checking would cost a
                # host<->device fetch that drains the dispatch pipeline,
                # so the check is cached by array identity.
                mal_np = np.asarray(malicious)  # blades-lint: disable=host-sync — once per mask object, by design (see comment above)
                if not (bool(mal_np[:plan.first_lane].all())
                        and not bool(mal_np[malicious_prefix:].any())):
                    raise ValueError(
                        f"malicious_prefix={malicious_prefix} promised "
                        "exactly the first lanes malicious, but the "
                        "malicious mask disagrees — elision would zero "
                        "benign updates (or treat trained malicious lanes "
                        "as benign on the compacted path)"
                    )
                _checked_mask[0] = malicious
            # The fused pallas finishes want their columns a whole number
            # of grid steps wide; padding at allocation (zero columns,
            # sliced off the aggregate) avoids a whole-matrix pad copy
            # inside the kernel call.  The compact matrix is also a whole
            # number of blocks (and of sublanes) high, its +inf padding
            # rows, which the kernel excludes via num_real, taking a
            # padded last block's surplus; or it keeps a row a plane
            # (compact_matrix).  The full fused finish's stripe is as wide
            # as the matrix's height allows (stripe_cols), a multiple of
            # the 512 the row-stats kernel walks.  The row-geometry path
            # pads for that kernel whenever it can serve its planner
            # bundles (chunk traversals are bounded to d_model either way,
            # so padding is inert on the fallback path).
            from blades_tpu.ops.pallas_select import (
                _BLOCK_D,
                stripe_cols,
                stripe_padded,
            )

            finish_cols, shape = None, (n, d_model)
            if compact:
                shape, finish_cols = compact_matrix(plan, nb, d_model)
            elif use_fused:
                finish_cols = stripe_cols(n)
                shape = (n, stripe_padded(d_model, n))
            elif row_geom or row_forges:
                from blades_tpu.ops.pallas_rowstats import (
                    kernel_applicable as _rowstats_ok,
                )

                if _rowstats_ok(n, d_model):
                    shape = (n, -(-d_model // _BLOCK_D) * _BLOCK_D)
            if compact and shape[0] != nb:
                updates_buf = _alloc_row_padded(shape[0], nb, shape[1])
            else:
                updates_buf = jnp.zeros(shape, update_dtype)
            client_opt = state.client_opt
            if not donate:
                client_opt = jax.tree.map(jnp.copy, client_opt)
            # Elided lanes: no program runs, their losses and norms read 0.
            elided = ([jnp.zeros((plan.first_lane,), jnp.float32)]
                      if plan.first_lane else [])
            losses, norms, stats = list(elided), list(elided), []
        for b in range(plan.blocks):
            with span("blades/block"):
                # The index goes in as a NumPy scalar: an argument of the
                # block's own launch, not an eager convert program.
                updates_buf, client_opt, loss, blk_norms, blk_stats = \
                    _dispatch(
                        _train_block, updates_buf, client_opt,
                        state.server.params, data_x, data_y, lengths,
                        malicious, sample_keys, train_keys, np.uint32(b),
                        plan=plan,
                    )
            losses.append(loss)
            norms.append(blk_norms)
            stats.append(blk_stats)

        def per_lane(parts):
            """The blocks' per-lane vectors as one ``(n,)`` vector: the
            padded last block's surplus lanes stop here."""
            if plan.surplus:
                parts = parts[:-1] + [parts[-1][plan.surplus:]]
            return jnp.concatenate(parts)

        with span("blades/finish"):
            if row_geom or row_forges:
                from blades_tpu.parallel.streamed_geometry import chunk_grid

                c, k_chunks, _ = chunk_grid(d_model, d_chunk)
                if _rowgeom_rewrites:
                    sq = jnp.zeros((n,), jnp.float32)
                    bad = jnp.zeros((n,), bool)
                    cat_norms = per_lane(norms)
                    for i in range(k_chunks):
                        updates_buf, sq, bad = _dispatch(
                            _rowgeom_mat_chunk, updates_buf, sq, bad,
                            malicious, cat_norms, k_adv, k_dp, jnp.int32(i),
                            jnp.int32(min(i * c, d_model - c)),
                        )
                else:
                    # Read-only buffer: no dedicated row-norm traversal — the
                    # sq request fuses into the forge's/aggregator's first
                    # statistics bundle (sq=None threads through).
                    sq = None
                    bad = jnp.zeros((n,), bool)
                if row_forges:
                    # Stats passes -> forged (d,) row, then scatter it into
                    # the malicious lanes chunk by chunk (donated buffer).
                    forged, sq = _dispatch(_forge_row, updates_buf, malicious,
                                           sq, k_adv)
                    for i in range(k_chunks):
                        updates_buf = _dispatch(
                            _scatter_chunk, updates_buf, forged, malicious,
                            jnp.int32(min(i * c, d_model - c)),
                        )
                if row_geom:
                    server, metrics = _dispatch(
                        _rowgeom_aggregate, state.server, updates_buf,
                        malicious, per_lane(losses), sq, bad, k_agg,
                    )
                else:
                    server, metrics = _dispatch(
                        _coordwise_after_forge, state.server, updates_buf,
                        malicious, per_lane(losses), sq, bad,
                    )
            elif compact:
                server, metrics = _dispatch(
                    _finish_fused_compact, state.server, updates_buf,
                    malicious, per_lane(losses), k_adv, nb_real=nb,
                )
            elif use_fused:
                server, metrics = _dispatch(
                    _finish_fused, state.server, updates_buf, malicious,
                    per_lane(losses), k_adv,
                )
            else:
                server, metrics = _dispatch(
                    _finish, state.server, updates_buf, malicious,
                    per_lane(losses), per_lane(norms),
                    k_adv, k_dp,
                )
            if row_geom or row_forges:
                # Pass-fusion telemetry (schema-registered, stamped host-side
                # like elided_lanes): planned full-matrix HBM traversals this
                # round — the fused plan vs the one-traversal-per-statistic
                # baseline.  Planner counts fill at first trace; the fixed
                # components are the materialization rewrite and the forged-
                # row scatter, each one traversal.  Data-dependent Weiszfeld
                # loops count maxiter iterations (a planned upper bound).
                fixed_passes = ((1 if _rowgeom_rewrites else 0)
                                + (1 if row_forges else 0))
                metrics["hbm_passes"] = jnp.int32(
                    _pass_recorder.executed + fixed_passes)
                metrics["hbm_passes_unfused"] = jnp.int32(
                    _pass_recorder.unfused + fixed_passes)
                _pass_recorder.finalize()
            if plan.first_lane:
                # Elision telemetry (schema-registered): lanes whose training
                # was skipped this round — the lanes num_unhealthy can
                # never count (an elided lane never trains, so it cannot trip
                # the health detectors; see parallel/dsharded.py's elision
                # caveats for the shared contract).  Only added when elision
                # engages, so non-elided rounds' metrics are unchanged.
                metrics["elided_lanes"] = np.int32(plan.first_lane)
            # Row counters (``counter_<name>``: the row takes them under
            # their schema-registered names).  A sequence task's tokens
            # the trained lanes saw, a host int; and whatever the model
            # counts of its own layers, device scalars.
            if fr.task.sequence:
                metrics["counter_tokens_trained"] = np.int32(
                    (n - plan.first_lane) * fr.num_batches_per_round
                    * fr.batch_size * data_x.shape[-1])
            if jax.tree.leaves(stats):
                metrics.update(
                    ("counter_" + name, v) for name, v in _dispatch(
                        _round_counters, stats,
                        surplus=plan.surplus).items())
            # Store telemetry (schema-registered, host-side like
            # elided_lanes): the blocks whose rows were written into the
            # matrix, how many of those writes were whole storage tiles at
            # a tile-aligned row (a plain copy on the chip), and the lanes
            # the padded last block trained again and dropped.
            metrics["store_blocks"] = np.int32(plan.blocks)
            metrics["store_blocks_aligned"] = np.int32(plan.aligned_stores)
            metrics["surplus_lanes"] = np.int32(plan.surplus)
            if finish_cols:
                # The fused finish's stripe width at this matrix's height
                # (pallas_select.stripe_cols): the columns its allocation
                # was padded to, and the grid is d_alloc over it.
                metrics["finish_stripe_cols"] = np.int32(finish_cols)
        return RoundState(server=server, client_opt=client_opt), metrics

    # Expose the jitted phases for profiling / inspection.  A round runs
    # train_block xN then exactly one of the finishes — finish_fused_compact
    # when the malicious prefix is elided and the kernel
    # applies (the headline benchmark configuration), finish_fused for
    # full-matrix kernel rounds, finish otherwise.  The fused handles
    # exist only for configs the kernel covers.
    step.train_block = _train_block
    step.finish = _finish
    if spec is not None:
        step.finish_fused = _finish_fused
        step.finish_fused_compact = _finish_fused_compact
    return step

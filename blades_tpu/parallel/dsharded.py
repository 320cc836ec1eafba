"""Large-federation round: all-to-all re-sharding of the update matrix.

At the BASELINE north-star scale (1000 clients x ResNet-18's d~11M), the
full ``(n, d)`` update matrix is ~45 GB f32 — it cannot be materialised
per device the way :func:`~blades_tpu.parallel.sharded.shard_map_step`'s
``all_gather`` does (SURVEY.md §7.3 "the real TPU systems problem").

The fix is the classic axis swap (the same collective pattern as
DeepSpeed-Ulysses' sequence<->head re-shard, done here over ICI with
``lax.all_to_all``): each device holds its local clients' full-width rows
``(n_local, d)``; one all-to-all turns that into all clients' rows on a
width shard ``(n, d_local)``.  Per-device memory stays ``n*d/n_dev``.

On the ``(n, d_local)`` layout every aggregator in the suite is exact:

- **coordinate-wise** (Mean, Median, Trimmedmean) — they never mix
  coordinates; aggregate the shard directly.
- **row-geometry** (Multikrum, GeoMed, MinMax-style distances, FLTrust
  cosines) — cross-coordinate reductions are ``psum``s of shard-partial
  Gram/norm terms (:mod:`blades_tpu.ops.layout`), so the geometry is
  exact without ever materialising ``(n, d)`` anywhere.
- **stateful** (Centeredclipping's ``(d,)`` momentum, Clippedclustering's
  norm history) — state stays replicated exactly as on the dense path
  (a ``(d,)`` vector is small; it is the ``(n, d)`` *matrix* that must
  never exist), sliced to the local window for compute.
- **spectral** (DnC) — only the ``sub_dim`` *sampled* columns are
  assembled (psum of locally-owned columns), an ``(n, sub_dim)`` matrix
  with ``sub_dim << d``; the SVD runs replicated.

The server optimizer step is the IDENTICAL replicated
momentum/schedule/weight-decay program as the dense path
(:meth:`~blades_tpu.core.server.Server.apply_aggregate`): only the final
``(d,)`` aggregate is all-gathered.  Update-forging adversaries receive a
:class:`~blades_tpu.ops.layout.ShardInfo` and compute their global
geometry the same psum'd way (see
:mod:`blades_tpu.adversaries.update_attacks`).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from blades_tpu.core.round import FedRound, RoundState
from blades_tpu.data.sampler import sample_client_batches_with_keys
from blades_tpu.ops import clustering, layout as L, masked
from blades_tpu.ops.aggregators import (
    Centeredclipping,
    Clippedclustering,
    DnC,
    FLTrust,
    GeoMed,
    Mean,
    Median,
    Multikrum,
    Signguard,
    Trimmedmean,
)
from blades_tpu.parallel.mesh import CLIENTS_AXIS

AXIS = CLIENTS_AXIS


def _sign_census_majority(clipped: jax.Array, shard: L.ShardInfo) -> jax.Array:
    """SignGuard's k-means majority over psum'd global sign fractions.

    Matches :func:`blades_tpu.ops.clustering.sign_features` on the dense
    matrix: padding columns are zero, so global ``#zero`` is exactly
    ``global_d - #pos - #neg``.
    """
    d = shard.global_d
    pos = shard.psum((clipped > 0).sum(axis=1))
    neg = shard.psum((clipped < 0).sum(axis=1))
    zero = d - pos - neg
    feats = (
        jnp.stack([pos, neg, zero], axis=1).astype(clipped.dtype) / d
    )
    return clustering.kmeans_majority(feats)


def _aggregate_dshard(
    aggregator,
    upd_shard: jax.Array,
    shard: L.ShardInfo,
    *,
    key: Optional[jax.Array] = None,
    agg_state=(),
    trusted_shard: Optional[jax.Array] = None,
) -> Tuple[jax.Array, object]:
    """Aggregate an ``(n, d_local)`` shard -> ``(d_local,)``, exactly.

    Returns ``(aggregate_shard, new_agg_state)`` — the same contract as
    ``Aggregator.__call__`` on the dense matrix, with global geometry
    recovered via psum.  State layout is identical to the dense path's
    (replicated), so checkpoints are interchangeable between paths.
    """
    n = upd_shard.shape[0]
    if isinstance(aggregator, Mean):
        return upd_shard.mean(axis=0), agg_state
    if isinstance(aggregator, Median):
        return masked.median(upd_shard), agg_state
    if isinstance(aggregator, Trimmedmean):
        k = aggregator.num_excluded
        if n <= 2 * k:
            raise ValueError(f"Trimmedmean needs > {2*k} clients, got {n}")
        s = jnp.sort(upd_shard, axis=0)
        return s[k : n - k].mean(axis=0), agg_state
    if isinstance(aggregator, Multikrum):
        f = aggregator.num_byzantine
        if 2 * f + 2 > n:
            raise ValueError(f"Too many Byzantine workers: 2*{f}+2 > {n}")
        if not (1 <= aggregator.k <= n):
            raise ValueError(f"k must be in [1, {n}], got {aggregator.k}")
        d2 = L.pairwise_sq_dists(upd_shard, shard)
        d2 = jnp.maximum(d2, 0.0)
        d2 = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d2)
        nearest = jnp.sort(d2, axis=1)[:, : n - f - 2]
        rank = jnp.argsort(jnp.argsort(nearest.sum(axis=1)))
        return masked.masked_mean(upd_shard, rank < aggregator.k), agg_state
    if isinstance(aggregator, GeoMed):
        weights = jnp.ones((n,), upd_shard.dtype) / n

        def dists(median_shard):
            return L.row_norms(upd_shard - median_shard[None, :], shard)

        def wavg(w):
            return (w[:, None] * upd_shard).sum(axis=0) / w.sum()

        median = wavg(weights)

        def body(_, m):
            dn = jnp.maximum(dists(m), aggregator.eps)
            return wavg(weights / dn)

        return lax.fori_loop(0, aggregator.maxiter, body, median), agg_state
    if isinstance(aggregator, DnC):
        if key is None:
            raise ValueError("DnC requires a PRNG key (see ops/aggregators.py)")
        d = shard.global_d
        sub_dim = min(aggregator.sub_dim, d)
        keep = n - int(aggregator.filter_frac * aggregator.num_byzantine)
        if keep < 1:
            raise ValueError(
                f"DnC keeps {keep} clients; needs >= 1 (n={n}, "
                f"f={aggregator.num_byzantine})"
            )
        offset = shard.offset()
        benign = jnp.zeros((n,), dtype=bool)
        # Assemble only the SAMPLED columns: each shard contributes the
        # columns it owns, one psum makes the (n, sub_dim) matrix global.
        for k_iter in jax.random.split(key, aggregator.num_iters):
            idx = jax.random.permutation(k_iter, d)[:sub_dim]
            local_pos = idx - offset
            owned = (local_pos >= 0) & (local_pos < shard.width)
            cols = jnp.take(
                upd_shard, jnp.clip(local_pos, 0, shard.width - 1), axis=1
            )
            sub = shard.psum(jnp.where(owned[None, :], cols, 0.0))
            mu = sub.mean(axis=0)
            centered = sub - mu
            v = jnp.linalg.svd(centered, full_matrices=False)[2][0]
            s = (centered @ v) ** 2
            rank = jnp.argsort(jnp.argsort(s))
            benign = benign | (rank < keep)
        return masked.masked_mean(upd_shard, benign), agg_state
    if isinstance(aggregator, FLTrust):
        if trusted_shard is None:
            raise ValueError(
                "FLTrust requires the server's trusted root-data update "
                "(FedRound.trusted_data)"
            )
        s_norm = jnp.sqrt(jnp.maximum(shard.psum((trusted_shard**2).sum()), 0.0))
        c_norm = jnp.maximum(L.row_norms(upd_shard, shard), 1e-12)
        cos = L.row_dots(upd_shard, trusted_shard, shard) / (
            c_norm * jnp.maximum(s_norm, 1e-12)
        )
        trust = jax.nn.relu(cos)
        rescaled = upd_shard * (s_norm / c_norm)[:, None]
        agg = (trust[:, None] * rescaled).sum(axis=0) / jnp.maximum(
            trust.sum(), 1e-12
        )
        return agg, agg_state
    if isinstance(aggregator, Centeredclipping):
        momentum = agg_state
        if momentum is None or (isinstance(momentum, tuple) and not momentum):
            momentum = jnp.zeros((shard.global_d,), upd_shard.dtype)
        mom_local = L.slice_to_shard(momentum, shard)

        def body(_, center):
            dev = L.clip_rows_to_norm(
                upd_shard - center[None, :], aggregator.tau, shard
            )
            return center + dev.mean(axis=0)

        mom_local = lax.fori_loop(0, aggregator.n_iter, body, mom_local)
        new_momentum = lax.all_gather(mom_local, shard.axis, axis=0, tiled=True)[
            : shard.global_d
        ]
        return mom_local, new_momentum
    if isinstance(aggregator, Signguard):
        norms = L.row_norms(upd_shard, shard)
        M = jnp.median(norms)
        clipped = upd_shard * jnp.minimum(
            1.0, M / jnp.maximum(norms, 1e-12)
        )[:, None]
        cnorms = jnp.minimum(norms, M)
        s1 = (cnorms >= 0.1 * M) & (cnorms <= 3.0 * M)
        s2 = _sign_census_majority(clipped, shard)
        mask = s1 & s2
        if aggregator.agg == "mean":
            return masked.masked_mean(clipped, mask), agg_state
        return masked.masked_median(clipped, mask), agg_state
    if isinstance(aggregator, Clippedclustering):
        norms = L.row_norms(upd_shard, shard)
        state = agg_state
        if state is None or (isinstance(state, tuple) and not state):
            state = aggregator.init(shard.global_d, n)
        hist, count = state["norm_history"], state["count"]
        cap = hist.shape[0]
        pos = (count + jnp.arange(n)) % cap
        hist = hist.at[pos].set(norms.astype(hist.dtype))
        count = count + n
        filled = jnp.arange(cap) < jnp.minimum(count, cap)
        threshold = masked.masked_median(hist[:, None], filled)[0]
        threshold = jnp.minimum(threshold, aggregator.max_tau)
        clipped = upd_shard * jnp.minimum(
            1.0, threshold / jnp.maximum(norms, 1e-12)
        )[:, None]
        cl_norms = jnp.minimum(norms, threshold)
        normed = clipped / jnp.maximum(cl_norms, 1e-12)[:, None]
        cos = jnp.clip(L.gram(normed, shard), -1.0, 1.0)
        dist = 1.0 - cos
        # Zero-norm rows -> max distance 2 (ref: clippedclustering.py:49-51).
        zero = cl_norms < 1e-12
        bad = zero[:, None] | zero[None, :]
        dist = jnp.where(bad, 2.0, dist)
        mask = clustering.agglomerative_majority(dist, linkage=aggregator.linkage)
        if aggregator.signguard:
            mask = mask & _sign_census_majority(clipped, shard)
        if aggregator.agg == "mean":
            agg = masked.masked_mean(clipped, mask)
        else:
            agg = masked.masked_median(clipped, mask)
        return agg, {"norm_history": hist, "count": count}
    raise NotImplementedError(
        f"{type(aggregator).__name__} has no d-sharded formulation"
    )


def _build_dsharded_body(fr: FedRound, mesh: Mesh,
                         malicious_prefix: Optional[int] = None) -> Callable:
    """The un-jitted shard_map round body that :func:`dsharded_step`
    jits.

    ``malicious_prefix``: the streamed path's malicious-lane training
    ELISION (parallel/streamed.py), on the client-shard layout.  Every
    update-forging adversary computes its forged rows from BENIGN
    statistics only and replaces the malicious rows wholesale
    (``scatter_forged``), so what those lanes train is dead computation
    — with ``malicious_prefix = f`` each chip trains only its benign
    lanes and writes zero rows for the malicious ones, which the forge
    then overwrites post-swap.  Exact: bit-equal round output (DP rows
    are clipped/noised per-row, so zeroed dead rows stay dead;
    tests/test_dsharded.py).  Requires the STRIDED client layout —
    every chip's local lanes are ``[f/n_dev malicious | benign]`` —
    produced by :func:`elision_client_order`; the step wrapper validates
    the caller's mask against that promise once per mask object.
    Ignored (trains everyone) when the adversary does not forge
    updates: a training-side attack's malicious lanes do real work.

    Elision caveats (ADVICE r5) — exactness above is *within the strided
    layout*; three things are observably different from other runs:

    - **Telemetry basis**: ``num_unhealthy`` counts only TRAINED lanes —
      an elided malicious lane whose real training would have produced
      non-finite values reads as healthy (its zero row is finite), so
      health counts can differ from the non-elided round even though
      server state is bit-equal.  The ``elided_lanes`` round metric
      (schema-registered) surfaces how many lanes that optimistic basis
      excludes.
    - **RNG pairing vs dense runs**: per-client sample/train keys derive
      from LANE POSITION (``fold_in(axis_index)`` + per-lane splits),
      and the elision layout PERMUTES which client sits in which lane
      (:func:`elision_client_order`, applied by ``Fedavg._setup``).  An
      elided run at seed ``s`` therefore pairs client ``i`` with a
      different key stream than a natural-order dense run at the same
      seed — statistically equivalent (both are valid iid assignments)
      but NOT bitwise-comparable across layouts.  Elided vs non-elided
      *on the same strided layout* (what tests assert) stays bit-equal.
    - **Frozen optimizer state**: an elided malicious lane's
      ``client_opt`` entry keeps its incoming value forever (the dead
      training that would have evolved it is skipped), so CHECKPOINTS
      diff against a non-elided run's even when server params are
      bit-equal.  Unobservable in training unless an adversary stops
      forging mid-run — which no registry attack does — but diff tools
      comparing checkpoint files must expect it.
    """
    # Override check, not hasattr: the Adversary base class defines an
    # identity on_updates_ready, and a training-side attack (SignFlip)
    # must keep training its lanes.
    from blades_tpu.parallel.streamed import _adv_forges

    adv_forges = _adv_forges(fr.adversary)
    n_dev = mesh.devices.size
    f_local = 0
    if malicious_prefix and adv_forges:
        # floor(f / n_dev) lanes elided per chip; the f mod n_dev
        # remainder malicious lanes sit in the tails and train
        # harmlessly (their rows are forged over anyway), keeping the
        # per-chip shapes uniform for SPMD.
        f_local = malicious_prefix // n_dev
    state_spec = RoundState(server=P(), client_opt=P(AXIS))
    data_spec = P(AXIS)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(state_spec, data_spec, data_spec, data_spec, data_spec, P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    def _step(state: RoundState, data_x, data_y, lengths, malicious, key):
        n_local = data_x.shape[0]
        k_local, k_adv, k_agg, k_dp = jax.random.split(key, 4)
        dev_key = jax.random.fold_in(k_local, lax.axis_index(AXIS))
        k_sample, k_train = jax.random.split(dev_key)

        hooks = fr._hooks()
        # Keys are pre-split over ALL local lanes and sliced, so the
        # benign lanes draw byte-identical batches/train streams whether
        # or not the malicious prefix is elided.
        sample_keys = jax.random.split(k_sample, n_local)
        client_keys = jax.random.split(k_train, n_local)

        def train(slc):
            bx, by = sample_client_batches_with_keys(
                sample_keys[slc], data_x[slc], data_y[slc], lengths[slc],
                fr.batch_size, fr.num_batches_per_round)
            return fr.task.local_round_batched(
                state.server.params,
                jax.tree.map(lambda a: a[slc], state.client_opt),
                bx, by, client_keys[slc], malicious[slc], *hooks)[:3]

        if f_local:
            # Elision: train only the benign tail; the malicious-prefix
            # lanes get zero rows (replaced by the forge post-swap),
            # zero losses (benign-masked out of train_loss), and keep
            # their (dead) optimizer state untouched.
            upd_b, opt_b, losses_b = train(slice(f_local, None))
            upd_local = jnp.concatenate(
                [jnp.zeros((f_local, upd_b.shape[1]), upd_b.dtype), upd_b])
            losses_local = jnp.concatenate(
                [jnp.zeros((f_local,), losses_b.dtype), losses_b])
            client_opt = jax.tree.map(
                lambda dead, new: jnp.concatenate([dead[:f_local], new]),
                state.client_opt, opt_b)
        else:
            upd_local, client_opt, losses_local = train(slice(None))
        upd_local = fr.apply_dp(
            upd_local, jax.random.fold_in(k_dp, lax.axis_index(AXIS))
        )

        # Zero-pad d to a multiple of the mesh, then the axis swap:
        # (n_local, d_pad) --all_to_all--> (n, d_pad / n_dev).
        d = upd_local.shape[1]
        d_pad = -(-d // n_dev) * n_dev
        width = d_pad // n_dev
        shard = L.ShardInfo(axis=AXIS, num_shards=n_dev, global_d=d, width=width)
        upd_local = jnp.pad(upd_local, ((0, 0), (0, d_pad - d)))
        upd_shard = lax.all_to_all(
            upd_local.reshape(n_local, n_dev, width),
            AXIS, split_axis=1, concat_axis=0, tiled=False,
        ).reshape(n_local * n_dev, width)

        mal_all = lax.all_gather(malicious, AXIS, axis=0, tiled=True)
        losses = lax.all_gather(losses_local, AXIS, axis=0, tiled=True)
        # Drop ghost (padding) lanes — see FedRound.num_clients.
        k = fr.num_clients
        if k is not None and k < upd_shard.shape[0]:
            upd_shard, mal_all, losses = upd_shard[:k], mal_all[:k], losses[:k]

        healthy = None
        if fr.health_check:
            # Row health over the FULL width: a lane is unhealthy if any
            # of its shards holds a non-finite value — one psum of the
            # per-shard verdicts, then the whole row is zeroed everywhere
            # (same semantics as core.health.sanitize_updates).
            local_bad = ~jnp.isfinite(upd_shard).all(axis=1)
            healthy = shard.psum(local_bad.astype(jnp.int32)) == 0
            upd_shard = jnp.where(healthy[:, None], upd_shard, 0.0)

        if adv_forges:
            upd_shard = fr.adversary.on_updates_ready(
                upd_shard, mal_all, k_adv,
                aggregator=fr.server.aggregator,
                global_params=state.server.params,
                shard=shard,
            )

        # FLTrust's trusted row: the server's own local round on root data,
        # computed replicated (identical on every device), window-sliced.
        trusted = fr.compute_trusted_update(
            state.server.params, jax.random.fold_in(k_agg, 1)
        )
        trusted_shard = (
            L.slice_to_shard(trusted, shard) if trusted is not None else None
        )

        agg_shard, agg_state = _aggregate_dshard(
            fr.server.aggregator, upd_shard, shard,
            key=k_agg, agg_state=state.server.agg_state,
            trusted_shard=trusted_shard,
        )

        # Gather only the (d,) aggregate; the optimizer step is the same
        # replicated program as the dense path (momentum/schedule/decay).
        agg = lax.all_gather(agg_shard, AXIS, axis=0, tiled=True)[:d]
        server = fr.server.apply_aggregate(state.server, agg, agg_state)

        benign = (~mal_all).astype(jnp.float32)
        train_loss = (losses * benign).sum() / jnp.maximum(benign.sum(), 1.0)
        metrics = {
            "train_loss": train_loss,
            "update_norm_mean": L.row_norms(upd_shard, shard).mean(),
            "agg_norm": jnp.linalg.norm(agg),
            "round": server.round,
        }
        if f_local:
            # Telemetry for the optimistic num_unhealthy basis (see the
            # elision caveats above): lanes whose training was skipped
            # this round, federation-wide.  Only present when elision is
            # engaged, keeping non-elided metrics pytrees unchanged.
            metrics["elided_lanes"] = jnp.int32(f_local * n_dev)
        if fr.health_check:
            from blades_tpu.core.health import guard_server_state

            # agg is already the replicated full (d,) vector.
            ok = jnp.isfinite(agg).all()
            server = guard_server_state(ok, server, state.server)
            metrics["num_unhealthy"] = (~healthy).sum()
            metrics["round_ok"] = ok
        return RoundState(server=server, client_opt=client_opt), metrics

    _step.f_local = f_local
    return _step


def elision_client_order(n: int, f: int, n_dev: int):
    """Client permutation for d-sharded malicious-lane elision.

    With the canonical prefix mask (clients ``0..f-1`` malicious) and
    contiguous sharding, whole chips would be all-malicious; elision
    needs every chip's LOCAL lanes to start with ``floor(f/n_dev)``
    malicious clients.  The ``f mod n_dev`` remainder malicious clients
    are placed in the first chips' TAILS, where they train harmlessly
    (uniform per-chip shapes; their rows are forged over regardless).
    Returns ``order`` such that ``array[order]`` lays clients out that
    way.

    NOTE: applying this permutation changes which lane-position-derived
    PRNG stream each client consumes, so a run on this layout is
    statistically- but not bitwise-comparable to a natural-order run at
    the same seed — see the elision caveats on
    :func:`_build_dsharded_body`.
    """
    import numpy as np

    if n % n_dev:
        raise ValueError(f"n={n} must divide the mesh ({n_dev})")
    if not (0 < f < n):
        raise ValueError(f"f={f} must be in (0, {n})")
    fl, r, nl = f // n_dev, f % n_dev, n // n_dev
    mal = iter(range(f))
    ben = iter(range(f, n))
    order = []
    for k in range(n_dev):
        extra = 1 if k < r else 0
        order += [next(mal) for _ in range(fl + extra)]
        order += [next(ben) for _ in range(nl - fl - extra)]
    return np.asarray(order)  # blades-lint: disable=host-sync — setup-time layout helper, never inside a round


def _validated(step, n_dev: int, f_local: int) -> Callable:
    """Wrap a jitted d-sharded step with the once-per-mask-object check
    that the caller's mask really is per-chip ``[f_local | benign]`` —
    a wrong mask would silently zero benign training (same promise
    validation as the streamed path, streamed.py)."""
    if not f_local:
        return step
    checked = [None]  # single slot pins the validated object (ADVICE r4)

    def wrapped(state, data_x, data_y, lengths, malicious, key):
        if checked[0] is not malicious:
            import numpy as np

            # Only the ELIDED prefix must be all-malicious — a benign
            # lane there would silently lose its training.  Malicious
            # lanes in the tail are fine (they train, then get forged).
            m = np.asarray(malicious).reshape(n_dev, -1)  # blades-lint: disable=host-sync — once per mask object (same contract as streamed.py)
            if not m[:, :f_local].all():
                raise ValueError(
                    f"d-sharded elision promised every chip's first "
                    f"{f_local} lanes malicious, but the mask disagrees "
                    "— lay clients out with elision_client_order, or "
                    "build the step without malicious_prefix")
            checked[0] = malicious
        return step(state, data_x, data_y, lengths, malicious, key)

    return wrapped


def dsharded_step(fr: FedRound, mesh: Mesh,
                  malicious_prefix: Optional[int] = None) -> Callable:
    """The giant-federation round: local training on client shards, ONE
    all-to-all to width shards, exact aggregation, and an all-gather of
    only the final ``(d,)`` aggregate into the replicated server step.

    Same signature and semantics as
    :func:`~blades_tpu.parallel.sharded.shard_map_step` — all ten
    aggregators, all update-forging adversaries, and the full server
    optimizer (momentum/schedule/weight-decay) are supported; results
    match the gather path up to float reassociation of the psum'd
    geometry (keyed noise draws excepted, see
    :class:`~blades_tpu.adversaries.update_attacks.NoiseAdversary`).
    Constraint: ``n`` divisible by the mesh size.

    ``malicious_prefix``: elide the dead malicious-lane training (see
    :func:`_build_dsharded_body`; requires the
    :func:`elision_client_order` layout, validated once per mask
    object).
    """
    body = _build_dsharded_body(fr, mesh, malicious_prefix)
    f_local = getattr(body, "f_local", 0)
    return _validated(jax.jit(body), mesh.devices.size, f_local)

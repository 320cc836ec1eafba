"""Experiment-parallelism: shape-compatible trials as vmapped lanes.

The reference runs Tune trials concurrently across a Ray cluster
(SURVEY.md §2.9, ref: blades/train.py:380-386).  On TPU the analogue is
ONE jit program with a leading trial axis: trials that share every
*static* config knob (model, aggregator type, adversary type, client
count, batch size...) but differ in **lane-traceable** knobs run as
vmapped lanes, so L trials cost one dispatch per round instead of L.

Lane-traceable knobs (``LANE_KEYS``):

- ``seed`` — per-lane data partition + PRNG key stream;
- ``client_lr`` / ``server_lr`` — become traced scalars inside the optax
  transforms (constructed per-trace, so a tracer flows through);
- ``dp_epsilon`` / ``dp_clip_threshold`` / ``dp_noise_factor`` — the DP
  grid (ref: fedavg_dp.yaml:15-16 sweeps eps over {1,10,100});
- ``adversary_scale`` — IPM's scale knob (ref:
  fedavg_cifar10_resnet_noniid.yaml sweeps IPM 0.1 vs 100).

Per-lane RNG mirrors the sequential driver exactly — lane i carries the
key stream of ``PRNGKey(seed_i)`` with the same split discipline as
``Fedavg`` — so a vmapped lane reproduces its sequential trial (within
vmap's floating-point reduction-order tolerance).

:func:`run_seed_lanes` (round 2's API) is the seed-only special case.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Flat FedavgConfig field names a lane may vary.  "seed" affects data and
# RNG; the rest become traced scalars threaded through dataclasses.replace
# on the FedRound (see _apply_lane).
LANE_KEYS = ("seed", "client_lr", "server_lr", "dp_epsilon",
             "dp_clip_threshold", "dp_noise_factor", "adversary_scale")


def _apply_lane(fr, sc: Dict[str, jax.Array]):
    """Rebuild a FedRound with this lane's traced scalars.

    Runs INSIDE the vmapped trace: the replaced fields hold tracers, so
    each lane computes with its own values while sharing one program.
    Only fields consumed arithmetically may be laned — structural gates
    (momentum on/off, DP on/off, adversary type) stay static and are
    enforced by the grouping logic in :func:`lane_groups`.
    """
    task, server, adv = fr.task, fr.server, fr.adversary
    if "client_lr" in sc:
        task = dataclasses.replace(
            task, spec=dataclasses.replace(task.spec, lr=sc["client_lr"])
        )
    if "server_lr" in sc:
        server = dataclasses.replace(server, lr=sc["server_lr"])
    if "adversary_scale" in sc:
        adv = dataclasses.replace(adv, scale=sc["adversary_scale"])
    kw = {}
    if "dp_clip_threshold" in sc:
        kw["dp_clip_threshold"] = sc["dp_clip_threshold"]
    if "dp_noise_factor" in sc:
        kw["dp_noise_factor"] = sc["dp_noise_factor"]
    return dataclasses.replace(fr, task=task, server=server, adversary=adv, **kw)


def run_lanes(
    config_builder: Callable[[], "FedavgConfig"],
    lane_overrides: List[Dict],
    max_rounds: int,
    program_key=None,
    donate: bool = True,
    tracer=None,
) -> List[List[Dict]]:
    """Run one trial per lane-override dict as vmapped lanes of a single
    program.

    Args:
        config_builder: zero-arg callable returning a fresh, un-frozen
            config with the group's SHARED settings applied.
        lane_overrides: per lane, a dict of ``LANE_KEYS`` (flat config
            field names) to that lane's value.  Keys must be identical
            across lanes (one program).
        max_rounds: FL rounds per trial.
        program_key: optional tuple fingerprinting the group's SHARED
            static config; when given, the vmapped step/eval programs go
            through the process-wide AOT executable cache
            (:mod:`blades_tpu.perf`), so identical lane groups compile
            once per process.
        donate: donate the lane states into each round dispatch (the
            L-times-stacked client opt states are the group's largest
            buffers); the pre-round states object is consumed.
        tracer: optional :class:`blades_tpu.obs.trace.Tracer` — round
            dispatches, evals and metric fetches become spans of the
            caller's tree (armed tracers additionally correlate device
            work via jax profiler annotations).

    Returns:
        Per lane, the list of per-round result dicts (Tune's
        ``result.json`` rows).
    """
    from blades_tpu.adversaries import make_malicious_mask
    from blades_tpu.data import DatasetCatalog
    from blades_tpu.obs.trace import Tracer

    if tracer is None:
        tracer = Tracer(record=False)  # aggregation-only, near-zero cost
    L = len(lane_overrides)
    keys_set = {frozenset(o.keys()) for o in lane_overrides}
    if len(keys_set) != 1:
        raise ValueError("all lanes must override the same keys")
    ok = next(iter(keys_set))
    unknown = set(ok) - set(LANE_KEYS)
    if unknown:
        raise ValueError(f"not lane-traceable: {sorted(unknown)}")

    # Per-lane configs (cheap: validate only) — the source of seeds and of
    # derived scalars like FedavgDPConfig's noise factor.
    cfgs = []
    for o in lane_overrides:
        c = config_builder()
        for k, v in o.items():
            if k == "adversary_scale":
                ac = dict(c.adversary_config or {})
                ac["scale"] = v
                c.adversary_config = ac
            else:
                setattr(c, k, v)
        c.validate()
        cfgs.append(c)
    base = cfgs[0]
    fr = base.get_fed_round()
    if getattr(fr.server.aggregator, "expects_trusted_row", False):
        raise ValueError("trust-bootstrapped aggregators are not lane-able")
    if "server_lr" in ok and base.lr_schedule:
        # lr_schedule() compares/divides schedule points (server.py),
        # which a traced per-lane lr cannot survive — the failure would
        # otherwise surface as an opaque TracerBoolConversionError.
        raise ValueError(
            "server_lr lanes are incompatible with a configured "
            "lr_schedule; drop the schedule or run these trials "
            "sequentially"
        )

    seeds = [c.seed for c in cfgs]
    # Traced scalar lanes, one per overridden knob (seed is handled via
    # data/keys; dp_epsilon reaches the program as the derived noise
    # factor validate() computed).
    def arr(field):
        return jnp.asarray([float(getattr(c, field)) for c in cfgs],
                           jnp.float32)

    sc = {}
    if "client_lr" in ok:
        sc["client_lr"] = arr("client_lr")
    if "server_lr" in ok:
        sc["server_lr"] = arr("server_lr")
    if "dp_epsilon" in ok or "dp_noise_factor" in ok:
        sc["dp_noise_factor"] = arr("dp_noise_factor")
    if "dp_clip_threshold" in ok:
        sc["dp_clip_threshold"] = arr("dp_clip_threshold")
    if "adversary_scale" in ok:
        sc["adversary_scale"] = jnp.asarray(
            [float(c.adversary_config["scale"]) for c in cfgs], jnp.float32
        )

    # Per-seed data partitions, stacked on a leading lane axis (shared and
    # broadcast when every lane uses the same seed).
    per_seed_data = len(set(seeds)) > 1

    def load(seed):
        ds = DatasetCatalog.get_dataset(
            base.dataset, num_clients=base.num_clients, iid=base.iid,
            alpha=base.dirichlet_alpha, seed=seed,
        )
        return ds

    first_ds = None
    if per_seed_data:
        stacks = {k: [] for k in ("x", "y", "ln", "tx", "ty", "tln")}
        for s in seeds:
            ds = load(s)
            first_ds = first_ds or ds
            stacks["x"].append(ds.train.x)
            stacks["y"].append(ds.train.y)
            stacks["ln"].append(ds.train.lengths)
            stacks["tx"].append(ds.test.x)
            stacks["ty"].append(ds.test.y)
            stacks["tln"].append(ds.test.lengths)

        # Shard sizes can differ per seed under Dirichlet; pad to the widest.
        def stack(arrs):
            cap = max(a.shape[1] for a in arrs) if arrs[0].ndim > 1 else None
            if cap is not None:
                arrs = [
                    np.pad(a, [(0, 0), (0, cap - a.shape[1])] + [(0, 0)] * (a.ndim - 2))
                    for a in arrs
                ]
            return jnp.asarray(np.stack(arrs))

        x, y, ln = stack(stacks["x"]), stack(stacks["y"]), stack(stacks["ln"])
        tx, ty, tln = (stack(stacks["tx"]), stack(stacks["ty"]),
                       stack(stacks["tln"]))
        dax = 0
    else:
        ds = load(seeds[0])
        first_ds = ds
        x, y, ln = (jnp.asarray(ds.train.x), jnp.asarray(ds.train.y),
                    jnp.asarray(ds.train.lengths))
        tx, ty, tln = (jnp.asarray(ds.test.x), jnp.asarray(ds.test.y),
                       jnp.asarray(ds.test.lengths))
        dax = None
    # Same auto-augment resolution as Fedavg._setup: crop+flip of the
    # synthetic fallback's Gaussian patterns destroys the signal.
    fr = base.resolve_augment_for_data(fr, first_ds)
    mal = make_malicious_mask(base.num_clients, base.num_malicious_clients)

    # Lane key streams, identical to the sequential driver's.
    keys = jax.vmap(lambda s: jax.random.PRNGKey(s))(jnp.asarray(seeds))
    init_keys, carry = jnp.moveaxis(jax.vmap(jax.random.split)(keys), 1, 0)

    states = jax.vmap(fr.init, in_axes=(0, None))(init_keys, base.num_clients)

    # Comm subsystem: codec byte accounting is static shared config —
    # stamped host-side into every lane's rows, exactly like the
    # sequential driver (fedavg._fill_round_metrics).
    comm_row = {}
    if fr.codec is not None:
        from blades_tpu.utils.tree import tree_size

        d_model = tree_size(states.server.params) // L  # per-lane width
        comm_row = fr.codec.round_metrics(base.num_clients, d_model)
        # Aggregation-domain provenance (ISSUE 11), mirroring the
        # sequential driver's stamps so f32/wire rows stay separable
        # across execution modes.
        comm_row["agg_domain"] = getattr(fr, "agg_domain", "f32")
        comm_row["agg_domain_bits"] = (fr.codec.storage_bits
                                       if comm_row["agg_domain"] == "wire"
                                       else 32)
    if fr.packing is not None:
        # Lane-packing provenance (parallel/packed.py): static shared
        # config, stamped into every laned row like the codec accounting.
        comm_row = dict(comm_row)
        comm_row["pack_factor"] = int(fr.packing.pack)
        comm_row["packed_lanes"] = int(base.num_clients // fr.packing.pack)

    def lane_step(state, x, y, ln, mal, key, sc):
        return _apply_lane(fr, sc).step(state, x, y, ln, mal, key)

    def lane_eval(state, tx, ty, tln, sc):
        return _apply_lane(fr, sc).evaluate(state, tx, ty, tln)

    vstep = jax.vmap(lane_step, in_axes=(0, dax, dax, dax, None, 0, 0))
    veval = jax.vmap(lane_eval, in_axes=(0, dax, dax, dax, 0))
    donate_argnums = (0,) if donate else ()
    if program_key is not None:
        from blades_tpu.perf import cached_jit

        # The shared AOT cache: identical groups (same static config,
        # same lane count, same data geometry) reuse one executable.
        # The key rides the per-seed layout and resolved augment because
        # both change the traced program, not just argument values.
        full_key = tuple(program_key) + (tuple(sorted(ok)), per_seed_data,
                                         str(fr.task.spec.augment))
        step = cached_jit(vstep, key=("lane_step",) + full_key,
                          donate_argnums=donate_argnums)
        evaluate = cached_jit(veval, key=("lane_eval",) + full_key)
    else:
        step = jax.jit(vstep, donate_argnums=donate_argnums)
        evaluate = jax.jit(veval)

    interval = base.evaluation_interval
    results: List[List[Dict]] = [[] for _ in range(L)]
    last_eval: List[Dict] = [{} for _ in range(L)]
    for r in range(1, max_rounds + 1):
        round_keys, carry = jnp.moveaxis(jax.vmap(jax.random.split)(carry), 1, 0)
        # The first dispatch pays XLA compilation — same phase split as
        # the sequential driver, so lane-group traces read the same way.
        with tracer.span("round" if r > 1 else "compile", step=r,
                         lanes=L):
            states, metrics = step(states, x, y, ln, mal, round_keys, sc)
            ev = (evaluate(states, tx, ty, tln, sc)
                  if interval and r % interval == 0 else None)
        # The round's stacked lane metrics and its evaluation, where one
        # ran, come back in one device_get.
        with tracer.span("fetch"):
            metrics, ev = jax.device_get((metrics, ev))
        if ev is not None:
            last_eval = [
                {k: float(ev[k][i]) for k in ("test_loss", "test_acc",
                                              "test_acc_top3")}
                for i in range(L)
            ]
        for i in range(L):
            row = {
                "training_iteration": r,
                "train_loss": float(metrics["train_loss"][i]),
                "agg_norm": float(metrics["agg_norm"][i]),
                "update_norm_mean": float(metrics["update_norm_mean"][i]),
                "seed": int(seeds[i]),
            }
            row.update(comm_row)
            row.update({k: v for k, v in lane_overrides[i].items()
                        if k != "seed"})
            row.update(last_eval[i])
            results[i].append(row)
    return results


def run_seed_lanes(config, seeds: List[int], max_rounds: int) -> List[List[Dict]]:
    """Seed-only lanes (round-2 API): one trial per seed."""
    return run_lanes(config.copy, [{"seed": int(s)} for s in seeds], max_rounds)

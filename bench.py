"""Benchmark: FL rounds/sec at the 1000-client north-star scale.

Two measured workloads, one JSON line:

1. **ResNet-10 @ 1000 clients** (headline ``value``): the reference's
   canonical CIFAR-10 model (``global_model: resnet`` -> ``ResNet10()``,
   ref: blades/tuned_examples/fedavg_cifar10_resnet_noniid.yaml:16 +
   fllib/models/catalog.py:20-21), ALIE forging the Byzantine quarter,
   exact coordinate-wise Median — one full FL round = local train +
   attack + robust aggregate + server step, all on device via the
   single-chip streaming round (:mod:`blades_tpu.parallel.streamed`):
   bf16 update matrix, client-block vmapped training, and the fused
   pallas finish (forge + exact Median in ONE HBM pass,
   ops/pallas_round.py).
2. **ResNet-18 @ 768 clients** (the model BASELINE.json actually names):
   768 is the single-chip capacity limit under malicious-lane elision —
   the benign-compacted bf16 update matrix stores 576 rows = 12.9 GB;
   n=1000 (22.3 GB full) cannot exist on one chip and is the multi-chip
   d-sharded configuration (``parallel/dsharded.py``).  The JSON carries
   an explicit v5e-8 projection formula instead of pretending.

Plus one env-gated A/B block per subsystem (``_BLOCKS`` below), each on
by default and each run after the headline.

This is a measurement path, so it runs on a TPU or not at all: with no
TPU it prints one ``{"error": ...}`` line and exits non-zero — a CPU
timing is never written under the device metric's name.  A block that
raises is recorded as ``{"error": ...}`` under its key and makes the
exit code non-zero.  Every result names the device it ran on.

Honest reporting:
- ``value`` is measured rounds/sec with a concrete fetch from the final
  output inside the timed region.
- ``mfu`` uses XLA's own compiled-program FLOP count against the bf16
  peak of the device that ran (``PEAK_BF16_FLOPS``, keyed by
  ``device_kind``; an unknown device is an error).
- ``vs_baseline`` divides by an ESTIMATED reference throughput — the
  reference publishes no throughput numbers (BASELINE.md), so the
  denominator is derived from the reference's own envelope: ~1 round/s
  at 60 clients on one GPU (SURVEY.md §6: 2000 rounds = multi-hour
  budget), scaled by 1000/60 clients with PERFECT 4-GPU scaling (its
  "large" preset) -> 0.24 rounds/s.  The estimate and its provenance
  ride in the JSON.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 32
SHARD = 32
LOCAL_STEPS = 1          # ref: algorithm_config.py:63 default
D_CHUNK = 1 << 17

# Estimated reference throughput at n=1000 (see module docstring).
BASELINE_EST_ROUNDS_PER_SEC = 0.24
# Published bf16 peak per chip, keyed by ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


METRIC_NAME = ("fl_rounds_per_sec_1000clients_fedavg_alie_median_cifar10_"
               "resnet10")


def _error_json(stage: str, detail: str) -> dict:
    return {
        "metric": METRIC_NAME,
        "value": None,
        "unit": "rounds/s",
        "vs_baseline": None,
        "error": stage,
        "detail": detail[-800:],
    }


def _device() -> dict:
    """The device as JAX reports it — stamped into every result."""
    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def _peak_flops() -> float:
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no bf16 peak recorded for device_kind {kind!r}; add it to "
            "PEAK_BF16_FLOPS with its source")
    return PEAK_BF16_FLOPS[kind]


def _flops_per_client_round(fr, params) -> float:
    """XLA's own FLOP count for one client's local round."""
    opt0 = fr.task.init_client_opt_state(params)
    bx = jnp.zeros((LOCAL_STEPS, BATCH, 32, 32, 3), jnp.float32)
    by = jnp.zeros((LOCAL_STEPS, BATCH), jnp.int32)

    def one_client(params, opt, bx, by, key):
        return fr.task.local_round(params, opt, bx, by, key,
                                   jnp.array(False))

    cost = (
        jax.jit(one_client)
        .lower(params, opt0, bx, by, jax.random.PRNGKey(0))
        .compile()
        .cost_analysis()
    )
    return float(cost["flops"])


def bench_workload(model: str, num_clients: int, client_block: int,
                   timed_rounds: int) -> dict:
    """Run the FedAvg+ALIE+Median streamed round for one model/scale."""
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.parallel.streamed import streamed_step

    num_byzantine = num_clients // 4
    task = TaskSpec(model=model, input_shape=(32, 32, 3), num_classes=10,
                    lr=0.1, compute_dtype="bfloat16").build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients,
                        num_byzantine=num_byzantine)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=BATCH,
                  num_batches_per_round=LOCAL_STEPS)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, SHARD, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, SHARD)), jnp.int32)
    lengths = jnp.full((num_clients,), SHARD, jnp.int32)
    mal = make_malicious_mask(num_clients, num_byzantine)

    state = fr.init(jax.random.PRNGKey(0), num_clients)
    # malicious_prefix: ALIE's forged rows are computed from benign
    # statistics and REPLACE whatever the byzantine quarter trains — so
    # their local training is dead computation and the round skips it
    # (exact same round output; see streamed_step's docstring).
    step = streamed_step(fr, client_block=client_block, d_chunk=D_CHUNK,
                         malicious_prefix=num_byzantine)
    d = sum(p.size for p in jax.tree.leaves(state.server.params))

    # This benchmark's capacity claims assume the benign-COMPACTED
    # matrix (the n=768 ResNet-18 config only fits HBM that way).
    # Verify the gate that streamed_step will apply actually engages —
    # a silent fallback to the full matrix would OOM r18 and misreport
    # the stored size.
    from blades_tpu.ops.pallas_select import kernel_applicable

    compacted = kernel_applicable(num_clients - num_byzantine, d)
    if not compacted:
        raise RuntimeError(
            "benign-compacted streamed path not engaged (non-TPU backend "
            "or BLADES_TPU_NO_PALLAS=1?) — this benchmark's configs "
            "assume it; run on TPU with the pallas kernels enabled"
        )

    flops_client = _flops_per_client_round(fr, state.server.params)
    # Two MFU bases: "executed" counts only the benign training that
    # actually runs (the byzantine quarter is elided: dead under the
    # ALIE forge, round output bit-equal); "all_lanes" counts all n
    # clients.
    flops_per_round = (num_clients - num_byzantine) * flops_client
    flops_all_lanes = num_clients * flops_client

    # Warmup / compile.
    state, m = step(state, x, y, lengths, mal, jax.random.PRNGKey(1))
    _ = float(m["train_loss"])

    t0 = time.perf_counter()
    for r in range(timed_rounds):
        state, metrics = step(state, x, y, lengths, mal,
                              jax.random.fold_in(jax.random.PRNGKey(2), r))
    # Fetch a concrete value from the final round: forces the whole chain.
    final_loss = float(metrics["train_loss"])
    assert final_loss == final_loss  # NaN guard
    dt = time.perf_counter() - t0

    rounds_per_sec = timed_rounds / dt
    peak = _peak_flops()
    mfu_exec = round(rounds_per_sec * flops_per_round / peak, 4)
    return {
        "rounds_per_sec": round(rounds_per_sec, 3),
        "mfu": mfu_exec,
        "mfu_executed": mfu_exec,
        "mfu_all_lanes": round(
            rounds_per_sec * flops_all_lanes / peak, 4),
        "flops_per_round": flops_per_round,
        "flops_per_round_all_lanes": flops_all_lanes,
        "clients": num_clients,
        "byzantine": num_byzantine,
        "model": model,
        "params": d,
        # STORED matrix: benign rows only (elision compacts the
        # byzantine quarter away).
        "update_matrix_gb": round((num_clients - num_byzantine) * d * 2 / 1e9,
                                  1),
        "malicious_training": "elided (ALIE replaces forged rows from "
                              "benign stats; see streamed_step docstring)",
    }


def _measure_dense_cnn(pack: int | None, timed_rounds: int = 3) -> dict:
    """The fixed 32-client dense CNN protocol (FedAvg + ALIE forge +
    exact Median), optionally under client lane-packing
    (``parallel/packed.py``).

    Reports BOTH MFU bases:
    ``mfu_executed`` uses XLA's compiled FLOP count of the ACTUAL round
    program that ran (the packed program's grouped kernels included),
    ``mfu_all_lanes`` the analytic ``n x per-client`` basis every earlier
    round used.  Packed runs additionally stamp ``pack_factor`` /
    ``packed_lanes``, mirroring the round-metrics schema fields.
    """
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec

    num_clients, num_byzantine = 32, 8
    task = TaskSpec(model="cnn", input_shape=(32, 32, 3), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients,
                        num_byzantine=num_byzantine)
    packing = None
    if pack:
        from blades_tpu.parallel.packed import ClientPacking

        packing = ClientPacking(pack=pack)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=BATCH,
                  num_batches_per_round=LOCAL_STEPS, packing=packing)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, SHARD, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, SHARD)), jnp.int32)
    lengths = jnp.full((num_clients,), SHARD, jnp.int32)
    mal = make_malicious_mask(num_clients, num_byzantine)
    state = fr.init(jax.random.PRNGKey(0), num_clients)
    step = jax.jit(fr.step, donate_argnums=(0,))

    # ONE compile: the AOT executable both yields the executed-FLOP
    # count and runs the timed loop — re-dispatching through the jit
    # wrapper would not hit its cache (lower/compile bypasses it).
    run = step.lower(state, x, y, lengths, mal,
                     jax.random.PRNGKey(1)).compile()
    flops_round = float(run.cost_analysis()["flops"])
    flops_all_lanes = num_clients * _flops_per_client_round(
        fr, state.server.params)
    peak = _peak_flops()

    state, m = run(state, x, y, lengths, mal, jax.random.PRNGKey(1))
    _ = float(m["train_loss"])  # compile + settle
    t0 = time.perf_counter()
    for r in range(timed_rounds):
        state, metrics = run(state, x, y, lengths, mal,
                             jax.random.fold_in(jax.random.PRNGKey(2), r))
    final_loss = float(metrics["train_loss"])
    assert final_loss == final_loss  # NaN guard
    dt = time.perf_counter() - t0
    rps = timed_rounds / dt
    d = sum(p.size for p in jax.tree.leaves(state.server.params))
    out = {
        "rounds_per_sec": round(rps, 4),
        "clients": num_clients, "byzantine": num_byzantine,
        "model": "cnn", "params": d, "batch": BATCH,
        "local_steps": LOCAL_STEPS, "timed_rounds": timed_rounds,
        "aggregator": "Median", "adversary": "ALIE",
        "path": "dense_packed" if pack else "dense",
        "mfu_executed": round(rps * flops_round / peak, 4),
        "mfu_all_lanes": round(rps * flops_all_lanes / peak, 4),
        "flops_per_round_executed": flops_round,
        "flops_per_round_all_lanes": flops_all_lanes,
    }
    if pack:
        out["pack_factor"] = pack
        out["packed_lanes"] = num_clients // pack
    return out


def _packed_cnn_block() -> dict:
    """Satellite measurement: the 32-client CNN protocol unpacked vs
    lane-packed (pack_factor=2 — two 64-channel clients per 128-lane
    vreg), same rounds/keys, speedup reported.  Exact math (grouped
    kernels are the per-client kernels reassociated), so the two runs
    are the same experiment at two arithmetic intensities."""
    unpacked = _measure_dense_cnn(pack=None)
    packed = _measure_dense_cnn(pack=2)
    speedup = None
    if unpacked["rounds_per_sec"]:
        speedup = round(packed["rounds_per_sec"]
                        / unpacked["rounds_per_sec"], 3)
    return {"unpacked": unpacked, "packed": packed,
            "packed_speedup": speedup}


def _measure_rowgeom_round(aggregator: str, fused: bool | None, *, model,
                           input_shape, num_clients, num_byzantine,
                           client_block, d_chunk, timed_rounds) -> dict:
    """One streamed row-geometry configuration (FedAvg + ALIE forge +
    ``aggregator``), measured end to end.  ``fused`` toggles the pass
    planner's fusion (``streamed_step(fuse_rowgeom=...)``); ``None``
    runs the Mean-aggregator baseline of the SAME protocol, whose
    trivial finish isolates the training cost so the A/B's finish
    wall-time can be derived as ``round_s - baseline_round_s``."""
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.parallel.streamed import streamed_step

    task = TaskSpec(model=model, input_shape=input_shape, num_classes=10,
                    lr=0.1).build()
    agg_name = "Mean" if fused is None else aggregator
    server = Server.from_config(aggregator=agg_name,
                                num_byzantine=num_byzantine, lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients,
                        num_byzantine=num_byzantine)
    fr = FedRound(task=task, server=server, adversary=adv,
                  batch_size=min(BATCH, 8),
                  num_batches_per_round=LOCAL_STEPS)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, 8, *input_shape)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, 8)), jnp.int32)
    lengths = jnp.full((num_clients,), 8, jnp.int32)
    mal = make_malicious_mask(num_clients, num_byzantine)
    step = streamed_step(fr, client_block=client_block, d_chunk=d_chunk,
                         fuse_rowgeom=True if fused is None else fused)
    state = fr.init(jax.random.PRNGKey(0), num_clients)
    state, m = step(state, x, y, lengths, mal, jax.random.PRNGKey(1))
    _ = float(m["train_loss"])  # compile + settle
    t0 = time.perf_counter()
    for r in range(timed_rounds):
        state, m = step(state, x, y, lengths, mal,
                        jax.random.fold_in(jax.random.PRNGKey(2), r))
    final_loss = float(m["train_loss"])
    assert final_loss == final_loss  # NaN guard
    dt = time.perf_counter() - t0
    out = {
        "aggregator": agg_name,
        "round_s": round(dt / timed_rounds, 4),
        "rounds_per_sec": round(timed_rounds / dt, 4),
        "clients": num_clients, "byzantine": num_byzantine, "model": model,
        "timed_rounds": timed_rounds,
    }
    if fused is not None:
        out["fused"] = fused
        # Planned full-matrix traversals per finish, stamped by the round
        # (obs schema fields hbm_passes / hbm_passes_unfused).
        out["hbm_passes"] = int(m["hbm_passes"])
        out["hbm_passes_unfused"] = int(m["hbm_passes_unfused"])
    return out


def _rowgeom_block() -> dict:
    """BLADES_BENCH_ROWGEOM satellite: the Multikrum/GeoMed streamed
    fused-vs-unfused A/B (ISSUE 9).  Per aggregator: planned finish pass
    counts (``hbm_passes``), round wall-times for both plans, and the
    finish wall-time derived against a Mean-baseline round of the same
    protocol (identical training, trivial finish)."""
    cfg = dict(model="resnet10", input_shape=(32, 32, 3),
               num_clients=200, num_byzantine=50, client_block=50,
               d_chunk=D_CHUNK, timed_rounds=2)
    base = _measure_rowgeom_round("Mean", None, **cfg)
    out = {"baseline_mean": base}
    for agg in ("Multikrum", "GeoMed"):
        fused = _measure_rowgeom_round(agg, True, **cfg)
        unfused = _measure_rowgeom_round(agg, False, **cfg)
        finish_f = max(fused["round_s"] - base["round_s"], 0.0)
        finish_u = max(unfused["round_s"] - base["round_s"], 0.0)
        out[agg.lower()] = {
            "fused": fused,
            "unfused": unfused,
            "finish_s_fused": round(finish_f, 4),
            "finish_s_unfused": round(finish_u, 4),
            "finish_speedup": (round(finish_u / finish_f, 3)
                               if finish_f > 0 else None),
        }
    return out


def _measure_quantagg_round(domain: str, aggregator: str, *, model,
                            input_shape, num_clients, num_byzantine,
                            timed_rounds) -> dict:
    """One aggregation-domain arm of the QUANTAGG A/B: the dense
    protocol (FedAvg + ALIE forge + ``aggregator``) under the int8
    quant codec, aggregating either decode-then-f32 (``domain="f32"``)
    or in the packed wire domain (``domain="wire"`` —
    ``Server.step_wire``).  Wire rounds additionally report the
    planner's traversal counts and the per-round HBM byte estimate of
    the defense-statistics traversals — ``hbm_passes * n * d *
    bytes/elem``, the exact loop the wire domain shrinks — against the
    SAME statistics at 4 bytes/elem (the f32 arm's dense aggregators
    run one XLA program, so the planner's pass count is the
    apples-to-apples traversal basis).  The rows that DO decode
    (selected slices, coordinate-wise outputs, the forge's sanctioned
    full read — f32-domain rounds touch those same f32 rows, they just
    never had a counter) ride separately as ``dequant_bytes_est``."""
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.comm.codecs import CodecConfig
    from blades_tpu.core import FedRound, Server, TaskSpec

    task = TaskSpec(model=model, input_shape=input_shape, num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator=aggregator,
                                num_byzantine=num_byzantine, lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients,
                        num_byzantine=num_byzantine)
    fr = FedRound(task=task, server=server, adversary=adv,
                  batch_size=min(BATCH, 8),
                  num_batches_per_round=LOCAL_STEPS,
                  codec=CodecConfig(name="quant", bits=8),
                  agg_domain=domain)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, 8, *input_shape)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, 8)), jnp.int32)
    lengths = jnp.full((num_clients,), 8, jnp.int32)
    mal = make_malicious_mask(num_clients, num_byzantine)
    state = fr.init(jax.random.PRNGKey(0), num_clients)
    step = jax.jit(fr.step, donate_argnums=(0,))
    state, m = step(state, x, y, lengths, mal, jax.random.PRNGKey(1))
    _ = float(m["train_loss"])  # compile + settle
    t0 = time.perf_counter()
    for r in range(timed_rounds):
        state, m = step(state, x, y, lengths, mal,
                        jax.random.fold_in(jax.random.PRNGKey(2), r))
    final_loss = float(m["train_loss"])
    assert final_loss == final_loss  # NaN guard
    dt = time.perf_counter() - t0
    d = sum(p.size for p in jax.tree.leaves(state.server.params))
    out = {
        "agg_domain": domain, "aggregator": aggregator,
        "round_s": round(dt / timed_rounds, 4),
        "rounds_per_sec": round(timed_rounds / dt, 4),
        "clients": num_clients, "byzantine": num_byzantine,
        "model": model, "params": d, "codec": "quant-int8",
        "timed_rounds": timed_rounds,
    }
    if domain == "wire":
        passes = int(m["hbm_passes"])
        dequant = int(m["dequant_rows"])
        out["hbm_passes"] = passes
        out["hbm_passes_unfused"] = int(m["hbm_passes_unfused"])
        out["dequant_rows"] = dequant
        out["agg_domain_bits"] = 8
        out["agg_hbm_bytes_est"] = passes * num_clients * d * 1
        # The same statistics traversed as dense f32 — the f32 arm's
        # apples-to-apples estimate, stamped here so the block can
        # report the reduction without re-deriving pass counts.
        out["agg_hbm_bytes_est_f32"] = passes * num_clients * d * 4
        out["dequant_bytes_est"] = dequant * d * 4
    return out


def _quantagg_block() -> dict:
    """BLADES_BENCH_QUANTAGG satellite (ISSUE 11): f32-domain vs
    wire-domain aggregation under the int8 quant codec on the dense
    protocol — Median (the bench's coordinate-wise finish, exact in
    either domain) and Multikrum (Gram geometry: the statistics that
    ride the MXU's int8 path on kernel-eligible shapes).  Alongside
    wall-times, each wire arm stamps the per-round HBM byte estimate of
    the defense statistics vs the f32 equivalent (the acceptance's
    >= ~2x reduction surfaces as ``agg_hbm_reduction``)."""
    cfg = dict(model="cnn", input_shape=(32, 32, 3), num_clients=32,
               num_byzantine=8, timed_rounds=3)
    out = {}
    for agg in ("Median", "Multikrum"):
        f32 = _measure_quantagg_round("f32", agg, **cfg)
        wire = _measure_quantagg_round("wire", agg, **cfg)
        reduction = None
        if wire.get("agg_hbm_bytes_est"):
            reduction = round(wire["agg_hbm_bytes_est_f32"]
                              / wire["agg_hbm_bytes_est"], 3)
        speedup = None
        if f32["rounds_per_sec"]:
            speedup = round(wire["rounds_per_sec"] / f32["rounds_per_sec"],
                            3)
        out[agg.lower()] = {
            "f32": f32, "wire": wire,
            "agg_hbm_reduction": reduction,
            "wire_speedup": speedup,
        }
    return out


def _measure_traced_cnn(traced: bool, *, num_clients=32, timed_rounds=4,
                        model="cnn", input_shape=(32, 32, 3)) -> dict:
    """One arm of the BLADES_BENCH_TRACE A/B: the 32-client dense CNN
    protocol (FedAvg + ALIE forge + exact Median) with the driver-style
    per-round fetch, either bare or under the FULL observability layer
    — armed span tracer (round spans + jax profiler annotations),
    armed watchdog observing every fetched row, flight recorder
    recording every row.  BOTH arms fetch the round scalars each round
    (exactly what the sweep driver does), so the delta is the tracing/
    watchdog overhead alone — the watchdog's zero-extra-device-syncs
    contract measured, not asserted."""
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.obs.flightrec import FlightRecorder
    from blades_tpu.obs.trace import Tracer
    from blades_tpu.obs.watchdog import Watchdog

    num_byzantine = num_clients // 4
    task = TaskSpec(model=model, input_shape=input_shape, num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients,
                        num_byzantine=num_byzantine)
    fr = FedRound(task=task, server=server, adversary=adv,
                  batch_size=min(BATCH, 8),
                  num_batches_per_round=LOCAL_STEPS)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, 8, *input_shape)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, 8)), jnp.int32)
    lengths = jnp.full((num_clients,), 8, jnp.int32)
    mal = make_malicious_mask(num_clients, num_byzantine)
    state = fr.init(jax.random.PRNGKey(0), num_clients)
    step = jax.jit(fr.step, donate_argnums=(0,))

    tracer = Tracer(record=True) if traced else None
    wd = Watchdog() if traced else None
    import tempfile

    flightrec = (FlightRecorder(
        os.path.join(tempfile.mkdtemp(prefix="blades_trace_ab_"),
                     "flightrec.json"),
        capacity=8, trial="bench_trace_ab", algo="FEDAVG")
        if traced else None)

    def one_round(r, key):
        nonlocal state
        state, m = step(state, x, y, lengths, mal, key)
        # Driver-style per-round fetch: BOTH arms pay this sync.
        row = {
            "training_iteration": r + 1,
            "train_loss": float(m["train_loss"]),
            "agg_norm": float(m["agg_norm"]),
            "update_norm_mean": float(m["update_norm_mean"]),
        }
        if traced:
            events = wd.observe(row)
            flightrec.record(row)
            if events or flightrec.check(row):
                flightrec.dump({"kind": "watchdog", "round": r + 1})
        return row

    # Warmup / compile outside the timed loop.
    if traced:
        with tracer.span("compile", step=0):
            row = one_round(-1, jax.random.PRNGKey(1))
    else:
        row = one_round(-1, jax.random.PRNGKey(1))
    t0 = time.perf_counter()
    for r in range(timed_rounds):
        key = jax.random.fold_in(jax.random.PRNGKey(2), r)
        if traced:
            with tracer.span("round", step=r + 1):
                row = one_round(r, key)
        else:
            row = one_round(r, key)
    dt = time.perf_counter() - t0
    assert row["train_loss"] == row["train_loss"]  # NaN guard
    out = {
        "rounds_per_sec": round(timed_rounds / dt, 4),
        "round_s": round(dt / timed_rounds, 4),
        "clients": num_clients, "byzantine": num_byzantine,
        "model": model, "timed_rounds": timed_rounds,
        "aggregator": "Median", "adversary": "ALIE",
        "traced": traced,
    }
    if traced:
        out["watchdog_events"] = len(wd.events)
        out["round_spans"] = int(
            tracer.summary().get("round", {}).get("count", 0))
    return out


def _trace_block() -> dict:
    """BLADES_BENCH_TRACE satellite (ISSUE 12): round wall-time with the
    observability layer fully armed (span tracer + watchdog + flight
    recorder) vs bare, on the 32-client dense CNN protocol — the
    acceptance is overhead < 2% with the watchdog armed."""
    kw = dict(model="cnn", input_shape=(32, 32, 3), num_clients=32,
              timed_rounds=5)
    bare = _measure_traced_cnn(False, **kw)
    traced = _measure_traced_cnn(True, **kw)
    overhead_pct = None
    if traced["rounds_per_sec"]:
        overhead_pct = round(
            (bare["rounds_per_sec"] / traced["rounds_per_sec"] - 1.0)
            * 100.0, 3)
    return {
        "bare": bare,
        "traced": traced,
        "overhead_pct": overhead_pct,
        "acceptance": "overhead < 2% with the watchdog armed",
        "acceptance_met": (overhead_pct is not None
                           and overhead_pct < 2.0),
    }


def _measure_ledger_cnn(armed: bool, *, num_clients=32, timed_rounds=4,
                        model="cnn", input_shape=(32, 32, 3)) -> dict:
    """One arm of the BLADES_BENCH_LEDGER A/B: the 32-client dense CNN
    protocol with the driver-style per-round fetch, either bare or with
    the client ledger armed — observe() folding the full cohort every
    round (participation, flag churn, score EWMA, norm Welford) plus
    the round_fields() fleet stamp.  BOTH arms pay the identical device
    work and row fetch; the diagnosis columns the armed arm feeds the
    ledger are host-synthesized (deterministic rng), so the delta is
    the ledger's pure host cost — its zero-extra-device-syncs contract
    measured, not asserted."""
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.obs.ledger import make_ledger

    num_byzantine = num_clients // 4
    task = TaskSpec(model=model, input_shape=input_shape, num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=num_clients,
                        num_byzantine=num_byzantine)
    fr = FedRound(task=task, server=server, adversary=adv,
                  batch_size=min(BATCH, 8),
                  num_batches_per_round=LOCAL_STEPS)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, 8, *input_shape)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, 8)), jnp.int32)
    lengths = jnp.full((num_clients,), 8, jnp.int32)
    mal = make_malicious_mask(num_clients, num_byzantine)
    state = fr.init(jax.random.PRNGKey(0), num_clients)
    step = jax.jit(fr.step, donate_argnums=(0,))

    ledger = make_ledger("resident", num_clients) if armed else None
    ids = np.arange(num_clients, dtype=np.int64)
    diag_rng = np.random.default_rng(7)

    def one_round(r, key):
        nonlocal state
        state, m = step(state, x, y, lengths, mal, key)
        # Driver-style per-round fetch: BOTH arms pay this sync.
        row = {
            "training_iteration": r + 1,
            "train_loss": float(m["train_loss"]),
            "agg_norm": float(m["agg_norm"]),
            "update_norm_mean": float(m["update_norm_mean"]),
        }
        if armed:
            scores = diag_rng.normal(size=num_clients)
            ledger.observe(ids, round=r + 1, flagged=scores > 1.0,
                           scores=scores,
                           norms=np.abs(diag_rng.normal(size=num_clients)))
            row.update(ledger.round_fields())
        return row

    row = one_round(-1, jax.random.PRNGKey(1))  # warmup / compile
    t0 = time.perf_counter()
    for r in range(timed_rounds):
        key = jax.random.fold_in(jax.random.PRNGKey(2), r)
        row = one_round(r, key)
    dt = time.perf_counter() - t0
    assert row["train_loss"] == row["train_loss"]  # NaN guard
    out = {
        "rounds_per_sec": round(timed_rounds / dt, 4),
        "round_s": round(dt / timed_rounds, 4),
        "clients": num_clients, "byzantine": num_byzantine,
        "model": model, "timed_rounds": timed_rounds,
        "aggregator": "Median", "adversary": "ALIE",
        "armed": armed,
    }
    if armed:
        out["ledger_clients_seen"] = row["ledger_clients_seen"]
        out["suspected_fraction"] = row["suspected_fraction"]
    return out


def _ledger_block() -> dict:
    """BLADES_BENCH_LEDGER satellite (ISSUE 16): round wall-time with
    the client-lifetime ledger armed (full-cohort observe + fleet
    round_fields each round) vs bare, on the 32-client dense CNN
    protocol — held to the same <2% acceptance bar as the PR 12
    observability layer."""
    kw = dict(model="cnn", input_shape=(32, 32, 3), num_clients=32,
              timed_rounds=5)
    bare = _measure_ledger_cnn(False, **kw)
    armed = _measure_ledger_cnn(True, **kw)
    overhead_pct = None
    if armed["rounds_per_sec"]:
        overhead_pct = round(
            (bare["rounds_per_sec"] / armed["rounds_per_sec"] - 1.0)
            * 100.0, 3)
    return {
        "bare": bare,
        "armed": armed,
        "overhead_pct": overhead_pct,
        "acceptance": "overhead < 2% with the ledger armed",
        "acceptance_met": (overhead_pct is not None
                           and overhead_pct < 2.0),
    }


def _measure_autotuned(tuned: bool, plan_cache_dir: str, *, num_clients,
                       model, dataset, input_shape, timed_rounds) -> dict:
    """One config-driven run of the bench protocol through the FULL
    driver (``FedavgConfig.build()`` — the layer the autotuner lives
    in), default knobs vs ``autotune=True`` (the numerics-preserving
    tier, so both runs compute the identical trajectory).  Tuned runs
    additionally report the selected plan and its provenance."""
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset=dataset, num_clients=num_clients, seed=0)
        .training(global_model=model, server_lr=0.5, train_batch_size=8,
                  aggregator={"type": "Median"},
                  input_shape=input_shape)
        .client(lr=0.1)
        .adversary(num_malicious_clients=num_clients // 4,
                   adversary_config={"type": "ALIE"})
        .evaluation(evaluation_interval=0)
    )
    if tuned:
        cfg.resources(autotune=True, autotune_cache_dir=plan_cache_dir)
    algo = cfg.build()
    algo.train()  # compile + settle
    t0 = time.perf_counter()
    for _ in range(timed_rounds):
        m = algo.train()
    assert float(m["train_loss"]) == float(m["train_loss"])  # NaN guard
    dt = time.perf_counter() - t0
    out = {
        "round_s": round(dt / timed_rounds, 4),
        "rounds_per_sec": round(timed_rounds / dt, 4),
        "clients": num_clients, "model": model,
        "timed_rounds": timed_rounds, "tuned": tuned,
    }
    if tuned and algo.plan is not None:
        prov = algo.plan_summary or {}
        out["plan_id"] = algo.plan.plan_id
        out["plan"] = algo.plan.as_dict()
        out["selection"] = {
            "mode": prov.get("mode"),
            "timed": bool(prov.get("timed")),
            "cache_hit": bool(prov.get("cache_hit")),
            "candidates": prov.get("candidates"),
            "truncated": prov.get("truncated", 0),
        }
    return out


def _autotune_block() -> dict:
    """BLADES_BENCH_AUTOTUNE satellite: tuned-vs-default A/B through the
    driver (ISSUE 10).  Both arms run the default (numerics-preserving)
    tier, so the trajectories are bit-identical and the delta is pure
    execution-plan effect; the candidates are wall-clock measured."""
    import tempfile

    kw = dict(num_clients=64, model="cnn", dataset="cifar10",
              input_shape=None, timed_rounds=3)
    with tempfile.TemporaryDirectory(prefix="blades_plan_cache_") as pdir:
        default = _measure_autotuned(False, pdir, **kw)
        tuned = _measure_autotuned(True, pdir, **kw)
    speedup = None
    if default["rounds_per_sec"]:
        speedup = round(tuned["rounds_per_sec"]
                        / default["rounds_per_sec"], 3)
    return {"default": default, "tuned": tuned,
            "tuned_speedup": speedup}


def _measure_async_cnn(*, num_clients=32, num_byzantine=8, agg_every=16,
                       rate=0.25, timed_cycles=3,
                       aggregator="Median") -> dict:
    """BLADES_BENCH_ASYNC satellite (ISSUE 14): the 32-client CNN
    protocol under buffered-async execution
    (blades_tpu/arrivals): a deterministic Poisson arrival process
    drives continuous update traffic, Lazy free-riders ride the
    Byzantine quarter, and the server fires a staleness-weighted
    ``aggregator`` every ``agg_every`` buffered arrivals.  Reports the
    ingest metric — ``updates_per_sec`` — NEXT TO ``rounds_per_sec``
    (one "round" = one aggregation cycle), which is the number that
    matters when clients arrive on their own clocks instead of cohorts.
    """
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.arrivals import AsyncEngine, AsyncSpec
    from blades_tpu.core import FedRound, Server, TaskSpec

    task = TaskSpec(model="cnn", input_shape=(32, 32, 3), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator=aggregator, lr=0.5)
    adv = get_adversary("Lazy", mode="copy")
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=BATCH,
                  num_batches_per_round=LOCAL_STEPS)
    spec = AsyncSpec(seed=0, rate=rate, agg_every=agg_every,
                     staleness_cap=8, weight_schedule="polynomial")
    engine = AsyncEngine(fr, spec, num_clients, train_seed=0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(num_clients, SHARD, 32, 32, 3)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(num_clients, SHARD)), jnp.int32)
    lengths = jnp.full((num_clients,), SHARD, jnp.int32)
    mal = np.asarray(make_malicious_mask(num_clients, num_byzantine))
    state = fr.init(jax.random.PRNGKey(0), num_clients)
    import dataclasses as _dc

    state = _dc.replace(
        state, arrivals=engine.init_history(state.server.params))

    # Compile + settle one cycle outside the timed window.
    state, m = engine.run_cycle(state, (x, y, lengths), mal)
    _ = float(m["train_loss"])
    t0 = time.perf_counter()
    for _i in range(timed_cycles):
        state, metrics = engine.run_cycle(state, (x, y, lengths), mal)
    final_loss = float(metrics["train_loss"])
    assert final_loss == final_loss  # NaN guard
    dt = time.perf_counter() - t0
    info = engine.last_info
    return {
        "rounds_per_sec": round(timed_cycles / dt, 4),
        "updates_per_sec": round(timed_cycles * agg_every / dt, 3),
        "clients": num_clients, "byzantine": num_byzantine,
        "model": "cnn", "batch": BATCH, "local_steps": LOCAL_STEPS,
        "timed_cycles": timed_cycles, "aggregator": aggregator,
        "adversary": "Lazy(copy)", "path": "async_buffered",
        "arrival_rate": rate, "agg_every": agg_every,
        "staleness_cap": spec.staleness_cap,
        "weight_schedule": spec.weight_schedule,
        "final_tick": info["tick"],
        "staleness_mean": info["staleness_mean"],
        "staleness_max": info["staleness_max"],
        "buffer_overflow": info["buffer_overflow"],
    }


def _measure_mesh_arm(hier: bool, *, num_clients, model, input_shape,
                      dataset, timed_rounds, n_devices,
                      mesh_shape=None) -> dict:
    """One arm of the BLADES_BENCH_MESH A/B (ISSUE 18) through the FULL
    driver: the flat GSPMD mesh round (``num_devices`` alone) vs the
    hierarchical pod-scale round (``execution='hier'`` on a 2-D
    ``(clients, d)`` mesh — per-chip pre-aggregation, ring gather of
    representatives).  With the default ``bucket_size=1`` the hier arm
    is bit-identical to the single-chip dense trajectory (the tier-1
    pinned contract); vs the flat GSPMD arm the losses agree only to
    float32 reduction-order tolerance.  The hier arm additionally
    stamps its trace-time ``ici_bytes``."""
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset=dataset, num_clients=num_clients, seed=0)
        .training(global_model=model, server_lr=0.5,
                  train_batch_size=BATCH,
                  num_batch_per_round=LOCAL_STEPS,
                  aggregator={"type": "Median"},
                  input_shape=input_shape)
        .client(lr=0.1)
        .adversary(num_malicious_clients=num_clients // 4,
                   adversary_config={"type": "ALIE"})
        .evaluation(evaluation_interval=0)
    )
    res = dict(num_devices=n_devices)
    if hier:
        res.update(execution="hier", mesh_shape=mesh_shape)
    cfg.resources(**res)
    algo = cfg.build()
    try:
        row = algo.train()  # compile + settle outside the timed loop
        t0 = time.perf_counter()
        for _ in range(timed_rounds):
            row = algo.train()
        dt = time.perf_counter() - t0
        final_loss = float(row["train_loss"])
        assert final_loss == final_loss  # NaN guard
        out = {
            "rounds_per_sec": round(timed_rounds / dt, 4),
            "round_s": round(dt / timed_rounds, 4),
            "clients": num_clients, "model": model,
            "batch": BATCH, "local_steps": LOCAL_STEPS,
            "timed_rounds": timed_rounds, "aggregator": "Median",
            "adversary": "ALIE", "n_devices": n_devices,
            "path": "hier" if hier else "flat_gspmd",
            "final_loss": final_loss,
        }
        if hier:
            out["mesh_shape"] = row.get("mesh_shape")
            out["ici_bytes"] = row.get("ici_bytes")
            out["preagg_kept"] = row.get("preagg_kept")
        return out
    finally:
        algo.stop()


def _mesh_block() -> dict:
    """BLADES_BENCH_MESH satellite (ISSUE 18): hierarchical-vs-flat
    mesh A/B on the chips that exist, arranged as a ``(n/2, 2)`` torus.
    bucket_size=1 pins hier to the dense trajectory, so the wall-time
    delta is the collective schedule and the stamped ``ici_bytes`` is
    the wire cost the hierarchy actually paid; the two arms' losses are
    cross-checked to reduction-order tolerance."""
    n_dev = len(jax.devices())
    if n_dev < 4 or n_dev % 2:
        return {"skipped": f"needs an even number >= 4 of chips, "
                           f"have {n_dev}"}
    kw = dict(num_clients=64, model="cnn", dataset="cifar10",
              input_shape=None, timed_rounds=3, n_devices=n_dev)
    flat = _measure_mesh_arm(False, **kw)
    hier = _measure_mesh_arm(True, mesh_shape=(n_dev // 2, 2), **kw)
    out = {"flat": flat, "hier": hier}
    if flat["rounds_per_sec"]:
        out["hier_over_flat"] = round(
            hier["rounds_per_sec"] / flat["rounds_per_sec"], 3)
    if flat.get("final_loss") is not None:
        # bucket_size=1 pins hier to the dense trajectory; the flat
        # GSPMD arm differs only by float32 reduction order, so the
        # delta is a cheap sanity stamp, not an identity claim.
        delta = abs(hier["final_loss"] - flat["final_loss"])
        out["loss_delta"] = delta
        out["loss_agree_1e4"] = delta < 1e-4
    return out


def _measure_gossip_arm(graph, *, num_clients, model, input_shape,
                        dataset, timed_rounds, n_devices) -> dict:
    """One arm of the BLADES_BENCH_GOSSIP A/B (ISSUE 19) through the
    FULL driver: ``graph=None`` runs the centralized dense round
    (single-server baseline), a graph name runs the decentralized
    gossip round (``execution='gossip'``) over that peer topology —
    per-node local training, neighborhood exchange, per-node robust
    aggregation, doubly-stochastic mixing.  The gossip arms stamp the
    trace-time ``gossip_ici_bytes`` and graph provenance next to the
    wall time."""
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset=dataset, num_clients=num_clients, seed=0)
        .training(global_model=model, server_lr=0.5,
                  train_batch_size=BATCH,
                  num_batch_per_round=LOCAL_STEPS,
                  aggregator={"type": "Median"},
                  input_shape=input_shape)
        .client(lr=0.1)
        .adversary(num_malicious_clients=num_clients // 4,
                   adversary_config={"type": "ALIE"})
        .evaluation(evaluation_interval=0)
    )
    if graph is None:
        cfg.resources(num_devices=n_devices)
    else:
        cfg.resources(num_devices=n_devices, execution="gossip")
        cfg.topology(graph=graph, k=4)
    algo = cfg.build()
    try:
        row = algo.train()  # compile + settle outside the timed loop
        t0 = time.perf_counter()
        for _ in range(timed_rounds):
            row = algo.train()
        dt = time.perf_counter() - t0
        final_loss = float(row["train_loss"])
        assert final_loss == final_loss  # NaN guard
        out = {
            "rounds_per_sec": round(timed_rounds / dt, 4),
            "round_s": round(dt / timed_rounds, 4),
            "clients": num_clients, "model": model,
            "batch": BATCH, "local_steps": LOCAL_STEPS,
            "timed_rounds": timed_rounds, "aggregator": "Median",
            "adversary": "ALIE", "n_devices": n_devices,
            "path": "centralized" if graph is None else f"gossip_{graph}",
            "final_loss": final_loss,
        }
        if graph is not None:
            out["gossip_ici_bytes"] = row.get("gossip_ici_bytes")
            out["topology"] = row.get("topology")
            out["spectral_gap"] = row.get("spectral_gap")
            out["consensus_dist"] = row.get("consensus_dist")
        return out
    finally:
        algo.stop()


def _gossip_block() -> dict:
    """BLADES_BENCH_GOSSIP satellite (ISSUE 19): decentralized-vs-
    centralized A/B on the chips that exist — the 32-client Median
    protocol run centralized (dense single-server round), over a ring
    (diameter n/2, cheapest wire), and over a 4-regular graph (denser
    mixing).  Per-round wall time and the trace-time
    ``gossip_ici_bytes`` land per arm; the spectral gaps stamp how much
    consensus contraction each wire budget buys."""
    kw = dict(num_clients=32, model="cnn", dataset="cifar10",
              input_shape=None, timed_rounds=3,
              n_devices=len(jax.devices()))
    central = _measure_gossip_arm(None, **kw)
    ring = _measure_gossip_arm("ring", **kw)
    kreg = _measure_gossip_arm("kregular", **kw)
    out = {"centralized": central, "ring": ring, "kregular": kreg}
    if central["rounds_per_sec"]:
        out["ring_over_centralized"] = round(
            ring["rounds_per_sec"] / central["rounds_per_sec"], 3)
        out["kregular_over_centralized"] = round(
            kreg["rounds_per_sec"] / central["rounds_per_sec"], 3)
    return out


def _measure_ooc_round(backend: str, *, num_clients=32, window=8,
                       num_byzantine=8, timed_rounds=3, model="cnn",
                       dataset="cifar10", adversary="ALIE",
                       momentum=0.9) -> dict:
    """One arm of the BLADES_BENCH_OOC A/B (ISSUE 15): the 32-client
    protocol through the FULL driver with a participation window —
    per-round cohorts of ``window`` clients whose state rows live in
    the ``backend`` store ("resident" keeps the population in HBM,
    "host"/"disk" stage cohort rows through the prefetcher).  Client
    momentum is ON so the per-client rows are real state, and the row
    stamps report the staging telemetry next to the wall time."""
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset=dataset, num_clients=num_clients, seed=0)
        .training(global_model=model, server_lr=0.5,
                  train_batch_size=BATCH,
                  num_batch_per_round=LOCAL_STEPS,
                  aggregator={"type": "Median"})
        .client(lr=0.1, momentum=momentum)
        .adversary(num_malicious_clients=num_byzantine,
                   adversary_config={"type": adversary})
        .evaluation(evaluation_interval=0)
        .resources(execution="dense", state_store=backend, window=window)
    )
    algo = cfg.build()
    try:
        row = algo.train()  # compile + settle outside the timed loop
        t0 = time.perf_counter()
        for _ in range(timed_rounds):
            row = algo.train()
        dt = time.perf_counter() - t0
        final_loss = float(row["train_loss"])
        assert final_loss == final_loss  # NaN guard
        return {
            "rounds_per_sec": round(timed_rounds / dt, 4),
            "clients": num_clients, "window": window,
            "byzantine": num_byzantine, "model": model,
            "batch": BATCH, "local_steps": LOCAL_STEPS,
            "timed_rounds": timed_rounds, "aggregator": "Median",
            "adversary": adversary, "path": "windowed_dense",
            "state_store": row.get("state_store", backend),
            "state_stage_ms": row.get("state_stage_ms"),
            "state_bytes_staged": row.get("state_bytes_staged"),
            "state_peak_hbm_bytes": row.get("state_peak_hbm_bytes"),
        }
    finally:
        algo.stop()


def _ooc_block() -> dict:
    """BLADES_BENCH_OOC satellite (ISSUE 15): resident-vs-host A/B on
    the 32-client windowed protocol — the staging overhead the
    out-of-core store pays for its O(window) memory ceiling — plus a
    large-n host-only point (a registered population whose resident
    stack would dwarf the cohort working set)."""
    resident = _measure_ooc_round("resident")
    host = _measure_ooc_round("host")
    out = {"resident": resident, "host": host}
    if resident["rounds_per_sec"]:
        out["host_over_resident"] = round(
            host["rounds_per_sec"] / resident["rounds_per_sec"], 3)
    # Large registered population, small cohort: the point the store
    # exists for.  The resident arm is deliberately absent (its stack
    # is the memory ceiling being removed).
    out["large_n_host"] = _measure_ooc_round(
        "host", num_clients=2048, window=64, num_byzantine=512,
        timed_rounds=2, model="mlp", dataset="mnist")
    return out


def _measure_datastore_round(backend: str, *, num_clients=32, window=8,
                             num_byzantine=8, timed_rounds=3, model="cnn",
                             dataset="cifar10", adversary="ALIE") -> dict:
    """One arm of the BLADES_BENCH_DATASTORE A/B (ISSUE 20): the
    32-client windowed protocol with the TRAINING DATA in the
    ``backend`` data store ("resident" stages cohorts from host numpy
    exactly as before; "memmap" gathers them from CRC'd disk shards and
    streams eval in device-sized chunks).  The state store stays
    resident in both arms so the delta isolates the data plane; one
    eval runs inside the arm so the memmap side exercises the chunked
    evaluator and stamps ``eval_chunks``."""
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset=dataset, num_clients=num_clients, seed=0)
        .training(global_model=model, server_lr=0.5,
                  train_batch_size=BATCH,
                  num_batch_per_round=LOCAL_STEPS,
                  aggregator={"type": "Median"})
        .client(lr=0.1, momentum=0.9)
        .adversary(num_malicious_clients=num_byzantine,
                   adversary_config={"type": adversary})
        .evaluation(evaluation_interval=0)
        .resources(execution="dense", window=window,
                   data_store=backend, eval_chunk_clients=8)
    )
    algo = cfg.build()
    try:
        row = algo.train()  # compile + settle outside the timed loop
        t0 = time.perf_counter()
        for _ in range(timed_rounds):
            row = algo.train()
        dt = time.perf_counter() - t0
        final_loss = float(row["train_loss"])
        assert final_loss == final_loss  # NaN guard
        ev = algo.evaluate()
        return {
            "rounds_per_sec": round(timed_rounds / dt, 4),
            "clients": num_clients, "window": window,
            "byzantine": num_byzantine, "model": model,
            "batch": BATCH, "local_steps": LOCAL_STEPS,
            "timed_rounds": timed_rounds, "aggregator": "Median",
            "adversary": adversary, "path": "windowed_dense",
            "data_store": row.get("data_store", backend),
            "data_stage_ms": row.get("data_stage_ms"),
            "data_bytes_staged": row.get("data_bytes_staged"),
            "test_acc": round(float(ev["test_acc"]), 4),
            "eval_chunks": ev.get("eval_chunks"),
        }
    finally:
        algo.stop()


def _datastore_block() -> dict:
    """BLADES_BENCH_DATASTORE satellite (ISSUE 20): resident-vs-memmap
    A/B on the 32-client windowed protocol — the shard-gather + chunked-
    eval overhead the disk-backed data store pays for its O(cohort)
    host-memory ceiling."""
    resident = _measure_datastore_round("resident")
    memmap = _measure_datastore_round("memmap")
    out = {"resident": resident, "memmap": memmap}
    if resident["rounds_per_sec"]:
        out["memmap_over_resident"] = round(
            memmap["rounds_per_sec"] / resident["rounds_per_sec"], 3)
    return out


def _measure_control_arm(controlled: bool, *, num_clients=32,
                         num_byzantine=8, rounds=12, model="cnn",
                         dataset="cifar10") -> dict:
    """One arm of the BLADES_BENCH_CONTROL A/B (ISSUE 17): the
    32-client protocol through the FULL driver under buffered-async
    execution and a DiurnalALIE campaign attack (ALIE bursts scheduled
    over virtual arrival time), with Signguard + forensics + the client
    ledger armed in BOTH arms — the only delta is the closed-loop
    controller quarantining ledger suspects vs the best static config
    riding out the bursts.  Stamps the actions taken and the final
    accuracy next to the wall time."""
    from blades_tpu.algorithms import FedavgConfig

    cfg = (
        FedavgConfig()
        .data(dataset=dataset, num_clients=num_clients, seed=7)
        .training(global_model=model, server_lr=0.5,
                  train_batch_size=BATCH,
                  num_batch_per_round=LOCAL_STEPS,
                  aggregator={"type": "Signguard"})
        .client(lr=0.1)
        .adversary(num_malicious_clients=num_byzantine,
                   adversary_config={"type": "DiurnalALIE", "period": 8,
                                     "duty": 0.99, "high": 1.5})
        .evaluation(evaluation_interval=rounds)
        .resources(execution="async")
        .arrivals(rate=0.4, agg_every=8, staleness_cap=4, seed=7)
        .observability(forensics=True, ledger=True, watchdog_rules=[
            {"name": "suspect_ceiling", "kind": "ceiling",
             "field": "suspected_fraction", "threshold": 0.05,
             "min_points": 1}])
    )
    if controlled:
        cfg.control(cooldown_rounds=2, quarantine_rounds=4,
                    quarantine_max=4,
                    rules={"suspect_ceiling": "quarantine"})
    algo = cfg.build()
    try:
        row = algo.train()  # compile + settle outside the timed loop
        t0 = time.perf_counter()
        for _ in range(rounds - 1):
            row = algo.train()
        dt = time.perf_counter() - t0
        final_loss = float(row["train_loss"])
        assert final_loss == final_loss  # NaN guard
        out = {
            "rounds_per_sec": round((rounds - 1) / dt, 4),
            "clients": num_clients, "byzantine": num_byzantine,
            "model": model, "dataset": dataset, "batch": BATCH,
            "local_steps": LOCAL_STEPS, "rounds": rounds,
            "aggregator": "Signguard",
            "adversary": "DiurnalALIE(period=8, duty=0.99)",
            "path": "async_controlled" if controlled else "async_static",
            "controlled": controlled,
            "final_train_loss": round(final_loss, 5),
        }
        if row.get("test_acc") is not None:
            out["final_test_acc"] = round(float(row["test_acc"]), 5)
        if controlled:
            out["actions_taken"] = row.get("control_actions_total")
            out["final_quarantine_size"] = row.get("quarantine_size")
            summary = getattr(algo, "control_summary", None)
            if summary:
                out["quarantined"] = summary.get("quarantined")
                out["watchdog_events"] = summary.get("watchdog_events")
        return out
    finally:
        algo.stop()


def _control_block() -> dict:
    """BLADES_BENCH_CONTROL satellite (ISSUE 17): controlled vs
    best-static A/B on the 32-client protocol under one campaign
    attack."""
    static = _measure_control_arm(False)
    controlled = _measure_control_arm(True)
    out = {"static": static, "controlled": controlled}
    if (static.get("final_test_acc") is not None
            and controlled.get("final_test_acc") is not None):
        out["acc_delta"] = round(
            controlled["final_test_acc"] - static["final_test_acc"], 5)
    if static["rounds_per_sec"]:
        out["controlled_over_static"] = round(
            controlled["rounds_per_sec"] / static["rounds_per_sec"], 3)
    return out


def _resnet18_block() -> dict:
    # n=768 (was 576 through round 3): malicious-lane elision stores
    # only the 576 benign rows of the bf16 update matrix (12.9 GB) —
    # the byzantine quarter's rows never exist — so the single-chip
    # capacity grew by exactly the attack fraction.  client_block 24
    # is the largest that fits (2.8 GB activation temps; 32 is a
    # verified compile OOM) and measures ~1.5% over 16.
    r18 = bench_workload("resnet18", 768, 24, timed_rounds=3)
    r18["note"] = (
        "768 is the single-chip limit under malicious-lane elision "
        "(the compacted matrix stores only the 576 benign rows = "
        "12.9 GB; through r3 the full-matrix limit was n=576, with "
        "n=640 a verified compile OOM at 16.66 > 15.75 GB HBM). "
        "n=1000 (22.3 GB bf16 full) remains the multi-chip d-sharded "
        "config (parallel/dsharded.py)."
    )
    # Derived projection (VERDICT r4 weak #5: the old x0.7 was a guess):
    # executed-client compute scaling + the analytic per-chip ICI wire
    # time of every collective the d-sharded round issues, with the
    # collective inventory reconciled against compiled HLO
    # (blades_tpu/parallel/comm_model.py, tests/test_comm_model.py).
    from blades_tpu.parallel.comm_model import project_multichip_rounds_per_sec

    r18["projection_1000clients_v5e8"] = project_multichip_rounds_per_sec(
        measured_rps=r18["rounds_per_sec"],
        n_benign_measured=576, n_target=1000, n_dev=8, d=r18["params"],
        update_bytes=2, aggregator="Median", adversary="ALIE",
        num_malicious=250)
    return r18


# (env gate, result key, block) — each on by default, run in this order
# after the headline.
_BLOCKS = (
    ("BLADES_BENCH_RESNET18", "resnet18", _resnet18_block),
    ("BLADES_BENCH_PACKED", "packed_cnn", _packed_cnn_block),
    ("BLADES_BENCH_ROWGEOM", "rowgeom", _rowgeom_block),
    ("BLADES_BENCH_AUTOTUNE", "autotune", _autotune_block),
    ("BLADES_BENCH_QUANTAGG", "quantagg", _quantagg_block),
    ("BLADES_BENCH_TRACE", "trace", _trace_block),
    ("BLADES_BENCH_LEDGER", "ledger", _ledger_block),
    ("BLADES_BENCH_ASYNC", "async", _measure_async_cnn),
    ("BLADES_BENCH_OOC", "ooc", _ooc_block),
    ("BLADES_BENCH_DATASTORE", "datastore", _datastore_block),
    ("BLADES_BENCH_CONTROL", "control", _control_block),
    ("BLADES_BENCH_GOSSIP", "gossip", _gossip_block),
    ("BLADES_BENCH_MESH", "mesh", _mesh_block),
)


def main() -> None:
    device = _device()
    if device["platform"] != "tpu":
        print(json.dumps(_error_json(
            "no_tpu", f"bench.py measures on a TPU; JAX found {device}")),
            flush=True)
        sys.exit(2)
    from blades_tpu.perf import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    try:
        r10 = bench_workload("resnet10", 1000, 50, timed_rounds=5)
    except Exception as e:
        print(json.dumps(_error_json("resnet10_workload_failed",
                                     f"{type(e).__name__}: {e}")),
              flush=True)
        raise

    out = {
        "metric": METRIC_NAME,
        "value": r10["rounds_per_sec"],
        "unit": "rounds/s",
        "device": device,
        "vs_baseline": round(r10["rounds_per_sec"] / BASELINE_EST_ROUNDS_PER_SEC, 2),
        "baseline": {
            "rounds_per_sec": BASELINE_EST_ROUNDS_PER_SEC,
            "kind": "estimate",
            "provenance": "reference publishes no throughput; ~1 round/s "
                          "@60 clients/1 GPU envelope x (1000/60 clients) "
                          "/ 4 GPUs perfect scaling",
        },
        "mfu": r10["mfu"],
        "flops_per_round": r10["flops_per_round"],
        # Same shape as the resnet18 block below, plus the shared knobs.
        "config": {**r10, "batch": BATCH, "local_steps": LOCAL_STEPS,
                   "update_matrix": "bf16", "path": "streamed_single_chip"},
    }

    failed = []
    for gate, name, block in _BLOCKS:
        if os.environ.get(gate, "1") != "1":
            continue
        try:
            out[name] = block()
        except Exception as e:
            # The headline survives a secondary block's failure, but the
            # run does not exit 0 over it.
            out[name] = {"error": f"{type(e).__name__}: {e}"[:500]}
            failed.append(name)
    print(json.dumps(out), flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()

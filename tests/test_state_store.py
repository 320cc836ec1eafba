"""Out-of-core per-client state tests (blades_tpu/state, ISSUE 15):

- store protocol: gather/scatter round trips, shard-checkpoint
  streaming, cross-backend restore;
- chaos on the store: torn/corrupt shard fail-fast, orphaned ``.tmp``
  cleanup, missing-manifest fail-fast;
- the cohort-equivalence CONTRACT: ``resident`` / ``host`` / ``disk``
  produce bit-identical rows, aggregates and server params for the
  same (seed, cohort schedule) — staging forced on for the host arm,
  so prefetch on/off identity rides the same check — across Mean
  (tier-1) + Multikrum + GeoMed (slow zoo), including a topk+EF codec
  run whose residual round-trips through the store;
- kill-and-resume: a mid-sweep SimulatedPreemption under
  ``state_store="disk"`` resumes bit-identically from the streaming
  shard checkpoints;
- the window=0 stateless degenerate case, validate()-time gates, the
  autotune plan knobs, schema registration, and the scaled-down
  acceptance demo: 10k registered / 256 sampled clients on CPU with
  the asserted window-proportional peak-HBM bound.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.algorithms import FedavgConfig
from blades_tpu.state import (
    DiskStore,
    HostStore,
    ResidentStore,
    StateStoreError,
    make_store,
    sample_cohort,
)

ROW_KEYS = ("train_loss", "agg_norm", "update_norm_mean")


def windowed_config(backend=None, window=4, *, seed=3, prefetch=False,
                    aggregator="Mean", codec=None, momentum=0.9, **overrides):
    """``backend=None`` leaves state_store DEFAULTED (resident) so the
    autotuner's composition contract sees an un-pinned knob."""
    cfg = (
        FedavgConfig()
        .data(dataset="mnist", num_clients=8, seed=seed)
        .training(global_model="mlp", server_lr=1.0, train_batch_size=8,
                  aggregator={"type": aggregator})
        .client(lr=0.1, momentum=momentum)
        .evaluation(evaluation_interval=0)
        .resources(state_store=backend, window=window)
    )
    cfg.prefetch = prefetch
    if codec is not None:
        cfg.communication(codec=codec)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _server_params(algo):
    return [np.asarray(p) for p in jax.tree.leaves(algo.state.server.params)]


def _store_rows(algo):
    """Every registered client's state rows, fetched through the store."""
    algo._state_pf.flush()
    rows = algo._state_store.gather(np.arange(algo.config.num_clients))
    return [np.asarray(l) for l in jax.tree.leaves(rows)]


# ---------------------------------------------------------------------------
# cohort sampling
# ---------------------------------------------------------------------------


def test_sample_cohort_deterministic_sorted_distinct():
    k = jax.random.PRNGKey(7)
    a = sample_cohort(k, 1000, 64)
    b = sample_cohort(k, 1000, 64)
    np.testing.assert_array_equal(a, b)          # pure in the round key
    assert a.dtype == np.int32
    assert np.all(np.diff(a) > 0)                # sorted, distinct
    assert a.min() >= 0 and a.max() < 1000
    c = sample_cohort(jax.random.PRNGKey(8), 1000, 64)
    assert not np.array_equal(a, c)              # key steers the draw
    full = sample_cohort(k, 16, 16)
    np.testing.assert_array_equal(full, np.arange(16))  # window == n
    with pytest.raises(ValueError):
        sample_cohort(k, 10, 11)


# ---------------------------------------------------------------------------
# store protocol: round trips + shard checkpoints + chaos
# ---------------------------------------------------------------------------


def _template():
    return {"opt": {"buf": jnp.zeros((5,), jnp.float32)},
            "residual": jnp.zeros((3,), jnp.float32)}


@pytest.mark.parametrize("backend", ["resident", "host", "disk"])
def test_store_gather_scatter_roundtrip(backend, tmp_path):
    store = make_store(backend, 12, _template(),
                       directory=str(tmp_path / "live"))
    try:
        ids = np.array([1, 4, 9], np.int32)
        rows = {"opt": {"buf": jnp.arange(15, dtype=jnp.float32)
                        .reshape(3, 5)},
                "residual": -jnp.ones((3, 3), jnp.float32)}
        store.scatter(ids, rows)
        got = store.gather(ids)
        np.testing.assert_array_equal(np.asarray(got["opt"]["buf"]),
                                      np.asarray(rows["opt"]["buf"]))
        np.testing.assert_array_equal(np.asarray(got["residual"]),
                                      np.asarray(rows["residual"]))
        # Untouched rows keep the template values.
        other = store.gather(np.array([0, 11], np.int32))
        np.testing.assert_array_equal(np.asarray(other["opt"]["buf"]),
                                      np.zeros((2, 5), np.float32))
        assert store.row_bytes == (5 + 3) * 4
        assert store.total_bytes() == 12 * 8 * 4
        assert (store.device_bytes() == store.total_bytes()
                if backend == "resident" else store.device_bytes() == 0)
    finally:
        store.close()


def test_disk_store_unsorted_ids_across_shards(tmp_path):
    """Regression (review): the async engine gathers event clients in
    FIFO arrival order — a multi-shard DiskStore must honor ARBITRARY
    id order on both gather and scatter, not just the sorted ids the
    sync cohort path produces."""
    template = {"buf": jnp.zeros((2,), jnp.float32)}
    store = DiskStore(10, template, directory=str(tmp_path / "live"),
                      shard_rows=3)  # ids span 4 shards
    try:
        ids = np.array([7, 0, 9, 3], np.int32)  # unsorted, cross-shard
        rows = {"buf": jnp.asarray(
            [[70.0, 71.0], [0.0, 1.0], [90.0, 91.0], [30.0, 31.0]])}
        store.scatter(ids, rows)
        got = store.gather(ids)
        np.testing.assert_array_equal(np.asarray(got["buf"]),
                                      np.asarray(rows["buf"]))
        # Sorted view agrees row-for-row with the unsorted write.
        sorted_got = store.gather(np.array([0, 3, 7, 9], np.int32))
        np.testing.assert_array_equal(
            np.asarray(sorted_got["buf"]),
            np.asarray(rows["buf"])[np.argsort(ids)])
    finally:
        store.close()


def test_prefetcher_surfaces_writeback_failure():
    """Regression (review): a store scatter that fails on the staging
    worker must re-raise on the driver thread (writeback reap / flush),
    never silently serve stale rows."""
    from blades_tpu.state import StatePrefetcher

    class ExplodingStore(HostStore):
        def scatter(self, ids, rows):
            raise OSError("disk full")

    store = ExplodingStore(8, _template())
    data = (np.zeros((8, 2, 2), np.float32), np.zeros((8, 2), np.int32),
            np.full((8,), 2, np.int32))
    pf = StatePrefetcher(store, data, np.zeros(8, bool),
                         lambda k: np.arange(4, dtype=np.int32),
                         async_staging=True)
    try:
        pf.writeback(np.arange(4, dtype=np.int32),
                     store.gather(np.arange(4)))
        with pytest.raises(OSError, match="disk full"):
            pf.flush()
    finally:
        pf.close()


def test_shard_checkpoint_cross_backend_restore(tmp_path):
    """A checkpoint streamed from one backend restores into any other,
    rows bit-equal — shards are the one on-disk format."""
    src = make_store("host", 10, _template())
    ids = np.arange(10, dtype=np.int32)
    rows = {"opt": {"buf": jnp.arange(50, dtype=jnp.float32)
                    .reshape(10, 5)},
            "residual": jnp.arange(30, dtype=jnp.float32).reshape(10, 3)}
    src.scatter(ids, rows)
    src.save(tmp_path / "ckpt", shard_rows=3)  # forces multiple shards
    manifest = json.loads((tmp_path / "ckpt" / "manifest.json").read_text())
    assert manifest["num_shards"] == 4 and len(manifest["files"]) == 8
    for backend in ("resident", "disk"):
        dst = make_store(backend, 10, _template(),
                         directory=str(tmp_path / f"live-{backend}"))
        try:
            dst.load(tmp_path / "ckpt")
            got = dst.gather(ids)
            np.testing.assert_array_equal(np.asarray(got["opt"]["buf"]),
                                          np.asarray(rows["opt"]["buf"]))
            np.testing.assert_array_equal(np.asarray(got["residual"]),
                                          np.asarray(rows["residual"]))
        finally:
            dst.close()


def test_torn_shard_and_orphan_tmp_chaos(tmp_path):
    """Chaos on the store checkpoint: a truncated shard and a
    bit-flipped shard both fail fast naming the file; an orphaned
    ``.tmp`` (killed atomic write) is cleaned up; a missing manifest —
    the kill-before-publish state — fails fast too."""
    store = make_store("host", 8, _template())
    store.save(tmp_path / "ckpt", shard_rows=4)
    shard = tmp_path / "ckpt" / "shard-00001.l00.npy"

    # Orphaned .tmp from a killed write: cleaned, restore succeeds.
    orphan = tmp_path / "ckpt" / "shard-00000.l00.npy.tmp"
    orphan.write_bytes(b"half-written garbage")
    make_store("host", 8, _template()).load(tmp_path / "ckpt")
    assert not orphan.exists()

    # Torn shard (truncation): loud failure naming the shard.
    data = shard.read_bytes()
    shard.write_bytes(data[: len(data) // 2])
    with pytest.raises(StateStoreError, match="shard-00001.l00.npy"):
        make_store("host", 8, _template()).load(tmp_path / "ckpt")

    # Same-size corruption: the CRC catches what the size check cannot.
    flipped = bytearray(data)
    flipped[-1] ^= 0xFF
    shard.write_bytes(bytes(flipped))
    with pytest.raises(StateStoreError, match="CRC32"):
        make_store("host", 8, _template()).load(tmp_path / "ckpt")
    shard.write_bytes(data)

    # Kill before the manifest publish: no manifest, no restore.
    (tmp_path / "ckpt" / "manifest.json").unlink()
    with pytest.raises(StateStoreError, match="manifest"):
        make_store("host", 8, _template()).load(tmp_path / "ckpt")

    # Population / layout drift fail fast as their own errors.
    store.save(tmp_path / "ckpt2", shard_rows=4)
    with pytest.raises(StateStoreError, match="registered clients"):
        make_store("host", 9, _template()).load(tmp_path / "ckpt2")


# ---------------------------------------------------------------------------
# the cohort-equivalence contract
# ---------------------------------------------------------------------------

# Tier-1 runs the headline aggregator; Multikrum/GeoMed run the same
# contract in the slow zoo (each backend arm is its own compile — the
# 870 s tier-1 budget convention of PR 7).
_CONTRACT_AGGREGATORS = ("Mean",)


@pytest.mark.parametrize("aggregator", [
    a if a in _CONTRACT_AGGREGATORS else pytest.param(
        a, marks=pytest.mark.slow)
    for a in ("Mean", "Multikrum", "GeoMed")])
def test_cohort_equivalence_across_backends(aggregator):
    """The contract: host and disk stores produce bit-identical rows,
    aggregates and server params to resident for the same (seed,
    cohort schedule).  The host arm runs with staging forced ON, so
    the double-buffered prefetcher (overlap patching included — window
    6 of 8 guarantees cohort overlap; 6 also satisfies Multikrum's
    2f+2 <= window bound at f=2) is part of the identity."""
    adv = {"num_malicious_clients": 2, "adversary_config": {"type": "ALIE"}}
    algos = {
        "resident": windowed_config("resident", 6, aggregator=aggregator,
                                    **adv).build(),
        "host": windowed_config("host", 6, aggregator=aggregator,
                                prefetch=True, **adv).build(),
        "disk": windowed_config("disk", 6, aggregator=aggregator,
                                **adv).build(),
    }
    try:
        rows = {k: [a.train() for _ in range(4)] for k, a in algos.items()}
        for r_res, r_host, r_disk in zip(rows["resident"], rows["host"],
                                         rows["disk"]):
            for k in ROW_KEYS:
                assert r_res[k] == r_host[k] == r_disk[k], (
                    aggregator, k, r_res[k], r_host[k], r_disk[k])
        params = {k: _server_params(a) for k, a in algos.items()}
        stores = {k: _store_rows(a) for k, a in algos.items()}
        for k in ("host", "disk"):
            for a, b in zip(params["resident"], params[k]):
                np.testing.assert_array_equal(a, b, err_msg=(aggregator, k))
            for a, b in zip(stores["resident"], stores[k]):
                np.testing.assert_array_equal(a, b, err_msg=(aggregator, k))
    finally:
        for a in algos.values():
            a.stop()


# Codec-EF x store composition (~8 s compile); the store's headline
# cross-backend equivalence stays tier-1 via
# test_cohort_equivalence_across_backends[Mean] (PR 20 budget rebalance).
@pytest.mark.slow
def test_topk_ef_residual_through_store():
    """topk+EF codec under the window: the per-client error-feedback
    residual lives in the store (windowed like the opt state) and the
    compressed trajectory is backend-invariant bit for bit."""
    codec = {"type": "topk", "topk_ratio": 0.1, "error_feedback": True}
    res = windowed_config("resident", 5, aggregator="Median",
                          codec=codec).build()
    host = windowed_config("host", 5, aggregator="Median", codec=codec,
                           prefetch=True).build()
    try:
        assert "residual" in res._row_template
        for _ in range(4):
            a, b = res.train(), host.train()
            for k in ROW_KEYS:
                assert a[k] == b[k], (k, a[k], b[k])
        for x, y in zip(_store_rows(res), _store_rows(host)):
            np.testing.assert_array_equal(x, y)
        # The residual genuinely accumulated (EF is active, not zeros).
        full = res._state_store.gather(np.arange(8))
        assert float(np.abs(np.asarray(full["residual"])).sum()) > 0.0
    finally:
        res.stop()
        host.stop()


# ---------------------------------------------------------------------------
# kill-and-resume on the windowed store
# ---------------------------------------------------------------------------


def _ooc_experiments(stop=8):
    return {
        "ooc": {
            "run": "FEDAVG",
            "stop": {"training_iteration": stop},
            "config": {
                "dataset_config": {"type": "mnist", "num_clients": 8,
                                   "train_bs": 8, "seed": 3},
                "global_model": "mlp",
                "client_config": {"lr": 0.1, "momentum": 0.9},
                "evaluation_interval": 4,
                "server_config": {"lr": 1.0,
                                  "aggregator": {"type": "Median"}},
                "state_store": "disk",
                "state_window": 5,
            },
        }
    }


def _result_rows(tdir, keep_eval_rounds=(4, 8)):
    rows = []
    for ln in (Path(tdir) / "result.json").read_text().strip().splitlines():
        r = json.loads(ln)
        for k in ("timers", "compile_cache_hits", "compile_cache_misses",
                  "state_stage_ms", "state_bytes_staged", "data_stage_ms"):
            r.pop(k, None)  # wall-clock / cache / staging-timing noise
        if r["training_iteration"] not in keep_eval_rounds:
            # Repeat-last-eval rows: _last_eval is not checkpointed (a
            # restored trial repeats nothing until its next fresh eval)
            # — pre-existing driver behavior on every path, so only
            # FRESH eval rounds participate in the bit-identity check.
            for k in ("test_loss", "test_acc", "test_acc_top3"):
                r.pop(k, None)
        rows.append(r)
    return rows


def test_kill_and_resume_disk_store_bit_identical(tmp_path):
    """Acceptance: a SimulatedPreemption mid-sweep under
    state_store="disk" retries from the latest STREAMING shard
    checkpoint and reproduces the straight-through rows exactly (the
    faults/ preemption harness, pointed at the windowed store)."""
    from blades_tpu.tune import run_experiments
    from blades_tpu.tune.sweep import verify_result_rounds

    [straight] = run_experiments(
        _ooc_experiments(), storage_path=str(tmp_path / "a"), verbose=0,
        lanes=False, checkpoint_freq=2)
    [preempted] = run_experiments(
        _ooc_experiments(), storage_path=str(tmp_path / "b"), verbose=0,
        lanes=False, checkpoint_freq=2, max_failures=1, preempt_after=5,
        retry_backoff_base=0.0)
    assert "status" not in preempted and preempted["rounds"] == 8
    tdir = Path(preempted["dir"])
    assert "SimulatedPreemption" in (tdir / "error.txt").read_text()
    assert verify_result_rounds(tdir / "result.json") == list(range(1, 9))
    # The resumed trajectory IS the straight-through one, row for row.
    assert _result_rows(straight["dir"]) == _result_rows(tdir)
    # Checkpoints hold streaming shards, not monolithic stacks.
    ckpts = sorted(tdir.glob("ckpt_*/client_state/manifest.json"))
    assert ckpts, "windowed checkpoints must carry shard files"


# ---------------------------------------------------------------------------
# stateless degenerate case + resident default + validate gates
# ---------------------------------------------------------------------------


def test_stateless_window0_and_resident_default():
    """window=0: round 1 matches the stateful run bit for bit (momentum
    buffers start at zero either way), round 2 diverges (the buffer was
    reset).  The default config builds NO store and keeps the cohort
    leaf None — the pre-PR pytree."""
    stateful = windowed_config(window=None).build()
    stateless = windowed_config("resident", 0).build()
    assert stateful._state_store is None
    assert getattr(stateful.state, "cohort", None) is None
    assert stateless._state_store is None  # nothing to store
    assert stateless.fed_round.stateless_clients
    a1, b1 = stateful.train(), stateless.train()
    for k in ROW_KEYS:
        assert a1[k] == b1[k], (k, a1[k], b1[k])
    a2, b2 = stateful.train(), stateless.train()
    assert a2["agg_norm"] != b2["agg_norm"]


def test_stateless_auto_execution_stays_dense(monkeypatch):
    """Regression (review): with window=0, execution='auto' must NOT
    resolve to the streamed path — streamed threads client_opt through
    its own block loop and would silently train STATEFUL clients."""
    monkeypatch.setenv("BLADES_TPU_DENSE_MATRIX_LIMIT_GB", "0.000001")
    stateful = windowed_config(window=None, prefetch=False).build()
    assert stateful._use_streamed()  # the tiny budget DOES trip auto...
    stateless = windowed_config("resident", 0, prefetch=False).build()
    assert not stateless._use_streamed()  # ...but stateless stays dense
    assert stateless.fed_round.stateless_clients
    r = stateless.train()
    assert np.isfinite(r["train_loss"])


def test_validate_gates():
    def check(match, **kw):
        with pytest.raises(ValueError, match=match):
            cfg = windowed_config(**kw)
            cfg.validate()

    check("needs a participation window", backend="host", window=None)
    check("cohort samples without replacement", backend="host", window=9)
    check("no windowed formulation", backend="host", window=4,
          execution="streamed")
    check("num_devices>1 is an unsupported", backend="host", window=4,
          num_devices=2)
    check("fault injection", backend="host", window=4,
          fault_config={"dropout_rate": 0.3})
    check("nothing for a 'host' store", backend="host", window=0)
    check("num_devices>1 is an unsupported", backend="resident", window=0,
          num_devices=2)
    check("top-k error-feedback", backend="resident", window=0,
          codec={"type": "topk", "topk_ratio": 0.1,
                 "error_feedback": True})
    check("state_store must be one of", backend="ramdisk", window=4)
    check("no windowed formulation", backend="host", window=4,
          execution="async")
    # Legal compositions still validate.
    windowed_config("disk", 4, health_check=True).validate()
    windowed_config("host", 4,
                    codec={"type": "quant", "bits": 8}).validate()
    # Forensics composes since the cohort-shaped re-index (ISSUE 16):
    # the windowed round diagnoses the (window, d) cohort matrix.
    windowed_config("host", 4, forensics=True).validate()


# ---------------------------------------------------------------------------
# async out-of-core composition
# ---------------------------------------------------------------------------


def test_async_event_cohort_through_store():
    """execution='async' + host store: the event cohort's opt rows are
    gathered/scattered per cycle (cohort-windowed cycle buffers) and
    the buffered trajectory is bit-identical to the resident engine."""
    spec = {"rate": 0.5, "agg_every": 4, "staleness_cap": 4}
    def build(backend):
        cfg = windowed_config(window=None, aggregator="Median")
        cfg.resources(execution="async")
        if backend != "resident":
            cfg.resources(state_store=backend)
        cfg.async_config = spec
        return cfg.build()

    res, host = build("resident"), build("host")
    try:
        assert host._state_store is not None and host._async is not None
        for _ in range(3):
            a, b = res.train(), host.train()
            for k in ROW_KEYS + ("tick",):
                assert a[k] == b[k], (k, a[k], b[k])
        assert b["state_store"] == "host" and b["cohort_size"] == 4
        # The driver-side RoundState never carries the full opt stack.
        assert host.state.client_opt is None
    finally:
        res.stop()
        host.stop()


# ---------------------------------------------------------------------------
# obs schema + autotune plan knobs
# ---------------------------------------------------------------------------


def test_windowed_row_stamps_schema_valid():
    from blades_tpu.obs.schema import ROUND_RECORD_FIELDS, validate_record

    algo = windowed_config("host", 4).build()
    try:
        row = algo.train()
    finally:
        algo.stop()
    stamps = {k: row[k] for k in ("state_store", "cohort_size",
                                  "state_stage_ms", "state_bytes_staged",
                                  "state_peak_hbm_bytes")}
    assert stamps["state_store"] == "host" and stamps["cohort_size"] == 4
    assert stamps["state_bytes_staged"] > 0
    assert set(stamps) <= set(ROUND_RECORD_FIELDS)
    validate_record({"experiment": "e", "trial": "t",
                     "training_iteration": 1, **stamps})


def test_plan_state_knobs():
    from blades_tpu.perf.autotune import Plan, apply_plan, enumerate_plans

    # Store-free plans keep the byte-identical pre-knob id format.
    assert Plan().plan_id == "dense|c131072|p1|mxu=off|nopre"
    windowed = Plan(state_store="host", state_window=256)
    assert windowed.plan_id.endswith("|ss=hostw256")
    with pytest.raises(ValueError):
        Plan(state_store="ramdisk")
    # Backend alternates are reassociating-tier; the window is pinned.
    space = enumerate_plans(
        executions=["dense"], d_chunks=[1 << 17],
        state_stores=["disk", "host", "resident"], state_windows=[16],
        allow_reassociating=True)
    assert space.baseline.state_store == "disk"
    tiers = {p.state_store: p.tier for p in space.candidates}
    assert tiers["disk"] == "default"
    assert tiers["host"] == tiers["resident"] == "reassociating"
    default_only = enumerate_plans(
        executions=["dense"], d_chunks=[1 << 17],
        state_stores=["disk", "host"], state_windows=[16],
        allow_reassociating=False)
    assert [p.state_store for p in default_only.candidates] == ["disk"]
    cfg = windowed_config("disk", 16)
    apply_plan(cfg, Plan(state_store="host", state_window=16,
                         tier="reassociating"))
    assert cfg.state_store == "host" and cfg.state_window == 16


def test_driver_plan_space_probes_backends():
    """The reassociating tier offers the alternate store backends for a
    windowed trial whose backend was left DEFAULTED (window pinned
    either way); an explicitly-set backend pins the list and the
    default tier never varies it — the composition contract."""
    cfg = windowed_config(window=4, autotune="on")  # backend defaulted
    algo = cfg.build()
    try:
        assert "state_store" not in cfg._explicit
        default = algo._plan_space(allow_reassociating=False)
        assert {p.state_store for p in default.candidates} == {"resident"}
        re = algo._plan_space(allow_reassociating=True)
        assert {p.state_store for p in re.candidates} == {"resident",
                                                          "host"}
        assert {p.state_window for p in re.candidates} == {4}
        assert re.baseline.state_store == "resident"
    finally:
        algo.stop()
    pinned = windowed_config("disk", 4, autotune="on").build()
    try:
        re = pinned._plan_space(allow_reassociating=True)
        assert {p.state_store for p in re.candidates} == {"disk"}
    finally:
        pinned.stop()


# ---------------------------------------------------------------------------
# the scaled-down acceptance demo: 10k registered / 256 sampled on CPU
# ---------------------------------------------------------------------------


def _tiny_population_dataset(n_clients, rows_per_client=4, shape=(4, 4, 1),
                             num_classes=2, seed=0):
    from blades_tpu.data.datasets import FLDataset
    from blades_tpu.data.partition import partition_dataset

    rng = np.random.default_rng(seed)
    n = n_clients * rows_per_client
    mus = rng.normal(size=(num_classes,) + shape).astype(np.float32)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = (mus[y] + 0.5 * rng.normal(size=(n,) + shape)).astype(np.float32)
    train = partition_dataset(x, y, n_clients, iid=True, seed=seed)
    test = partition_dataset(x[: 2 * n_clients], y[: 2 * n_clients],
                             n_clients, iid=True, seed=seed + 1)
    return FLDataset(name="tinypop", train=train, test_x=x[:64],
                     test_y=y[:64], test=test, num_classes=num_classes,
                     input_shape=shape)


def test_10k_registered_256_sampled_memory_ceiling():
    """The acceptance demo, scaled for CPU tier-1: 10 000 registered
    clients / 256 sampled per round train through the host store, and
    the asserted peak device-resident state is WINDOW-proportional —
    a small multiple of the cohort working set, an order of magnitude
    under the O(n_registered * d) resident stack this store removes."""
    from blades_tpu.models.mlp import MLP

    n, w = 10_000, 256
    cfg = (
        FedavgConfig()
        .data(dataset=_tiny_population_dataset(n), num_clients=n, seed=0)
        .training(global_model=MLP(hidden1=8, hidden2=8, num_classes=2),
                  num_classes=2, input_shape=(4, 4, 1), server_lr=0.5,
                  train_batch_size=4)
        .client(lr=0.1, momentum=0.9)
        .evaluation(evaluation_interval=0)
        .resources(state_store="host", window=w)
    )
    algo = cfg.build()
    try:
        rows = [algo.train() for _ in range(2)]
        for r in rows:
            assert np.isfinite(r["train_loss"])
        row_bytes = algo._state_store.row_bytes
        assert row_bytes > 0
        data_bytes = sum(np.asarray(a[:w]).nbytes
                         for a in algo._host_train)
        peak = rows[-1]["state_peak_hbm_bytes"]
        # Window-proportional: the staged + live + write-back cohort
        # slots plus the cohort's data shards...
        assert peak <= 3 * w * row_bytes + data_bytes
        # ...and nowhere near the resident stack it replaces.
        assert peak < n * row_bytes // 4
        assert algo._state_store.total_bytes() == n * row_bytes
        assert rows[-1]["cohort_size"] == w
    finally:
        algo.stop()

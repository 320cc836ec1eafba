"""Single-chip streaming round (parallel/streamed.py): equivalence with
the dense FedRound.step at f32 storage, bf16 smoke, capability guards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.adversaries import get_adversary, make_malicious_mask
from blades_tpu.core import FedRound, Server, TaskSpec
from blades_tpu.parallel.streamed import streamed_step
from blades_tpu.utils.tree import ravel_fn

N = 8
F = 2


def make_fr(aggregator="Median", adversary="ALIE", **kw):
    task = TaskSpec(model="mlp", lr=0.1, input_shape=(28, 28, 1)).build()
    server = Server.from_config(aggregator=aggregator, num_byzantine=F, lr=1.0,
                                **kw.pop("server_kwargs", {}))
    adv = get_adversary(adversary, num_clients=N, num_byzantine=F) if adversary else None
    return FedRound(task=task, server=server, adversary=adv, batch_size=8, **kw)


@pytest.fixture(scope="module")
def data():
    from blades_tpu.data import DatasetCatalog

    ds = DatasetCatalog.get_dataset("mnist", num_clients=N)
    return (
        jnp.array(ds.train.x), jnp.array(ds.train.y), jnp.array(ds.train.lengths),
        make_malicious_mask(N, F),
    )


@pytest.mark.parametrize("aggregator,adversary", [
    ("Median", "ALIE"),
    ("Mean", "IPM"),
    ("Trimmedmean", "ALIE"),
])
def test_streamed_matches_dense_f32(data, aggregator, adversary):
    """f32 storage + deterministic coordinate-wise attacks: the chunked
    pipeline must reproduce the dense round exactly (same key stream)."""
    x, y, ln, mal = data
    fr = make_fr(aggregator, adversary)
    key = jax.random.PRNGKey(3)

    st_a = fr.init(jax.random.PRNGKey(0), N)
    st_a, m_a = jax.jit(fr.step)(st_a, x, y, ln, mal, key)

    st_b = fr.init(jax.random.PRNGKey(0), N)
    step = streamed_step(fr, client_block=4, d_chunk=10_000,
                         update_dtype=jnp.float32)
    st_b, m_b = step(st_b, x, y, ln, mal, key)

    ravel, _, _ = ravel_fn(st_a.server.params)
    np.testing.assert_allclose(
        np.asarray(ravel(st_a.server.params)),
        np.asarray(ravel(st_b.server.params)), atol=1e-6, rtol=1e-5,
    )
    np.testing.assert_allclose(float(m_a["train_loss"]), float(m_b["train_loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m_a["update_norm_mean"]),
                               float(m_b["update_norm_mean"]), rtol=1e-4)


def test_streamed_bf16_trains(data):
    """bf16 storage: order statistics survive the rounding; multi-round
    training still descends."""
    x, y, ln, mal = data
    fr = make_fr("Median", "ALIE")
    st = fr.init(jax.random.PRNGKey(0), N)
    step = streamed_step(fr, client_block=4, d_chunk=10_000)
    losses = []
    for r in range(8):
        st, m = step(st, x, y, ln, mal, jax.random.fold_in(jax.random.PRNGKey(1), r))
        losses.append(float(m["train_loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert int(m["round"]) == 8


def test_streamed_rejects_unsupported_configs():
    """Every registry aggregator AND forger now has a streamed
    formulation; unknown custom aggregators/forgers are rejected with a
    pointer at build time."""
    import dataclasses

    from blades_tpu.adversaries.base import Adversary
    from blades_tpu.ops.aggregators import Aggregator

    @dataclasses.dataclass(frozen=True)
    class CustomAgg(Aggregator):
        def aggregate(self, updates):
            return updates.mean(axis=0)

    fr = make_fr("Mean")
    fr = dataclasses.replace(fr, server=dataclasses.replace(
        fr.server, aggregator=CustomAgg()))
    with pytest.raises(NotImplementedError, match="streamed formulation"):
        streamed_step(fr)

    @dataclasses.dataclass(frozen=True)
    class CustomForger(Adversary):
        def on_updates_ready(self, updates, malicious, key, **kw):
            return updates

    fr = make_fr("Median")
    fr = dataclasses.replace(fr, adversary=CustomForger())
    with pytest.raises(NotImplementedError, match="forge"):
        streamed_step(fr)


def test_streamed_dp_clip_matches_dense_exactly(data):
    """DP clipping on the streamed path uses full-row norms precomputed at
    train time — with f32 storage and noise off it must reproduce the
    dense round (to cross-dispatch float tolerance)."""
    fr_dp = make_fr(dp_clip_threshold=0.05)
    state = fr_dp.init(jax.random.PRNGKey(0), N)
    x, y, ln, mal = data
    key = jax.random.PRNGKey(9)

    dense_state, dm = jax.jit(fr_dp.step)(state, x, y, ln, mal, key)
    step = streamed_step(fr_dp, client_block=4, d_chunk=64,
                         update_dtype=jnp.float32, donate=False)
    st_state, sm = step(state, x, y, ln, mal, key)

    # Same tolerance as the sibling f32 equivalence test: bit-exactness
    # across different dispatch/fusion shapes is backend-dependent.
    np.testing.assert_allclose(
        np.asarray(dm["agg_norm"]), np.asarray(sm["agg_norm"]),
        atol=1e-6, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(dense_state.server.params),
                    jax.tree.leaves(st_state.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)


def test_streamed_dp_noise_is_applied(data):
    fr_dp = make_fr(dp_clip_threshold=0.05, dp_noise_factor=2.0)
    state = fr_dp.init(jax.random.PRNGKey(0), N)
    x, y, ln, mal = data
    step = streamed_step(fr_dp, client_block=4, d_chunk=64,
                         update_dtype=jnp.float32, donate=False)
    _, m = step(state, x, y, ln, mal, jax.random.PRNGKey(9))
    # Clipped rows have norm <= 0.05; with sigma = 0.1 noise across d
    # coords the measured mean row norm must sit far above the clip.
    assert float(m["update_norm_mean"]) > 0.05 * 2
    assert np.isfinite(float(m["train_loss"]))


@pytest.mark.parametrize("aggregator,adversary", [
    ("Median", "ALIE"),          # fused-eligible coordinate path (chunked on CPU)
    ("GeoMed", "IPM"),           # row-geometry aggregator
    ("Median", "MinMax"),        # row-geometry forger
])
def test_malicious_prefix_elision_is_exact(data, aggregator, adversary):
    """Skipping the dead malicious-lane training blocks must reproduce the
    full round bit-for-bit at f32 storage: same server params, same
    aggregate/metrics, same benign-lane outputs (the forged rows never
    depended on what malicious clients trained)."""
    x, y, ln, mal = data
    fr = make_fr(aggregator, adversary)
    key = jax.random.PRNGKey(7)

    st_a = fr.init(jax.random.PRNGKey(0), N)
    full = streamed_step(fr, client_block=2, d_chunk=10_000,
                         update_dtype=jnp.float32)
    st_a, m_a = full(st_a, x, y, ln, mal, key)

    st_b = fr.init(jax.random.PRNGKey(0), N)
    elided = streamed_step(fr, client_block=2, d_chunk=10_000,
                           update_dtype=jnp.float32, malicious_prefix=F)
    st_b, m_b = elided(st_b, x, y, ln, mal, key)

    for a, b in zip(jax.tree.leaves(st_a.server.params),
                    jax.tree.leaves(st_b.server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_array_equal(np.asarray(m_a[k]), np.asarray(m_b[k]))
    # Elision telemetry (VERDICT item 6): the skipped lanes — the basis
    # num_unhealthy can never count — are surfaced; the full round's
    # metrics carry no such key (identity preserved).
    assert int(m_b["elided_lanes"]) == F
    assert "elided_lanes" not in m_a


def test_malicious_prefix_without_forge_trains_everyone(data):
    """No update forge (training-only attack): malicious training is NOT
    dead, and the prefix hint must be ignored."""
    x, y, ln, mal = data
    fr = make_fr("Mean", "SignFlip")
    key = jax.random.PRNGKey(7)

    st_a = fr.init(jax.random.PRNGKey(0), N)
    full = streamed_step(fr, client_block=2, d_chunk=10_000,
                         update_dtype=jnp.float32)
    st_a, m_a = full(st_a, x, y, ln, mal, key)

    st_b = fr.init(jax.random.PRNGKey(0), N)
    hinted = streamed_step(fr, client_block=2, d_chunk=10_000,
                           update_dtype=jnp.float32, malicious_prefix=F)
    st_b, m_b = hinted(st_b, x, y, ln, mal, key)

    for a, b in zip(jax.tree.leaves(st_a.server.params),
                    jax.tree.leaves(st_b.server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m_a["train_loss"]),
                                  np.asarray(m_b["train_loss"]))


def test_malicious_prefix_promise_is_validated(data):
    """A mask that disagrees with the promised prefix must fail loudly,
    not silently aggregate zero rows for benign clients."""
    x, y, ln, _ = data
    bad_mask = jnp.arange(N) >= (N - F)  # malicious at the TAIL
    fr = make_fr("Median", "ALIE")
    st = fr.init(jax.random.PRNGKey(0), N)
    step = streamed_step(fr, client_block=2, d_chunk=10_000,
                         update_dtype=jnp.float32, malicious_prefix=F)
    with pytest.raises(ValueError, match="elision"):
        step(st, x, y, ln, bad_mask, jax.random.PRNGKey(7))


def test_malicious_prefix_promise_check_is_per_object(data):
    """The once-per-mask validation cache must hold the validated OBJECT,
    not a recyclable id (ADVICE r4): a freed-and-reallocated DIFFERENT
    mask at the recycled address must still be validated and raise."""
    import gc

    x, y, ln, _ = data
    fr = make_fr("Median", "ALIE")
    st = fr.init(jax.random.PRNGKey(0), N)
    step = streamed_step(fr, client_block=2, d_chunk=10_000,
                         update_dtype=jnp.float32, malicious_prefix=F,
                         donate=False)
    # A locally-created correct mask (the fixture's must stay alive, so
    # its id could never be recycled and the test would prove nothing).
    good = jnp.arange(N) < F
    step(st, x, y, ln, good, jax.random.PRNGKey(7))

    freed_id = id(good)
    del good
    gc.collect()
    # Hunt for a wrong mask landing on the freed address.  Under the
    # fixed cache the slot PINS the validated object, so no collision
    # can occur and every wrong mask is validated; under a reverted
    # bare-id cache a collision would silently skip validation (zeroing
    # benign rows instead of raising) and fail this test.
    for i in range(16):
        bad = jnp.arange(N) >= (N - F)
        with pytest.raises(ValueError, match="elision"):
            step(st, x, y, ln, bad, jax.random.PRNGKey(8 + i))
        if id(bad) == freed_id:
            break  # the regression scenario itself was exercised
        del bad


def test_malicious_prefix_elision_exact_under_dp(data):
    """Elision + per-row DP: malicious lanes' clip norms differ (0 for
    untrained rows) but are dead — the forge overwrites those rows after
    DP.  Full vs elided must stay bit-equal at f32."""
    x, y, ln, mal = data
    fr = make_fr("Median", "ALIE", dp_clip_threshold=0.05,
                 dp_noise_factor=0.5)
    key = jax.random.PRNGKey(13)

    st_a = fr.init(jax.random.PRNGKey(0), N)
    full = streamed_step(fr, client_block=2, d_chunk=10_000,
                         update_dtype=jnp.float32)
    st_a, m_a = full(st_a, x, y, ln, mal, key)

    st_b = fr.init(jax.random.PRNGKey(0), N)
    elided = streamed_step(fr, client_block=2, d_chunk=10_000,
                           update_dtype=jnp.float32, malicious_prefix=F)
    st_b, m_b = elided(st_b, x, y, ln, mal, key)

    for a, b in zip(jax.tree.leaves(st_a.server.params),
                    jax.tree.leaves(st_b.server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m_a["agg_norm"]),
                                  np.asarray(m_b["agg_norm"]))


# -- block geometry from the storage tile, one compiled block (ISSUE 27) ---

@pytest.mark.parametrize("n,prefix,client_block,dtype,compact,want", [
    # (first_lane, first_row, block, blocks, surplus, aligned stores)
    # The two benchmark cells: 47 x 16 with 2 surplus lanes, and 36 x 16.
    (1000, 250, 25, jnp.bfloat16, True, (250, 0, 16, 47, 2, 47)),
    (768, 192, 24, jnp.bfloat16, True, (192, 0, 16, 36, 0, 36)),
    # The same federations off the compact path: rows are lanes, training
    # starts at the block boundary under the prefix, and a full matrix
    # with a short last block has no room for a padded tile.
    (1000, 250, 25, jnp.bfloat16, False, (240, 240, 16, 48, 8, 0)),
    (768, 192, 24, jnp.bfloat16, False, (192, 192, 16, 36, 0, 36)),
    # float32 storage: 8-row tiles, so 24 stays 24.
    (768, 192, 24, jnp.float32, True, (192, 0, 24, 24, 0, 24)),
    (1000, 250, 25, jnp.float32, True, (250, 0, 24, 32, 18, 32)),
    # A prime federation runs in tile-sized blocks, not 1-client ones.
    (997, 0, 50, jnp.bfloat16, False, (0, 0, 48, 21, 11, 0)),
    (997, 249, 50, jnp.bfloat16, True, (249, 0, 48, 16, 20, 16)),
    # client_block under a tile: the block is client_block, nothing is a
    # whole tile, and elision stops at the block boundary as before.
    (8, 2, 2, jnp.float32, False, (2, 2, 2, 3, 0, 0)),
    (8, 3, 2, jnp.float32, False, (2, 2, 2, 3, 0, 0)),
    (8, 2, 4, jnp.bfloat16, False, (0, 0, 4, 2, 0, 0)),
    (7, 0, 4, jnp.float32, False, (0, 0, 4, 2, 1, 0)),
    # More room than clients: one block of them all, or tiles of them.
    (6, 0, 50, jnp.float32, False, (0, 0, 6, 1, 0, 0)),
    (20, 0, 50, jnp.bfloat16, False, (0, 0, 16, 2, 12, 0)),
    (16, 0, 8, jnp.float32, False, (0, 0, 8, 2, 0, 2)),
    # Fewer benign lanes than a tile: the compact block is no larger
    # than what it trains.  Under a tile on the compact path the matrix
    # keeps a row a plane (ISSUE 32), where every store is whole tiles.
    (20, 10, 50, jnp.bfloat16, True, (10, 0, 10, 1, 0, 1)),
    # The language-model cell: 8 blocks of one lane, each a plane.
    (10, 2, 1, jnp.bfloat16, True, (2, 0, 1, 8, 0, 8)),
    (10, 2, 1, jnp.float32, True, (2, 0, 1, 8, 0, 8)),
    (10, 2, 3, jnp.float32, True, (2, 0, 3, 3, 1, 3)),
    (26, 2, 15, jnp.bfloat16, True, (2, 0, 15, 2, 6, 2)),
    # The same blocks off the compact path: (rows, d), part tiles.
    (10, 2, 1, jnp.bfloat16, False, (2, 2, 1, 8, 0, 0)),
])
def test_block_plan(n, prefix, client_block, dtype, compact, want):
    from blades_tpu.parallel.streamed import block_plan

    plan = block_plan(n, prefix, client_block, dtype, compact=compact)
    assert (plan.first_lane, plan.first_row, plan.block, plan.blocks,
            plan.surplus, plan.aligned_stores) == want
    # The layout's rule, from the shapes alone.
    assert plan.planes == (compact and plan.block < plan.tile)
    # Equal dispatches, none over the bound, cover the trained range with
    # less than one block of surplus, and the last one's early start
    # stays inside the lanes its matrix has rows for.
    assert plan.first_lane + plan.blocks * plan.block - plan.surplus == n
    assert plan.block <= client_block and 0 <= plan.surplus < plan.block
    assert plan.first_lane <= prefix
    assert n - plan.block >= (plan.first_lane if compact else 0)
    assert plan.first_row % plan.block == 0


@pytest.mark.parametrize("n,prefix,client_block,dtype,shape", [
    # Blocks of whole tiles keep the two-dimensional matrix, a whole
    # number of blocks and sublanes high and of 512-column stripes wide:
    # 16 of bf16 (the two ResNet cells, the first with its padded last
    # block), 8 and 24 of float32.
    (1000, 250, 25, jnp.bfloat16, (752, 4_903_424)),
    (768, 192, 24, jnp.bfloat16, (576, 4_903_424)),
    (21, 3, 10, jnp.float32, (24, 4_904_448)),
    (768, 192, 24, jnp.float32, (576, 4_903_424)),
    # Blocks under a tile: a row a plane, no padding row, a whole number
    # of the plane finish's blocks across.
    (10, 2, 1, jnp.bfloat16, (8, 38_320, 128)),
    (10, 2, 3, jnp.float32, (8, 38_320, 128)),
    (26, 2, 15, jnp.bfloat16, (24, 38_336, 128)),
])
def test_compact_matrix_is_two_dimensional_for_blocks_of_whole_tiles(
        n, prefix, client_block, dtype, shape):
    from blades_tpu.ops.pallas_select import plane_cols, stripe_cols
    from blades_tpu.parallel.streamed import block_plan, compact_matrix

    d = 4_903_242    # ResNet-10's
    plan = block_plan(n, prefix, client_block, dtype, compact=True)
    got, cols = compact_matrix(plan, n - prefix, d)
    assert got == shape and plan.planes == (len(shape) == 3)
    if plan.planes:
        assert cols == plane_cols(n - prefix)
        assert got[0] == n - prefix and got[1] * 128 % cols == 0
        assert 0 <= got[1] * 128 - d < cols
    else:
        assert cols == stripe_cols(got[0])
        assert got[0] % plan.block == 0 == got[0] % 8 and got[1] % cols == 0


N_RAGGED, F_RAGGED = 21, 3


@pytest.fixture(scope="module")
def ragged_data():
    from blades_tpu.data import DatasetCatalog

    ds = DatasetCatalog.get_dataset("mnist", num_clients=N_RAGGED)
    return (jnp.array(ds.train.x), jnp.array(ds.train.y),
            jnp.array(ds.train.lengths),
            make_malicious_mask(N_RAGGED, F_RAGGED))


def _ragged_fr():
    # Client momentum: a lane trained twice would show in client_opt.
    task = TaskSpec(model="mlp", lr=0.1, momentum=0.9,
                    input_shape=(28, 28, 1)).build()
    server = Server.from_config(aggregator="Median",
                                num_byzantine=F_RAGGED, lr=1.0)
    adv = get_adversary("ALIE", num_clients=N_RAGGED,
                        num_byzantine=F_RAGGED)
    return FedRound(task=task, server=server, adversary=adv, batch_size=8,
                    health_check=True)


@pytest.fixture(scope="module")
def ragged_dense(ragged_data):
    fr = _ragged_fr()
    st = fr.init(jax.random.PRNGKey(0), N_RAGGED)
    return jax.jit(fr.step)(st, *ragged_data, jax.random.PRNGKey(5))


@pytest.mark.parametrize("matrix", ["full", "compact", "compact_tile_copy"])
def test_ragged_round_matches_dense_bit_for_bit(ragged_data, ragged_dense,
                                                matrix, monkeypatch):
    """21 clients, 3 malicious, client_block 10 at f32 storage: neither n
    nor the prefix is a multiple of the 8-lane block, and the last block
    is padded.  Full matrix: training starts at lane 0 (the block
    boundary under 3), three blocks of 8 of which the last starts at lane
    13 with 3 surplus lanes.  Compact (the TPU path, its kernel
    interpreted here): training starts at lane 3, the last of three
    blocks has 6 surplus lanes, and the 18 benign rows sit in a 24-row
    matrix; once stored by the general store and once by the chip's tile
    copy (ops/pallas_store.py, interpreted here), which drops the surplus
    rows inside the copy.  All equal the dense round: the aggregate, the
    server state, the losses, and client_opt, each client trained exactly
    once.  And one executable serves all three blocks."""
    import functools

    fr = _ragged_fr()
    st_a, m_a = ragged_dense
    want = {"store_blocks": 3, "store_blocks_aligned": 0, "surplus_lanes": 3}
    tile_copies = []
    if matrix != "full":
        from blades_tpu.ops import pallas_round, pallas_select

        monkeypatch.setattr(pallas_select, "kernel_applicable",
                            lambda n, d: True)
        monkeypatch.setattr(
            pallas_round, "fused_finish_compact",
            functools.partial(pallas_round.fused_finish_compact,
                              interpret=True))
        want = {"store_blocks": 3, "store_blocks_aligned": 3,
                "surplus_lanes": 6, "elided_lanes": F_RAGGED}
    if matrix == "compact_tile_copy":
        from blades_tpu.ops import pallas_store

        def interpreted(*args, **kw):
            tile_copies.append((args[1].shape, kw["surplus"]))
            return store(*args, **kw, interpret=True)

        store = pallas_store.store_row_block
        monkeypatch.setattr(pallas_store, "store_row_block", interpreted)
        # The store's own binding of the shared gate.
        monkeypatch.setattr(pallas_store, "kernel_applicable",
                            lambda n, d: True)
    step = streamed_step(fr, client_block=10, d_chunk=10_000,
                         update_dtype=jnp.float32,
                         malicious_prefix=F_RAGGED)
    st_b = fr.init(jax.random.PRNGKey(0), N_RAGGED)
    st_b, m_b = step(st_b, *ragged_data, jax.random.PRNGKey(5))

    assert {k: int(m_b[k]) for k in want} == want
    assert ("elided_lanes" in m_b) == (matrix != "full")
    # ONE trace, ONE executable, whatever the last block's length.
    assert step.train_block._cache_size() == 1
    d = sum(p.size for p in jax.tree.leaves(st_b.server.params))
    assert tile_copies == [((8, d), 6)] * (matrix == "compact_tile_copy")
    # Surplus lanes reach neither the loss nor the health count.
    np.testing.assert_array_equal(np.asarray(m_a["train_loss"]),
                                  np.asarray(m_b["train_loss"]))
    assert int(m_b["num_unhealthy"]) == int(m_a["num_unhealthy"]) == 0
    if matrix == "full":  # the chunked finish: bit for bit, as at n=8
        for a, b in zip(jax.tree.leaves(st_a.server.params),
                        jax.tree.leaves(st_b.server.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:  # the fused kernel's reduction order may differ in the last ulp
        ravel, _, _ = ravel_fn(st_a.server.params)
        np.testing.assert_allclose(
            np.asarray(ravel(st_a.server.params)),
            np.asarray(ravel(st_b.server.params)), atol=1e-6, rtol=1e-5)
    # Every trained lane's optimizer state is what the dense round left:
    # the surplus lanes' second training was written back as read.
    first = 0 if matrix == "full" else F_RAGGED
    leaves = list(zip(jax.tree.leaves(st_a.client_opt),
                      jax.tree.leaves(st_b.client_opt)))
    assert any(np.asarray(a).any() for a, _ in leaves)  # momentum is there
    for a, b in leaves:
        np.testing.assert_array_equal(np.asarray(a)[first:],
                                      np.asarray(b)[first:])


@pytest.mark.parametrize("client_block,prefix,want", [
    # (store_blocks, store_blocks_aligned, surplus_lanes) at n=8, f32
    (2, None, (4, 0, 0)),   # below a tile: four 2-lane stores, none whole
    (4, None, (2, 0, 0)),
    (3, None, (3, 0, 1)),   # 3 + 3 + a last block that starts a lane early
    (2, F, (3, 0, 0)),      # the first block elided
    (8, None, (1, 1, 0)),   # one whole f32 tile at row 0
])
def test_store_counters_follow_the_plan(data, client_block, prefix, want):
    x, y, ln, mal = data
    fr = make_fr("Median", "ALIE")
    st = fr.init(jax.random.PRNGKey(0), N)
    step = streamed_step(fr, client_block=client_block, d_chunk=10_000,
                         update_dtype=jnp.float32, malicious_prefix=prefix)
    _, m = step(st, x, y, ln, mal, jax.random.PRNGKey(7))
    assert (int(m["store_blocks"]), int(m["store_blocks_aligned"]),
            int(m["surplus_lanes"])) == want
    assert isinstance(m["store_blocks"], np.integer)  # host-side stamps
    assert step.train_block._cache_size() == 1


@pytest.mark.parametrize("n_wide,want_ops", [
    (64, {"multiply", "add"}),            # 48 benign lanes: three blocks
    (72, {"multiply", "add", "minimum"}),  # 54: a padded last block
])
def test_store_row_is_an_unsigned_multiple_of_the_block(data, n_wide,
                                                        want_ops):
    """The lowered block computes the matrix row from uint32 index x B
    and feeds it to the store unwrapped: no signed compare, no select
    (JAX wraps a signed dynamic index in select(i < 0, i + rows, i)).
    With a padded last block the row is clamped by an unsigned
    minimum, which is still no select."""
    import re

    from blades_tpu.parallel.streamed import block_plan

    x, y, ln, mal = data
    fr = make_fr("Median", "ALIE")
    st = fr.init(jax.random.PRNGKey(0), n_wide)
    wide = jax.tree.map(lambda a: jnp.concatenate([a] * (n_wide // N)),
                        (x, y, ln, mal))
    step = streamed_step(fr, client_block=25, d_chunk=10_000)
    plan = block_plan(n_wide, 16, 25, jnp.bfloat16, compact=True)
    assert (plan.block, plan.tile) == (16, 16)
    rows = plan.blocks * plan.block
    d = sum(p.size for p in jax.tree.leaves(st.server.params))
    keys = jax.random.split(jax.random.PRNGKey(0), n_wide)
    text = step.train_block.lower(
        jnp.zeros((rows, d), jnp.bfloat16), st.client_opt, st.server.params,
        *wide, keys, keys, np.uint32(1), plan=plan).as_text()
    (store,) = [ln_ for ln_ in text.splitlines()
                if "dynamic_update_slice" in ln_ and f"{rows}x{d}xbf16" in ln_]
    row = re.search(r"dynamic_update_slice %\S+, %\S+, (%\w+),", store)
    # SSA names are per function: keep @main's lines down to the store.
    main = text[text.index("@main("):text.index(store)]
    defs = {m.group(1): m.group(2) for m in
            re.finditer(r"(%\w+) = (.*)", main)}
    # Walk back from the store's row operand: adds and multiplies of
    # ui32 scalars down to the block's index and the constant B.
    seen, todo, ops = set(), [row.group(1)], []
    while todo:
        name = todo.pop()
        if name in seen or name not in defs:
            continue
        seen.add(name)
        ops.append(defs[name])
        todo += re.findall(r"%\w+", defs[name].split(":")[0])
    assert all("tensor<ui32>" in op for op in ops)
    assert not any("select" in op or "compare" in op for op in ops)
    assert want_ops <= {m.group(1) for op in ops for m in
                        [re.match(r"stablehlo\.(\w+)", op)] if m}
    assert any("stablehlo.constant dense<16> : tensor<ui32>" in op
               for op in ops)


@pytest.mark.parametrize("aggregator,kw", [
    # DP: the surplus lane's f32 row norm must not reach the clip.
    ("Median", dict(dp_clip_threshold=0.05, dp_noise_factor=0.5)),
    # Row geometry: the full matrix, losses and norms through its finish.
    ("Multikrum", dict(health_check=True)),
])
def test_padded_last_block_equals_an_even_split(data, aggregator, kw):
    """Values do not depend on the block partition: n=8 in blocks of 3
    (3 + 3 + a last block that starts one lane early and drops that
    lane's row, loss, norm and optimizer state) equals n=8 in blocks of
    4, bit for bit, on the paths the dense round cannot pin (DP's
    per-chunk noise keys) or that read the per-lane vectors."""
    x, y, ln, mal = data
    fr = make_fr(aggregator, "ALIE", **kw)
    out = []
    for client_block in (3, 4):
        st = fr.init(jax.random.PRNGKey(0), N)
        step = streamed_step(fr, client_block=client_block, d_chunk=10_000,
                             update_dtype=jnp.float32)
        out.append(step(st, x, y, ln, mal, jax.random.PRNGKey(11)))
    (st_a, m_a), (st_b, m_b) = out
    assert (int(m_a["surplus_lanes"]), int(m_b["surplus_lanes"])) == (1, 0)
    for a, b in zip(jax.tree.leaves((st_a, {k: m_a[k] for k in (
                        "train_loss", "update_norm_mean", "agg_norm")})),
                    jax.tree.leaves((st_b, {k: m_b[k] for k in (
                        "train_loss", "update_norm_mean", "agg_norm")}))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

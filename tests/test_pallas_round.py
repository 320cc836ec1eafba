"""Fused streamed-finish kernel (ops/pallas_round.py) in interpret mode.

The kernel is TPU-only in production (``should_use`` gates on the
backend); these tests run it through the pallas interpreter on the CPU
mesh and check it against the plain-jnp reference semantics the chunked
finish implements: forge (ALIE/IPM) -> aggregate (Mean/Median/
Trimmedmean), stripe-local sanitize, row norms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blades_tpu.adversaries.base import benign_mean_std
from blades_tpu.ops.pallas_round import fused_finish
from blades_tpu.ops.pallas_select import (
    _BLOCK_D,
    plane_cols,
    stripe_cols,
    stripe_compiler_params,
    stripe_padded,
)


def _ref_forge(x, mal, forge, round_bf16=False):
    mean, std = benign_mean_std(x, mal)
    if forge is None:
        return x
    if forge[0] == "alie":
        forged = mean + forge[1] * std
    else:
        forged = -forge[1] * mean
    if round_bf16:
        forged = forged.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where(mal[:, None], forged, x)


def _ref_agg(x, agg):
    n = x.shape[0]
    if agg[0] == "mean":
        return x.mean(axis=0)
    s = jnp.sort(x, axis=0)
    if agg[0] == "median":
        return (s[(n - 1) // 2] + s[n // 2]) / 2
    k = agg[1]
    return s[k:n - k].mean(axis=0)


# ---------------------------------------------------------------------------
# The stripe's width follows the matrix's height (pallas_select.stripe_cols)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [64, 512, 576, 752, 2048])
def test_stripe_cols_is_512_for_tall_matrices(rows):
    """The federations of hundreds of clients compile the programs they
    always did."""
    assert stripe_cols(rows) == _BLOCK_D == 512


@pytest.mark.parametrize("rows", [8, 16])
def test_stripe_cols_is_wide_for_short_matrices(rows):
    cols = stripe_cols(rows)
    assert cols > 512 and cols % 512 == 0
    # Rows pad to sublanes inside the call: the width is the padded height's.
    assert stripe_cols(rows - 3) == cols


def test_stripe_cols_never_widens_with_height():
    widths = [stripe_cols(rows) for rows in range(1, 2049)]
    assert all(a >= b for a, b in zip(widths, widths[1:]))
    assert all(w >= 512 and w % 512 == 0 for w in widths)


@pytest.mark.parametrize("rows", [8, 16, 24, 64, 256, 512, 752, 2048])
def test_stripe_vmem_limit_can_be_granted(rows):
    """What stripe_compiler_params asks Mosaic for at the rule's width
    (eight stripes and sixteen one-row residents, or the 16 MiB that
    stand by default) is far under a v5e core's 128 MiB at every height:
    no width asks for what cannot be had, and a short matrix's wide
    stripe asks for no more than a tall one's."""
    limit = stripe_compiler_params(rows, cols=stripe_cols(rows))
    assert (16 << 20) <= limit.vmem_limit_bytes <= (8 * 2048 + 128) * 512 * 4
    if rows <= 752:
        assert limit.vmem_limit_bytes == 16 << 20   # the cells' programs


@pytest.mark.parametrize("rows,d", [(8, 1000), (8, 413_959_168), (752, 4_903_242)])
def test_stripe_padded_is_whole_stripes(rows, d):
    cols, padded = stripe_cols(rows), stripe_padded(d, rows)
    assert padded % cols == 0 and 0 <= padded - d < cols


def _widths_case(rows, stripes, off):
    """d just under / at / just over ``stripes`` wide stripes."""
    return rows, stripes * stripe_cols(rows) + off


# Every (height, width) edge once, the aggregator x forge x storage grid
# spread over them (each column is computed alone and does not know the
# stripe: an edge is an edge for all of them).
_WIDTH_GRID = [
    (*_widths_case(8, 1, -1), ("median",), ("alie", 0.7), jnp.bfloat16),
    (*_widths_case(8, 1, 0), ("trimmed", 2), ("ipm", 1.5), jnp.float32),
    (*_widths_case(8, 1, 1), ("mean",), ("alie", 0.7), jnp.float32),
    (*_widths_case(8, 2, -1), ("trimmed", 2), ("alie", 0.7), jnp.bfloat16),
    (*_widths_case(8, 2, 0), ("median",), ("ipm", 1.5), jnp.bfloat16),
    (*_widths_case(8, 2, 1), ("median",), ("alie", 0.7), jnp.float32),
    (*_widths_case(16, 1, -1), ("median",), ("ipm", 1.5), jnp.float32),
    (*_widths_case(16, 1, 0), ("mean",), ("ipm", 1.5), jnp.bfloat16),
    (*_widths_case(16, 1, 1), ("trimmed", 3), ("alie", 0.7), jnp.float32),
    (*_widths_case(13, 2, -1), ("median",), ("alie", 0.7), jnp.bfloat16),
    (*_widths_case(16, 2, 0), ("trimmed", 3), ("ipm", 1.5), jnp.bfloat16),
    (*_widths_case(16, 2, 1), ("mean",), ("alie", 0.7), jnp.bfloat16),
    (*_widths_case(24, 1, -1), ("trimmed", 4), ("ipm", 1.5), jnp.bfloat16),
    (*_widths_case(24, 1, 0), ("median",), ("alie", 0.7), jnp.float32),
    (*_widths_case(24, 1, 1), ("median",), ("ipm", 1.5), jnp.bfloat16),
    (*_widths_case(24, 2, -1), ("mean",), ("ipm", 1.5), jnp.float32),
    (*_widths_case(21, 2, 0), ("median",), ("alie", 0.7), jnp.bfloat16),
    (*_widths_case(24, 2, 1), ("trimmed", 4), ("alie", 0.7), jnp.float32),
]


def _width_id(v):
    return getattr(v, "__name__", None) or (
        "-".join(str(p) for p in v) if isinstance(v, tuple) else str(v))


@pytest.mark.parametrize("kernel", ["compact", "full"])
@pytest.mark.parametrize("rows,d,agg,forge,dtype", _WIDTH_GRID, ids=_width_id)
def test_wide_stripe_has_the_bits_of_the_512_column_kernel(
        kernel, rows, d, agg, forge, dtype):
    """The aggregate and the forged row are computed per column and do not
    know the stripe: at the rule's width they have the bits the same call
    gives when forced to 512 columns.  The row norms accumulate across
    stripes, so their summation order follows the width.

    float32 storage is held to 2e-6 here and not to the bit: the
    interpreter's kernel body is an XLA:CPU program, whose reductions over
    the rows are emitted by the block's shape (the forge's float32 mean
    and variance then differ in the last place at some shapes, and bf16
    storage rounds that away).  Mosaic reduces a column's rows the same
    way at any width: tools/chip_kernels.py --sweep compares the bits on
    the chip, in either storage."""
    from blades_tpu.ops import pallas_round

    rng = np.random.default_rng(seed=rows * 7 + d)
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32).astype(dtype)
    if kernel == "compact":
        def call(cols):
            return pallas_round._fused_finish_compact_jit(
                x, None, forged_mult=3, forge=forge, agg=agg, sanitize=True,
                interpret=True, cols=cols)
    else:
        mal = jnp.arange(rows) < max(rows // 4, 1)

        def call(cols):
            agg_vec, sq, bad = pallas_round._fused_finish_jit(
                x, mal, None, forge=forge, agg=agg, sanitize=True,
                interpret=True, cols=cols)
            return agg_vec, sq, bad, agg_vec
    wide, narrow = call(None), call(512)
    assert wide[0].shape == (d,)
    for i in (0, 3):   # the aggregate and the forged row
        if dtype == jnp.bfloat16:
            np.testing.assert_array_equal(
                np.asarray(wide[i]).view(np.uint32),
                np.asarray(narrow[i]).view(np.uint32))
        else:
            np.testing.assert_allclose(np.asarray(wide[i]),
                                       np.asarray(narrow[i]),
                                       rtol=0, atol=2e-6)
    assert not np.asarray(wide[2]).any()
    np.testing.assert_allclose(np.asarray(wide[1]), np.asarray(narrow[1]),
                               rtol=1e-6)


def test_compact_sanitize_is_local_to_the_wide_stripe():
    """The one semantic that follows the width: a non-finite value blanks
    its row over its stripe, which at 8 rows is the wide one.  The flag is
    the 512-column kernel's, and so is every column outside that stripe."""
    from blades_tpu.ops import pallas_round

    rows, width = 8, stripe_cols(8)
    d = 2 * width + 100
    rng = np.random.default_rng(seed=3)
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)
    x = x.at[5, width + 700].set(jnp.nan)       # in the second wide stripe

    def call(x, cols):
        return pallas_round._fused_finish_compact_jit(
            x, None, forged_mult=2, forge=("alie", 0.7), agg=("median",),
            sanitize=True, interpret=True, cols=cols)

    wide, narrow = call(x, None), call(x, 512)
    blanked = call(x.at[5, width:2 * width].set(0), None)
    assert list(np.nonzero(np.asarray(wide[2]))[0]) == [5]
    np.testing.assert_array_equal(np.asarray(wide[2]), np.asarray(narrow[2]))
    outside = np.r_[0:width, 2 * width:d]
    for i in (0, 3):   # the aggregate and the forged row
        np.testing.assert_array_equal(np.asarray(wide[i]),
                                      np.asarray(blanked[i]))
        np.testing.assert_array_equal(np.asarray(wide[i])[outside],
                                      np.asarray(narrow[i])[outside])


# ---------------------------------------------------------------------------
# A matrix whose rows are planes (ISSUE 32): the same body over
# (rows, d // 128, 128), against the two-dimensional compact finish
# ---------------------------------------------------------------------------


def _planes(x):
    """``(rows, d)`` as ``(rows, ceil(d / 128), 128)``, zero columns past
    ``d``: the layout parallel/streamed.py::compact_matrix allocates."""
    rows, d = x.shape
    width = -(-d // 128) * 128
    return jnp.pad(x, ((0, 0), (0, width - d))).reshape(rows, width // 128,
                                                        128)


@pytest.mark.parametrize("rows", [1, 8, 10, 16, 24, 32, 64, 2048])
def test_plane_cols_is_whole_vregs_of_either_storage(rows):
    """A grid step of the plane finish takes s x 128 columns, s a multiple
    of 16 sublanes (whole vregs of bf16 and of float32 storage), never
    more at a taller matrix, and within the VMEM that is granted."""
    cols = plane_cols(rows)
    assert cols % (16 * 128) == 0 and cols >= 16 * 128
    assert plane_cols(rows + 1) <= cols
    limit = stripe_compiler_params(rows, cols=cols).vmem_limit_bytes
    assert (16 << 20) <= limit <= (8 * 2048 + 128) * 2048 * 4
    if rows <= 64:
        assert limit == 16 << 20


_PLANE_AGGS = [("median",), ("trimmed", 2), ("mean",)]
_PLANE_FORGES = [("alie", 0.7), ("ipm", 1.5), ("adaptive", 1.5)]
# Heights 8, 10 and 24 with d on and off a block's edge; the aggregator x
# forge grid whole at every geometry pair, the storage alternating.
_PLANE_GRID = [
    (rows, blocks * plane_cols(rows) + off, agg, forge,
     (jnp.bfloat16, jnp.float32)[(i + j + k) % 2])
    for i, (rows, blocks, off) in enumerate([
        (8, 1, 0), (8, 1, 74), (10, 2, 0), (10, 1, -130), (24, 3, 0),
        (24, 2, 1)])
    for j, agg in enumerate(_PLANE_AGGS)
    for k, forge in enumerate(_PLANE_FORGES)
    if (i + j + k) % 2 == 0 or rows == 8
]


@pytest.mark.parametrize("rows", [3, 8, 10, 16, 24])
def test_plane_row_sums_are_added_in_the_sublanes_order(rows):
    """Where the rows lie on the sublanes Mosaic adds them vreg to vreg
    (8 rows each, the rows past the last as zeros) and then folds the 8
    sublanes as a butterfly of shifts 4, 2, 1 (measured on the chip,
    PERF.md §6, PR 32).  Over row planes the float sums are written out
    in that order, so that both layouts give the forged row the same
    float32 mean and deviation, to the bit."""
    from blades_tpu.ops.pallas_round import _sum_rows

    rng = np.random.default_rng(rows)
    x = (rng.normal(size=(rows, 16, 128))
         * np.exp(2 * rng.normal(size=(rows, 16, 128)))).astype(np.float32)
    padded = np.zeros((-(-rows // 8) * 8, 16, 128), np.float32)
    padded[:rows] = x
    v = padded[:8].copy()
    for group in padded[8:].reshape(-1, 8, 16, 128):
        v = v + group
    want = (((v[0] + v[4]) + (v[2] + v[6]))
            + ((v[1] + v[5]) + (v[3] + v[7])))
    got = np.asarray(jax.jit(_sum_rows)(jnp.asarray(x)))
    assert got.shape == (1, 16, 128)
    np.testing.assert_array_equal(got[0].view(np.uint32),
                                  want.view(np.uint32))
    # Two dimensions: the reduction itself, as ever.
    flat = jnp.asarray(x.reshape(rows, -1))
    np.testing.assert_array_equal(
        np.asarray(_sum_rows(flat)),
        np.asarray(jnp.sum(flat, axis=0, keepdims=True)))


@pytest.mark.parametrize("rows,d,agg,forge,dtype", _PLANE_GRID, ids=_width_id)
def test_plane_finish_equals_the_two_dimensional_compact_finish(
        rows, d, agg, forge, dtype):
    """The same values as (rows, d) and as row planes give the same
    aggregate and forged row, the row norms to 2e-6 relative (float32
    sums in another order), no row flagged.  The plane call returns the
    matrix's whole width: past d lie the zero columns.

    To the bit on the chip, where both layouts add a column's rows in
    one order (``_sum_rows``; ``tools/chip_kernels.py``'s ``plane_*``
    cases hold ``same_bits`` there).  Here the interpreter runs the
    two-dimensional body as an XLA:CPU program that adds the rows in an
    order of its own, so the forge's float32 mean and deviation may
    differ in the last place: float32 storage is held to 2e-6, as in the
    widths' test above, and on bf16 storage that last place flips the
    forged row's rounding in a column of some hundreds, by one bf16
    place (and with it a Median or a kept sum that lands on the forged
    row): every other column is equal to the bit."""
    from blades_tpu.ops import pallas_round

    rng = np.random.default_rng(seed=rows * 11 + d)
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32).astype(dtype)
    x3 = _planes(x)
    noise = noise3 = None
    if forge[0] == "adaptive":
        noise = jax.random.uniform(jax.random.PRNGKey(d), (d,), jnp.float32)
        noise3 = jnp.pad(noise, (0, x3[0].size - d))

    def call(x, noise):
        return pallas_round._fused_finish_compact_jit(
            x, noise, forged_mult=3, forge=forge, agg=agg, sanitize=True,
            interpret=True)

    flat, plane = call(x, noise), call(x3, noise3)
    assert plane[0].shape == plane[3].shape == (x3[0].size,)
    for i in (0, 3):   # the aggregate and the forged row
        got, want = np.asarray(plane[i])[:d], np.asarray(flat[i])
        if dtype == jnp.bfloat16 and (i == 3 or agg[0] == "median"):
            off = got.view(np.uint32) != want.view(np.uint32)
            assert off.mean() <= 1e-2
            np.testing.assert_allclose(got[off], want[off], rtol=2.0 ** -7,
                                       atol=1e-6)
        else:   # float32 sums, or float32 storage
            np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 if
                                       dtype == jnp.float32 else 2.0 ** -8)
    assert plane[1].shape == plane[2].shape == (rows,)
    assert not np.asarray(plane[2]).any()
    np.testing.assert_allclose(np.asarray(plane[1]), np.asarray(flat[1]),
                               rtol=2e-6)


@pytest.mark.parametrize("rows", [8, 10, 24])
def test_plane_sanitize_blanks_a_poisoned_row_over_its_block_only(rows):
    """sanitize is local to a grid step's block, as it is local to a
    stripe in two dimensions: a non-finite value blanks its row over the
    plane_cols(rows) columns of its block, the flag is the 2-D kernel's,
    and every column outside that block has the 2-D kernel's bits."""
    from blades_tpu.ops import pallas_round

    cols = plane_cols(rows)
    d = 2 * cols + 300
    rng = np.random.default_rng(seed=rows)
    x = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)
    at = cols + 700                             # in the second block
    x = x.at[5, at].set(jnp.inf)

    def call(x):
        return pallas_round._fused_finish_compact_jit(
            x, None, forged_mult=2, forge=("alie", 0.7), agg=("median",),
            sanitize=True, interpret=True)

    plane, flat = call(_planes(x)), call(x)
    blanked = call(_planes(x.at[5, cols:2 * cols].set(0)))
    assert list(np.nonzero(np.asarray(plane[2]))[0]) == [5]
    np.testing.assert_array_equal(np.asarray(plane[2]), np.asarray(flat[2]))
    # The 2-D kernel blanks the row over ITS stripe, which may reach
    # across this block's edge: compare where neither blanked anything.
    stripe = stripe_cols(rows)
    start = at // stripe * stripe
    outside = np.r_[0:min(cols, start), max(2 * cols, start + stripe):d]
    for i in (0, 3):
        np.testing.assert_array_equal(np.asarray(plane[i]),
                                      np.asarray(blanked[i]))
        np.testing.assert_array_equal(np.asarray(plane[i])[outside],
                                      np.asarray(flat[i])[outside])


def test_plane_finish_takes_no_uniforms_unless_the_forge_is_adaptive():
    """At d = 4.1e8 a (1, d) float32 zero row is 1.66 GB: the plane call
    hands the kernel the uniforms only where a forge reads them.  The
    two-dimensional call keeps its three inputs (the cells' programs)."""
    from blades_tpu.ops import pallas_round

    x = jnp.zeros((8, plane_cols(8)), jnp.bfloat16)

    def operands(x, forge, noise=None):
        jaxpr = jax.make_jaxpr(lambda x, r: (
            pallas_round._fused_finish_compact_jit(
                x, r, forged_mult=2, forge=forge, interpret=True)))(x, noise)
        (call,) = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                   if e.primitive.name == "pallas_call"]
        return len(call.invars)

    noise = jnp.zeros((x.shape[1],), jnp.float32)
    assert operands(_planes(x), ("alie", 0.7)) == 2
    assert operands(_planes(x), ("ipm", 1.5)) == 2
    assert operands(_planes(x), ("adaptive", 1.5), noise) == 3
    assert operands(x, ("alie", 0.7)) == 3


def test_plane_finish_refuses_a_plane_that_is_not_128_lanes():
    from blades_tpu.ops import pallas_round

    with pytest.raises(ValueError, match="row-plane matrix"):
        pallas_round._fused_finish_compact_jit(
            jnp.zeros((8, 16, 64), jnp.float32), None, forged_mult=2,
            forge=("alie", 0.7), interpret=True)


# ---------------------------------------------------------------------------
# On row planes the Median orders each column's keys by a sorting network
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", range(1, 13))
def test_sorting_network_sorts_every_zero_one_vector(n):
    """The 0-1 principle: a network of compare-exchanges that sorts every
    vector of 0s and 1s of length n sorts every vector of length n.
    Batcher's network has 19 exchanges at 8 inputs, 63 at 16, 191 at 32."""
    from blades_tpu.ops.pallas_round import sorting_network

    net = sorting_network(n)
    assert all(0 <= i < j < n for i, j in net)
    v = (np.arange(1 << n)[:, None] >> np.arange(n)) & 1
    for i, j in net:
        v[:, i], v[:, j] = (np.minimum(v[:, i], v[:, j]),
                            np.maximum(v[:, i], v[:, j]))
    assert (np.diff(v, axis=1) >= 0).all()
    assert [len(sorting_network(r)) for r in (8, 16, 32)] == [19, 63, 191]


def _select_values(rng, shape, wide):
    """Values drawn from a small pool, so that columns hold ties: the bf16
    grid, -0 and +0, both infinities and a NaN of either sign, and with
    full keys float32 values off the bf16 grid too."""
    grid = (rng.normal(size=12) * np.exp(2 * rng.normal(size=12)))
    pool = np.concatenate([
        np.asarray(jnp.asarray(grid, jnp.bfloat16).astype(jnp.float32)),
        [0.0, -0.0, np.inf, -np.inf, np.nan, -np.nan]]).astype(np.float32)
    if wide:
        pool = np.concatenate([pool, rng.normal(size=6).astype(np.float32)])
    return rng.choice(pool, size=shape)


@pytest.mark.parametrize("wide", [False, True], ids=["keys16", "keys32"])
@pytest.mark.parametrize("rows", [2, 3, 8, 10, 16, 24, 32])
def test_network_ranks_are_the_radix_searchs_keys(rows, wide):
    """The Median's two ranks, read off the rows sorted by the network and
    the forged key counted ``mult`` times, are the keys the radix search
    finds (``_kth_key16_mult`` then ``_next_key16_above_mult``, or the
    32-step pair for float32 storage), with rows past ``num_real`` at +inf
    as the kernel masks them.  With the whole network every rank of the
    multiset is that of a sort of its keys."""
    from blades_tpu.ops import pallas_round as pr

    keys_of = pr._keys_of if wide else pr._keys16_of
    kth = pr._kth_key_mult if wide else pr._kth_key16_mult
    nxt = pr._next_key_above_mult if wide else pr._next_key16_above_mult
    rng = np.random.default_rng(2 * rows + wide)
    for mult in (1, 2, 3):
        for nb in sorted({rows, rows - 1} - {0}):
            x = _select_values(rng, (rows, 2, 128), wide)
            x[nb:] = np.inf
            keys = keys_of(jnp.asarray(x))
            fkey = keys_of(jnp.asarray(_select_values(rng, (1, 2, 128),
                                                      wide)))
            k1, k2 = (nb + mult - 1) // 2, (nb + mult) // 2
            net = pr.median_network(x.shape, nb, mult, not wide,
                                    ("median",))
            got = pr._ranks_by_network(keys, fkey, mult, net, (k1, k2),
                                       wide=wide)
            v1 = kth(keys, k1, fkey, mult)
            cnt_le = (jnp.sum((keys <= v1).astype(jnp.int32), axis=0,
                              keepdims=True)
                      + mult * (fkey <= v1).astype(jnp.int32))
            v2 = jnp.where(cnt_le >= k2 + 1, v1, nxt(keys, v1, fkey))
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(v1))
            np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(v2))
            every = pr._ranks_by_network(
                keys, fkey, mult, pr.sorting_network(rows),
                range(rows + mult), wide=wide)
            want = np.sort(np.concatenate(
                [np.asarray(keys), np.repeat(np.asarray(fkey), mult, 0)]),
                axis=0)
            np.testing.assert_array_equal(
                np.concatenate([np.asarray(v) for v in every]), want)


def test_finish_network_rows_follows_the_shape():
    """The network runs for the Median on row planes wherever it costs
    fewer vector operations than the radix search (two an exchange
    against about three a row a bit): the language-model cells' 8 rows,
    not the tall matrices' (rows, d) layout, nor the Trimmed mean, nor
    planes so tall that Batcher's n log^2 n exchanges outgrow the
    search."""
    from blades_tpu.ops.pallas_round import finish_network_rows

    bf16, f32 = jnp.bfloat16, jnp.float32
    median = dict(agg=("median",))
    assert finish_network_rows((8, 3234080, 128), bf16, forged_mult=2,
                               **median) == 8
    assert finish_network_rows((24, 32, 128), f32, forged_mult=8,
                               **median) == 24
    assert finish_network_rows((752, 4904960), bf16, forged_mult=250,
                               num_real=750, **median) == 0
    assert finish_network_rows((8, 80, 128), bf16, forged_mult=2,
                               agg=("trimmed", 2)) == 0
    assert finish_network_rows((2048, 16, 128), bf16, forged_mult=683,
                               **median) == 0


@pytest.mark.parametrize("n,d", [(24, 1000), (17, 700), (64, 2048)])
@pytest.mark.parametrize(
    "forge,agg",
    [
        (("alie", 0.7), ("median",)),
        (("ipm", 1.5), ("trimmed", 3)),
        (None, ("mean",)),
    ],
)
def test_fused_matches_reference(n, d, forge, agg):
    rng = np.random.default_rng(seed=n + d)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    mal = jnp.asarray(rng.random(n) < 0.25)
    ref = _ref_forge(x, mal, forge)
    agg_vec, sq, bad = fused_finish(x, mal, forge=forge, agg=agg,
                                    interpret=True)
    np.testing.assert_allclose(agg_vec, _ref_agg(ref, agg),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sq, (ref ** 2).sum(axis=1),
                               rtol=1e-4, atol=1e-4)
    assert not bool(bad.any())


@pytest.mark.parametrize("forge", [("alie", 0.7), ("ipm", 2.0), None])
def test_fused_bf16_sixteen_step_radix(forge):
    """bf16 storage: forged rows round to storage precision, selection is
    exact in the 16-bit key space."""
    n, d = 32, 1500
    rng = np.random.default_rng(seed=5)
    x16 = jnp.asarray(rng.normal(size=(n, d)), jnp.float32).astype(jnp.bfloat16)
    mal = jnp.asarray(rng.random(n) < 0.25)
    ref = _ref_forge(x16.astype(jnp.float32), mal, forge, round_bf16=True)
    agg_vec, _, _ = fused_finish(x16, mal, forge=forge, agg=("median",),
                                 interpret=True)
    np.testing.assert_array_equal(
        np.asarray(agg_vec), np.asarray(_ref_agg(ref, ("median",)))
    )


def test_fused_adaptive_matches_adversary_hook():
    """('adaptive', b) with pre-drawn uniforms reproduces the dense
    AdaptiveAdversary.on_updates_ready forge exactly (same key)."""
    from blades_tpu.adversaries import get_adversary

    n, d = 24, 900
    rng = np.random.default_rng(seed=11)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    mal = jnp.asarray(rng.random(n) < 0.25)
    key = jax.random.PRNGKey(42)
    adv = get_adversary({"type": "Adaptive", "b": 2.0},
                        num_clients=n, num_byzantine=int(mal.sum()))
    ref = adv.on_updates_ready(x, mal, key)
    noise = jax.random.uniform(key, (d,), jnp.float32)
    agg_vec, _, _ = fused_finish(x, mal, noise, forge=("adaptive", 2.0),
                                 agg=("median",), interpret=True)
    np.testing.assert_allclose(agg_vec, _ref_agg(ref, ("median",)),
                               rtol=1e-5, atol=1e-5)


def test_fused_adaptive_bf16_matches_rounded_reference():
    """The production combination: adaptive forge + bf16 storage — the
    forged row rounds to bf16 and the 16-step radix selects among the
    rounded values exactly."""
    from blades_tpu.adversaries import get_adversary

    n, d = 24, 900
    rng = np.random.default_rng(seed=13)
    x16 = jnp.asarray(rng.normal(size=(n, d)), jnp.float32).astype(jnp.bfloat16)
    mal = jnp.asarray(rng.random(n) < 0.25)
    key = jax.random.PRNGKey(21)
    adv = get_adversary({"type": "Adaptive", "b": 2.0},
                        num_clients=n, num_byzantine=int(mal.sum()))
    xf = x16.astype(jnp.float32)
    ref = adv.on_updates_ready(xf, mal, key)
    # forged rows round to storage precision in the kernel
    ref = jnp.where(mal[:, None], ref.astype(jnp.bfloat16).astype(jnp.float32),
                    ref)
    noise = jax.random.uniform(key, (d,), jnp.float32)
    agg_vec, _, _ = fused_finish(x16, mal, noise, forge=("adaptive", 2.0),
                                 agg=("median",), interpret=True)
    np.testing.assert_array_equal(
        np.asarray(agg_vec), np.asarray(_ref_agg(ref, ("median",)))
    )


def test_fused_adaptive_requires_noise():
    x = jnp.zeros((8, 600), jnp.float32)
    with pytest.raises(ValueError, match="forge_noise"):
        fused_finish(x, jnp.zeros((8,), bool), forge=("adaptive", 2.0),
                     agg=("mean",), interpret=True)


def test_fused_sanitize_stripe_local():
    """A non-finite value zeroes its row within that stripe only (same
    chunk-local semantics as the streamed chunk path; the stripe is as
    wide as the matrix's height allows), and the row is reported
    unhealthy."""
    n = 16
    STRIPE = stripe_cols(n)
    d = STRIPE + 40
    rng = np.random.default_rng(seed=7)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    x = x.at[3, 2].set(jnp.inf)
    mal = jnp.zeros((n,), bool)
    agg_vec, sq, bad = fused_finish(x, mal, forge=None, agg=("mean",),
                                    sanitize=True, interpret=True)
    clean = x.at[3, :STRIPE].set(0.0)
    np.testing.assert_allclose(agg_vec, clean.mean(axis=0), rtol=1e-5,
                               atol=1e-6)
    assert list(np.nonzero(np.asarray(bad))[0]) == [3]


def test_fused_rejects_overtrimming():
    x = jnp.zeros((8, 600), jnp.float32)
    with pytest.raises(ValueError, match="trimmed"):
        fused_finish(x, jnp.zeros((8,), bool), agg=("trimmed", 4),
                     interpret=True)


def test_streamed_step_fused_branch_matches_chunked(monkeypatch):
    """Force the streamed round onto the fused finish (interpret mode)
    and check the whole round matches the chunked finish."""
    import functools

    from blades_tpu import parallel
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.ops import pallas_round

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: True)
    monkeypatch.setattr(
        pallas_round, "fused_finish",
        functools.partial(pallas_round.fused_finish, interpret=True),
    )

    n, f = 12, 3
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n, 8)), jnp.int32)
    lengths = jnp.full((n,), 8, jnp.int32)
    mal = make_malicious_mask(n, f)
    key = jax.random.PRNGKey(3)

    state0 = fr.init(jax.random.PRNGKey(0), n)
    step_fused = parallel.streamed.streamed_step(
        fr, client_block=4, update_dtype=jnp.float32, donate=False)
    s1, m1 = step_fused(state0, x, y, lengths, mal, key)

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: False)
    state0 = fr.init(jax.random.PRNGKey(0), n)
    step_chunked = parallel.streamed.streamed_step(
        fr, client_block=4, update_dtype=jnp.float32, donate=False)
    s2, m2 = step_chunked(state0, x, y, lengths, mal, key)

    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5)
    p1 = jax.tree.leaves(s1.server.params)
    p2 = jax.tree.leaves(s2.server.params)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# Benign-compacted finish (virtual forged-row multiplicity)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb,mult,d", [
    (24, 8, 1000), (17, 5, 700), (18, 6, 600), (11, 13, 520)])
@pytest.mark.parametrize(
    "forge,agg",
    [
        (("alie", 0.7), ("median",)),
        (("alie", 0.7), ("mean",)),
        (("ipm", 1.5), ("trimmed", 3)),
        (("ipm", 1.5), ("median",)),
    ],
)
def test_compact_matches_full_kernel(nb, mult, d, forge, agg):
    """The compact kernel over nb benign rows + a virtual forged row of
    multiplicity `mult` must equal the FULL kernel over the
    (nb + mult, d) matrix whose first `mult` rows are malicious."""
    from blades_tpu.ops.pallas_round import fused_finish_compact

    if agg[0] == "trimmed" and nb + mult <= 2 * agg[1]:
        pytest.skip("overtrimmed")
    rng = np.random.default_rng(seed=nb * 31 + d)
    xb = jnp.asarray(rng.normal(size=(nb, d)), jnp.float32)
    # Full matrix: malicious prefix rows hold garbage the forge replaces.
    garbage = jnp.asarray(rng.normal(size=(mult, d)) * 50.0, jnp.float32)
    x_full = jnp.concatenate([garbage, xb], axis=0)
    mal = jnp.arange(nb + mult) < mult

    a_full, sq_full, bad_full = fused_finish(
        x_full, mal, forge=forge, agg=agg, sanitize=True, interpret=True)
    a_c, sq_c, bad_c, forged = fused_finish_compact(
        xb, forged_mult=mult, forge=forge, agg=agg, sanitize=True,
        interpret=True)

    np.testing.assert_allclose(np.asarray(a_full), np.asarray(a_c),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sq_full[mult:]), np.asarray(sq_c),
                               rtol=1e-6)
    # Malicious rows' norms are ||forged||^2 — reconstructable outside.
    np.testing.assert_allclose(
        np.asarray(sq_full[:mult]),
        np.full(mult, float(forged @ forged)), rtol=1e-5)
    assert not np.asarray(bad_c).any()


def test_compact_bf16_matches_full_bf16():
    from blades_tpu.ops.pallas_round import fused_finish_compact

    nb, mult, d = 24, 8, 800
    rng = np.random.default_rng(3)
    xb = jnp.asarray(rng.normal(size=(nb, d)), jnp.bfloat16)
    x_full = jnp.concatenate(
        [jnp.zeros((mult, d), jnp.bfloat16), xb], axis=0)
    mal = jnp.arange(nb + mult) < mult
    for agg in (("median",), ("trimmed", 5), ("mean",)):
        a_full, _, _ = fused_finish(x_full, mal, forge=("alie", 1.2),
                                    agg=agg, interpret=True)
        a_c, _, _, _ = fused_finish_compact(
            xb, forged_mult=mult, forge=("alie", 1.2), agg=agg,
            interpret=True)
        np.testing.assert_allclose(np.asarray(a_full), np.asarray(a_c),
                                   atol=2e-4, rtol=1e-4)


def test_compact_adaptive_matches_full():
    from blades_tpu.ops.pallas_round import fused_finish_compact

    nb, mult, d = 16, 6, 520
    rng = np.random.default_rng(5)
    xb = jnp.asarray(rng.normal(size=(nb, d)), jnp.float32)
    x_full = jnp.concatenate([jnp.ones((mult, d)) * 9.0, xb], axis=0)
    mal = jnp.arange(nb + mult) < mult
    noise = jnp.asarray(rng.random(d), jnp.float32)
    a_full, _, _ = fused_finish(x_full, mal, noise,
                                forge=("adaptive", 2.0), agg=("median",),
                                interpret=True)
    a_c, _, _, _ = fused_finish_compact(
        xb, noise, forged_mult=mult, forge=("adaptive", 2.0),
        agg=("median",), interpret=True)
    np.testing.assert_allclose(np.asarray(a_full), np.asarray(a_c),
                               atol=1e-5, rtol=1e-5)


def test_compact_mxu_variants_match_default():
    """The MXU radix-count formulation must be BIT-exact vs the VPU one
    (the per-step counts are small integers, exact in f32); the MXU
    stats formulation matches up to f32 reassociation ulps.  These are
    the round-5 radix-headroom candidates (PERF_NOTES_r4: the radix is
    ~43 ms of the ~80 ms compact finish, VPU-bound)."""
    from blades_tpu.ops.pallas_round import fused_finish_compact

    nb, mult, d = 40, 12, 1100
    rng = np.random.default_rng(17)
    for dtype in (jnp.float32, jnp.bfloat16):
        xb = jnp.asarray(rng.normal(size=(nb, d)), dtype)
        for agg in (("median",), ("trimmed", 7), ("mean",)):
            base = fused_finish_compact(
                xb, forged_mult=mult, forge=("alie", 1.5), agg=agg,
                sanitize=True, interpret=True,
                radix_mxu=False, stats_mxu=False)
            counts = fused_finish_compact(
                xb, forged_mult=mult, forge=("alie", 1.5), agg=agg,
                sanitize=True, interpret=True,
                radix_mxu=True, stats_mxu=False)
            # radix_mxu alone: identical selection -> identical outputs.
            for a, b in zip(base, counts):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            allmxu = fused_finish_compact(
                xb, forged_mult=mult, forge=("alie", 1.5), agg=agg,
                sanitize=True, interpret=True,
                radix_mxu=True, stats_mxu=True)
            for a, b in zip(base[:2], allmxu[:2]):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-4, rtol=1e-4)


def test_compact_rejects_forgeless():
    from blades_tpu.ops.pallas_round import fused_finish_compact

    with pytest.raises(ValueError, match="forge"):
        fused_finish_compact(jnp.zeros((8, 600)), forged_mult=2,
                             forge=None, interpret=True)


def test_mxu_finish_env_resolved_per_call(monkeypatch):
    """ADVICE r5 #1: BLADES_TPU_MXU_FINISH is resolved in the un-jitted
    wrapper on EVERY call — toggling the env after the first call must
    switch the mode (the old trace-time read cached the first call's
    resolution under the None statics and silently kept it)."""
    from blades_tpu.ops import pallas_round

    seen = []

    def spy(updates, noise=None, **kw):
        seen.append((kw["radix_mxu"], kw["stats_mxu"]))
        return "sentinel"

    monkeypatch.setattr(pallas_round, "_fused_finish_compact_jit", spy)
    x = jnp.zeros((8, 600))

    monkeypatch.delenv("BLADES_TPU_MXU_FINISH", raising=False)
    assert pallas_round.fused_finish_compact(
        x, forged_mult=2, forge=("alie", 1.5)) == "sentinel"
    monkeypatch.setenv("BLADES_TPU_MXU_FINISH", "counts")
    pallas_round.fused_finish_compact(x, forged_mult=2, forge=("alie", 1.5))
    monkeypatch.setenv("BLADES_TPU_MXU_FINISH", "all")
    pallas_round.fused_finish_compact(x, forged_mult=2, forge=("alie", 1.5))
    monkeypatch.setenv("BLADES_TPU_MXU_FINISH", "")
    pallas_round.fused_finish_compact(x, forged_mult=2, forge=("alie", 1.5))
    assert seen == [(False, False), (True, False), (True, True),
                    (False, False)]
    # Explicit arguments always beat the env.
    monkeypatch.setenv("BLADES_TPU_MXU_FINISH", "all")
    pallas_round.fused_finish_compact(x, forged_mult=2, forge=("alie", 1.5),
                                      radix_mxu=False, stats_mxu=False)
    assert seen[-1] == (False, False)


def test_mxu_finish_config_path_resolved_per_call(monkeypatch):
    """The first-class ``resources(mxu_finish=...)`` path (ISSUE 10
    satellite, extending the PR 4 toggle test): with the env UNSET the
    caller's config-resolved ``mxu_finish`` string selects the mode per
    call; a SET env var — even set AFTER the first call — overrides the
    config value (the explicit per-process escape hatch)."""
    from blades_tpu.ops import pallas_round

    seen = []

    def spy(updates, noise=None, **kw):
        seen.append((kw["radix_mxu"], kw["stats_mxu"]))
        return "sentinel"

    monkeypatch.setattr(pallas_round, "_fused_finish_compact_jit", spy)
    x = jnp.zeros((8, 600))
    monkeypatch.delenv("BLADES_TPU_MXU_FINISH", raising=False)

    for mode in ("", "counts", "all", None):
        pallas_round.fused_finish_compact(
            x, forged_mult=2, forge=("alie", 1.5), mxu_finish=mode)
    assert seen == [(False, False), (True, False), (True, True),
                    (False, False)]
    # A SET env var beats the config value, toggled after first call.
    monkeypatch.setenv("BLADES_TPU_MXU_FINISH", "all")
    pallas_round.fused_finish_compact(
        x, forged_mult=2, forge=("alie", 1.5), mxu_finish="counts")
    assert seen[-1] == (True, True)
    # Even env="" (set-but-empty) is an explicit override, not a fall-
    # through to the config value.
    monkeypatch.setenv("BLADES_TPU_MXU_FINISH", "")
    pallas_round.fused_finish_compact(
        x, forged_mult=2, forge=("alie", 1.5), mxu_finish="all")
    assert seen[-1] == (False, False)


def test_streamed_step_compact_branch_matches_chunked(monkeypatch):
    """Force the streamed round onto the benign-compacted fused finish
    (elided malicious prefix + virtual-multiplicity kernel, interpret
    mode) and check the whole round matches the chunked finish."""
    import functools

    from blades_tpu import parallel
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.ops import pallas_round, pallas_select

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: True)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: True)
    # fused_finish_compact is an un-jitted wrapper (it resolves the
    # BLADES_TPU_MXU_FINISH env per call, ADVICE r5 #1) — partial the
    # wrapper itself to force interpret mode.
    monkeypatch.setattr(
        pallas_round, "fused_finish_compact",
        functools.partial(pallas_round.fused_finish_compact,
                          interpret=True),
    )

    n, f = 12, 4  # f divisible by client_block -> compact path
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n, 8)), jnp.int32)
    lengths = jnp.full((n,), 8, jnp.int32)
    mal = make_malicious_mask(n, f)
    key = jax.random.PRNGKey(3)

    state0 = fr.init(jax.random.PRNGKey(0), n)
    step_compact = parallel.streamed.streamed_step(
        fr, client_block=4, update_dtype=jnp.float32, donate=False,
        malicious_prefix=f)
    s1, m1 = step_compact(state0, x, y, lengths, mal, key)
    # Blocks of 4 lie under float32's storage tile of 8: the 8 benign rows
    # are planes, and the Median orders them by the sorting network.
    assert int(m1["finish_network_rows"]) == 8

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: False)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: False)
    state0 = fr.init(jax.random.PRNGKey(0), n)
    step_chunked = parallel.streamed.streamed_step(
        fr, client_block=4, update_dtype=jnp.float32, donate=False)
    s2, m2 = step_chunked(state0, x, y, lengths, mal, key)

    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.server.params),
                    jax.tree.leaves(s2.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_compact_caller_prepadded_rows_match_autopad():
    """num_real + caller +inf padding (the no-copy giant-scale path) must
    equal the concat-padding path."""
    from blades_tpu.ops.pallas_round import fused_finish_compact

    nb, mult, d = 11, 5, 600  # nb % 8 != 0
    rng = np.random.default_rng(9)
    xb = jnp.asarray(rng.normal(size=(nb, d)), jnp.float32)
    npad = -(-nb // 8) * 8
    x_pad = jnp.concatenate(
        [xb, jnp.full((npad - nb, d), jnp.inf, jnp.float32)], axis=0)
    for agg in (("median",), ("trimmed", 3), ("mean",)):
        a1, sq1, bad1, f1 = fused_finish_compact(
            xb, forged_mult=mult, forge=("alie", 0.9), agg=agg,
            sanitize=True, interpret=True)
        a2, sq2, bad2, f2 = fused_finish_compact(
            x_pad, forged_mult=mult, forge=("alie", 0.9), agg=agg,
            sanitize=True, num_real=nb, interpret=True)
        # 1-ulp tolerance: the two wrappers build wb differently (concat
        # vs arange-compare), and XLA's CPU pipeline reassociates the
        # forge-stat reductions differently around them.
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(sq1), np.asarray(sq2),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(bad1), np.asarray(bad2))
        assert not np.asarray(bad2).any()  # pad +inf rows must not flag
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                                   rtol=1e-5, atol=1e-6)


def test_streamed_step_compact_with_row_padding(monkeypatch):
    """Compact streamed round where nb is NOT a sublane multiple: the
    pre-padded +inf rows must be invisible (parity vs chunked)."""
    import functools

    from blades_tpu import parallel
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.ops import pallas_round
    from blades_tpu.ops import pallas_select

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: True)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: True)
    # fused_finish_compact is an un-jitted wrapper (it resolves the
    # BLADES_TPU_MXU_FINISH env per call, ADVICE r5 #1) — partial the
    # wrapper itself to force interpret mode.
    monkeypatch.setattr(
        pallas_round, "fused_finish_compact",
        functools.partial(pallas_round.fused_finish_compact,
                          interpret=True),
    )

    n, f = 16, 4  # nb = 12 -> padded to 16 rows
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n, 8)), jnp.int32)
    lengths = jnp.full((n,), 8, jnp.int32)
    mal = make_malicious_mask(n, f)
    key = jax.random.PRNGKey(3)

    state0 = fr.init(jax.random.PRNGKey(0), n)
    step_compact = parallel.streamed.streamed_step(
        fr, client_block=4, update_dtype=jnp.float32, donate=False,
        malicious_prefix=f)
    s1, m1 = step_compact(state0, x, y, lengths, mal, key)
    assert int(m1["finish_network_rows"]) == 12   # 12 planes, no padding

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: False)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: False)
    state0 = fr.init(jax.random.PRNGKey(0), n)
    step_chunked = parallel.streamed.streamed_step(
        fr, client_block=4, update_dtype=jnp.float32, donate=False)
    s2, m2 = step_chunked(state0, x, y, lengths, mal, key)

    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.server.params),
                    jax.tree.leaves(s2.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_streamed_round_of_whole_tiles_keeps_the_radix_search(monkeypatch):
    """Blocks of whole storage tiles (8 float32 lanes) keep the matrix
    ``(rows, d)``, the rows on the sublanes, where an exchange across them
    is no plain vector operation: the Median runs the radix search, the
    round stamps ``finish_network_rows`` 0 beside its stripe's width, and
    the round equals the chunked finish's."""
    from blades_tpu import parallel
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.ops import pallas_round, pallas_select

    seen = []
    compact = pallas_round.fused_finish_compact

    def interpreted(updates, *args, **kw):
        seen.append(updates.shape)
        return compact(updates, *args, **kw, interpret=True)

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: True)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: True)
    monkeypatch.setattr(pallas_round, "fused_finish_compact", interpreted)

    n, f = 24, 8
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=1)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n, 8)), jnp.int32)
    lengths = jnp.full((n,), 8, jnp.int32)
    mal = make_malicious_mask(n, f)
    key = jax.random.PRNGKey(5)

    step = parallel.streamed.streamed_step(
        fr, client_block=8, update_dtype=jnp.float32, donate=False,
        malicious_prefix=f)
    s1, m1 = step(fr.init(jax.random.PRNGKey(0), n), x, y, lengths, mal, key)
    assert len(seen) == 1 and len(seen[0]) == 2   # (rows, d_alloc)
    assert int(m1["finish_stripe_cols"]) == stripe_cols(seen[0][0])
    assert int(m1["finish_network_rows"]) == 0
    assert isinstance(m1["finish_network_rows"], np.integer)

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: False)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: False)
    step_chunked = parallel.streamed.streamed_step(
        fr, client_block=8, update_dtype=jnp.float32, donate=False)
    s2, m2 = step_chunked(fr.init(jax.random.PRNGKey(0), n), x, y, lengths,
                          mal, key)
    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.server.params),
                    jax.tree.leaves(s2.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("client_block,blocks,surplus", [(1, 8, 0),
                                                         (3, 3, 1)])
def test_streamed_round_at_8_rows_keeps_a_row_a_plane(
        monkeypatch, client_block, blocks, surplus):
    """The language-model cell's geometry on the MLP: 10 clients, 2 ALIE
    elided, 8 benign rows stored in blocks under a storage tile (of one
    lane, as the cell's; of three, the last one padded).  The matrix
    keeps a row a plane, (8, d_alloc // 128, 128), a whole number of the
    finish's blocks across (no pad inside the call copies it) and with no
    padding row; every store counts as aligned; ONE executable trains and
    stores every block (traced once: one call of ``row_planes``); the
    round equals the chunked finish's; and the
    metrics carry the block's columns as a host int that the row's schema
    knows."""
    import functools

    from blades_tpu import parallel
    from blades_tpu.adversaries import get_adversary, make_malicious_mask
    from blades_tpu.core import FedRound, Server, TaskSpec
    from blades_tpu.obs.schema import ROUND_RECORD_FIELDS
    from blades_tpu.ops import pallas_round, pallas_select, pallas_store

    seen, stored = [], []

    def interpreted(updates, *args, **kw):
        seen.append(updates.shape)
        return compact(updates, *args, **kw, interpret=True)

    def planes_of(upd, tail):
        stored.append(isinstance(upd, jax.Array))
        return row_planes(upd, tail)

    compact = pallas_round.fused_finish_compact
    row_planes = pallas_store.row_planes
    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: True)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: True)
    monkeypatch.setattr(pallas_round, "fused_finish_compact", interpreted)
    monkeypatch.setattr(pallas_store, "row_planes", planes_of)

    n, f = 10, 2
    task = TaskSpec(model="mlp", input_shape=(8, 8, 1), num_classes=10,
                    lr=0.1).build()
    server = Server.from_config(aggregator="Median", lr=0.5)
    adv = get_adversary("ALIE", num_clients=n, num_byzantine=f)
    fr = FedRound(task=task, server=server, adversary=adv, batch_size=4,
                  num_batches_per_round=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 8, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(n, 8)), jnp.int32)
    lengths = jnp.full((n,), 8, jnp.int32)
    mal = make_malicious_mask(n, f)
    key = jax.random.PRNGKey(3)

    build = functools.partial(parallel.streamed.streamed_step, fr,
                              client_block=client_block,
                              update_dtype=jnp.float32, donate=False)
    step = build(malicious_prefix=f)
    s1, m1 = step(fr.init(jax.random.PRNGKey(0), n), x, y, lengths, mal, key)

    width = plane_cols(8)
    d = sum(p.size for p in jax.tree.leaves(s1.server.params))
    assert seen == [(8, -(-d // width) * width // 128, 128)]
    assert int(m1["finish_stripe_cols"]) == width
    assert isinstance(m1["finish_stripe_cols"], np.integer)  # a host stamp
    assert "finish_stripe_cols" in ROUND_RECORD_FIELDS
    # The Median over 8 row planes orders them by the sorting network.
    assert int(m1["finish_network_rows"]) == 8
    assert isinstance(m1["finish_network_rows"], np.integer)
    assert "finish_network_rows" in ROUND_RECORD_FIELDS
    assert (int(m1["store_blocks"]), int(m1["store_blocks_aligned"]),
            int(m1["surplus_lanes"])) == (blocks, blocks, surplus)
    assert step.train_block._cache_size() == 1
    # ONE lane reaches the store as its leaves, to be laid out in one
    # dimension (a (1, d) row of bf16 is half padding); three as rows.
    assert stored == [client_block != 1]

    monkeypatch.setattr(pallas_round, "should_use", lambda n, d: False)
    monkeypatch.setattr(pallas_select, "kernel_applicable",
                        lambda n, d: False)
    s2, m2 = build()(fr.init(jax.random.PRNGKey(0), n), x, y, lengths, mal,
                     key)
    assert "finish_stripe_cols" not in m2   # the chunked finish: no stripe
    assert "finish_network_rows" not in m2
    assert int(m2["store_blocks_aligned"]) == 0   # (rows, d): part tiles
    for k in ("train_loss", "agg_norm", "update_norm_mean"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.server.params),
                    jax.tree.leaves(s2.server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)

"""The streamed round's whole-tile row-block store (ops/pallas_store.py),
interpreted on the CPU, against ``lax.dynamic_update_slice``."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from blades_tpu.ops import pallas_store


@pytest.mark.parametrize("dtype,lanes", [(jnp.bfloat16, 16),
                                         (jnp.float32, 8)])
@pytest.mark.parametrize("surplus,head", [
    (0, 0),  # a round without a short last block: the plain copy only
    (3, 0),  # a round with one, in any block but the last
    (3, 3),  # its last block: rows moved up by 3, three rows of +inf
    (6, 6),
])
def test_store_row_block_matches_dynamic_update_slice(dtype, lanes, surplus,
                                                      head):
    """The middle one of three row blocks, from an update two column
    blocks and 74 columns wide into a matrix padded to the next stripe:
    the block's rows are the update's (from row ``head`` on, then
    ``head`` rows of +inf), its padding columns zero, and the rest of the
    matrix, the +inf row at its end included, untouched."""
    d = 2 * pallas_store._block_cols(lanes, dtype) + 74
    width = -(-d // 512) * 512
    rng = np.random.default_rng(lanes + surplus + head)
    mat = rng.normal(size=(3 * lanes, width)).astype(np.float32)
    mat[-1] = np.inf
    mat = jnp.asarray(mat, dtype)
    upd = jnp.asarray(rng.normal(size=(lanes, d)), dtype)

    got = pallas_store.store_row_block(
        mat, upd, jnp.uint32(1), jnp.uint32(head), surplus=surplus,
        interpret=True)

    tile = jnp.zeros((lanes, width), dtype).at[:, :d].set(upd)
    tile = jnp.concatenate(
        [tile[head:], jnp.full((head, width), jnp.inf, dtype)])
    want = lax.dynamic_update_slice(mat, tile, (lanes, 0))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_store_gate_wants_whole_tiles_in_a_matrix_of_whole_blocks(
        monkeypatch):
    monkeypatch.setattr(pallas_store, "kernel_applicable", lambda n, d: True)
    ok = pallas_store.store_applicable
    assert ok(752, 4903424, 16, 0, 16)       # r10_median's matrix
    assert ok(768, 4903424, 16, 192, 16)     # a full matrix, no short block
    assert not ok(750, 4903424, 16, 0, 16)   # no room for a padded block
    assert not ok(752, 4903424, 8, 0, 16)    # half a bf16 tile
    assert not ok(752, 4903424, 16, 8, 16)   # off the block grid
    monkeypatch.undo()
    assert not ok(752, 4903424, 16, 0, 16)   # no TPU here

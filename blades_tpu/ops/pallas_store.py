"""Row-block stores into the streamed round's update matrix.

HBM holds a ``(rows, d)`` matrix in storage tiles of 8 sublanes of 32-bit
words x 128 lanes: 8 rows of a 4-byte type, 16 of a 2-byte one (two rows
share every word).  ``lax.dynamic_update_slice`` at a runtime row offset
takes XLA's general emitter on TPU: a read-modify-write of every tile the
rows touch, with a runtime sublane shift, whatever XLA knows of the
offset's low bits (only a CONSTANT offset marks the op
``is_index_aligned``; measured in PR 26).  For 16 rows of 752 that was
~100 GB/s of rows stored on a v5e; for ONE row of 8 it is all of the
matrix, read and written, for each row (36 ms for a 0.83 GB row of a 6.6
GB matrix: 390 GB/s of traffic of which a sixteenth is the row; PR 32).
Two plain copies take its place:

- **Blocks of whole tiles** (:func:`store_row_block`).  Where a block is
  a whole number of storage tiles landing on a tile boundary, this Mosaic
  kernel streams the block's ``(lanes, d)`` rows through VMEM into
  row-block ``block_index`` of the matrix, which it aliases, so nothing
  else of the matrix moves.
- **Blocks under a tile** (:func:`row_planes`).  No kernel can write part
  of a tile: a DMA moves whole tiles in any layout that keeps the rows on
  the second-minor axis (a Mosaic copy of one word-row of the matrix
  viewed as ``u32[4, W]`` is refused: "Slice shape along dimension 0 must
  be aligned to tiling (4), but is 1").  So the matrix itself keeps such
  rows on the MAJOR axis, ``(rows, d_alloc // 128, 128)``, a row a plane
  of whole tiles (parallel/streamed.py::compact_matrix), and there
  ``lax.dynamic_update_slice`` of ``row_planes(upd)`` at ``(row, 0, 0)``
  is XLA's own contiguous aligned copy, in place.

A round whose trained lanes are no whole number of blocks pads its last
block (parallel/streamed.py::block_plan): that block's first ``surplus``
lanes are clients the block before it already trained, and its store
drops them: the rows move up by ``surplus`` and the tile's last
``surplus`` rows read ``+inf``, the matrix's row padding.  The branch
that does so runs in that one block only; every other block, and every
block of a round without a short last block, is the plain copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from blades_tpu.ops.pallas_select import kernel_applicable

# VMEM bytes of one (lanes, columns) block; the pipeline holds four
# (input and output, double-buffered) plus the masked blocks' f32
# temporaries: well under the 16 MiB scoped default.
_BLOCK_BYTES = 1 << 20


def _block_cols(lanes: int, dtype) -> int:
    return _BLOCK_BYTES // (lanes * jnp.dtype(dtype).itemsize) // 128 * 128


def store_applicable(rows: int, width: int, lanes: int, row0: int,
                     tile: int) -> bool:
    """Can every block of ``lanes`` rows, the first at row ``row0``, of a
    ``(rows, width)`` matrix take the tile copy?  The shared kernel gate
    (TPU backend, escape hatch, size floor: see
    :func:`blades_tpu.ops.pallas_select.kernel_applicable`, whose bounds
    also keep a column block between one lane tile and the matrix's
    width), whole storage tiles (``tile`` rows) at whole-block offsets,
    and a matrix that is a whole number of blocks high, so that a padded
    last block lands inside it."""
    return (kernel_applicable(lanes, width) and lanes % tile == 0
            and row0 % lanes == 0 and rows % lanes == 0)


def row_planes(upd, tail):
    """``(lanes, d)`` rows as ``(lanes,) + tail`` planes of a matrix
    ``(rows,) + tail`` (``tail``: ``(d_alloc // 128, 128)``), the columns
    past ``d`` zero: what ``lax.dynamic_update_slice`` then writes at
    ``(row, 0, 0)`` as a contiguous copy.

    ONE lane may come as the params' pytree of its update's leaves, each
    ``(1, ...)`` (``Task.local_round_batched(ravel_update=False)``), and
    is then concatenated in ONE dimension: a 2-byte ``(1, d)`` row is
    laid out ``T(2,128)(2,1)``, a second, empty row in every 32-bit word
    (1.66 GB for 0.83 GB of row at d = 4.1e8), every leaf is re-tiled on
    its way into it and the whole of it once more on its way out (8.5 ms
    a block on a v5e for that last pass alone: PERF.md §6, PR 32),
    whereas a ``(d,)`` vector is dense and reaches the plane as a
    bitcast."""
    width = tail[0] * tail[1]
    if not isinstance(upd, jax.Array):
        flat = jnp.concatenate([leaf.reshape(-1)
                                for leaf in jax.tree.leaves(upd)])
        return jnp.pad(flat, (0, width - flat.size)).reshape((1, *tail))
    return jnp.pad(upd, ((0, 0), (0, width - upd.shape[1]))).reshape(
        (upd.shape[0], *tail))


def _copy_kernel(scalars_ref, upd_ref, mat_ref, out_ref, *, d: int,
                 cols: int, surplus: int):
    del mat_ref  # the alias's
    lanes = out_ref.shape[0]
    j = pl.program_id(0)
    edge = (j + 1) * cols > d
    plain = jnp.logical_not(edge)
    if surplus:
        # The scalars: the block's index and the count of surplus lanes
        # at the head of THIS block, `surplus` in the round's last block
        # and 0 in every other.
        short = scalars_ref[1] > 0
        whole = jnp.logical_not(short)
        plain, edge = plain & whole, edge & whole

    def masked():
        # The block that holds column d: what lies past it in the input
        # block is not the update's, and the matrix's padding columns
        # there must stay zero.  Compared and selected in f32: Mosaic on
        # a v5e refuses both on packed types.
        col = j * cols + lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        return jnp.where(col < d, upd_ref[...].astype(jnp.float32), 0.0)

    @pl.when(plain)
    def _():
        out_ref[...] = upd_ref[...]

    @pl.when(edge)
    def _():
        out_ref[...] = masked().astype(out_ref.dtype)

    if surplus:
        @pl.when(short)
        def _():
            # Row i takes row i + surplus; the rows that wrapped around
            # are the surplus lanes', and read +inf (all columns, as the
            # matrix's padding rows are allocated).
            x = pltpu.roll(masked(), lanes - surplus, 0)
            row = lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
            out_ref[...] = jnp.where(row < lanes - surplus, x,
                                     jnp.inf).astype(out_ref.dtype)


def store_row_block(matrix, upd, block_index, head=None, *,
                    surplus: int = 0, interpret: bool = False):
    """``matrix`` with rows ``[block_index * lanes, (block_index + 1) *
    lanes)`` replaced by ``upd`` (``(lanes, d)``, ``d <= matrix.shape[1]``;
    columns past ``d`` are written zero), in place where ``matrix`` is
    donated.  The caller checks :func:`store_applicable`.

    ``surplus`` (static) says the round has a short last block, and
    ``head`` (a scalar: ``surplus`` in that block, 0 elsewhere) whether
    this is it: its rows are stored from ``upd[surplus:]`` on, followed
    by ``surplus`` rows of ``+inf``."""
    lanes, d = upd.shape
    width = matrix.shape[1]
    cols = _block_cols(lanes, matrix.dtype)
    last_in = (d - 1) // cols
    scalars = [block_index] + ([head] if surplus else [])
    return pl.pallas_call(
        functools.partial(_copy_kernel, d=d, cols=cols, surplus=surplus),
        out_shape=jax.ShapeDtypeStruct(matrix.shape, matrix.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(width, cols),),
            in_specs=[
                # Clamped: a block wholly inside the padding columns reads
                # the update's last block and masks all of it.
                pl.BlockSpec((lanes, cols),
                             lambda j, s: (0, jnp.minimum(j, last_in))),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((lanes, cols), lambda j, s: (s[0], j)),
        ),
        input_output_aliases={2: 0},
        name="store_row_block",
        interpret=interpret,
    )(jnp.stack([jnp.asarray(s, jnp.int32) for s in scalars]), upd, matrix)

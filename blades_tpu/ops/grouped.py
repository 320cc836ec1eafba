"""An expert share computed as routing: the (token, expert) pairs whose
expert is held here are gathered by expert and go through ONE grouped
matrix product a projection, forward and backward, in static shapes.

The buffer holds a row for every selected pair, ``P = tokens x top-k``
(the most a share can be sent: no capacity, no dropped token); the pairs
whose expert is held are sorted to its front by expert, and the grouped
product walks only the row tiles its ``group_sizes`` cover, so the work
follows the pairs routed here and not ``tokens x experts held``.  Rows past
``group_sizes.sum()`` are never computed: what they hold is unspecified
and nothing reads it.

- :func:`grouped_matmul`: ``out[rows of group g] = lhs[rows of group g] @
  rhs[g]``.  On a TPU ``jax.experimental.pallas.ops.tpu.megablox``'s
  ``gmm`` with its own VJP (``gmm`` forward and for the left operand's
  cotangent, ``tgmm`` for the right's; their grid is the tiles in use, a
  dynamic bound); elsewhere a plain ``jnp`` form (a weight gathered a row:
  small sizes only).  Both batch under ``vmap`` (the trained lanes of a
  block).
- :func:`sort_pairs`: the sorted order, its inverse and the group sizes.
- :func:`gather_rows`: ``x[idx]`` whose cotangent is a gather too (the
  caller hands it the inverse map), so that neither the dispatch nor the
  combine scatters.  The ``jnp`` form's row mover: XLA's gathers take the
  whole worst-case buffer, every row of it.
- The Pallas row movers (where :func:`movers_applicable`): **spread**
  writes the buffer's row tiles in use, row ``r`` token ``order[r] //
  top_k``'s row (the dispatch; the combine's cotangent, scaled by the
  pair's weight), and **collect** writes each token the weighted sum of
  its held pairs' rows (the combine; the dispatch's cotangent).  Neither
  moves a row of a pair held elsewhere, and the spread's grid is the row
  tiles in use, a dynamic bound like the grouped product's.  The spread
  copies each row alone, from a float32 copy of its source whose rows are
  planes ``(T, 1, h)`` (a row of a 2-byte type is no whole storage tile).
  The collect copies the buffer in chunks of ``_CHUNK`` rows and picks
  rows out of a chunk by a one-hot matrix on the MXU, exact in float32
  (one nonzero product a row); which chunks hold a token tile's pairs
  follows from the sort: one held expert's pairs lie in token order, so
  the buffer rows of a token tile's pairs are one increasing run an
  expert.
- :func:`routed_ffn`: dispatch -> SwiGLU experts -> weighted combine,
  under the device scopes ``blades/dispatch`` (the sort and the move into
  the buffer), ``blades/experts`` (the grouped products and the SwiGLU) and
  ``blades/combine`` (the move back and the weighted sum), each with its
  cotangents.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row tile of the grouped product: a group's last tile is computed whole,
# so each held expert costs up to one tile of rows no pair asked for.
ROW_TILE = 256
# Rows of a source a mover copies at once: one MXU pass deep (the one-hot
# selection's contraction) and whole bf16 storage tiles (16 rows).
_CHUNK = 128
# Tokens a collect writes a grid step.
_TOKEN_TILE = 256


class _Tiles(NamedTuple):
    tile: int         # the grouped product's row tile: a spread step's rows
    chunk: int        # buffer rows a collect copies at once
    tt: int           # tokens a collect step writes
    interpret: bool   # in Pallas's interpreter
# The k and n tile of the package's kernels.  Its ``gmm`` takes ONE tiling
# for its three products (forward; ``gmm`` and ``tgmm`` for the cotangents):
# 896 x 896 times as a tiling searched for each product did (4.07 against
# 4.02 ms for an up and a down projection, forward + backward, at 8192
# pairs; 14.0 against 14.5 at 65 536), 128 x 128 three times slower, 1024 x
# 1024 a tenth slower (``tools/chip_kernels.py --grouped``, PR 33).
_TILE_KN = 896


def rows_computed(group_sizes, tile: int):
    """Pair rows the grouped product works on, padding to its row tiles
    included: every tile a group touches is computed for that group."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = (ends + tile - 1) // tile - starts // tile
    return (jnp.where(group_sizes > 0, tiles, 0) * tile).sum()


def _group_of_row(group_sizes, rows: int):
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(rows)
    gid = jnp.searchsorted(ends, row, side="right")
    return jnp.minimum(gid, group_sizes.shape[0] - 1), row < ends[-1]


def _grouped_matmul_jnp(lhs, rhs, group_sizes, transpose_rhs):
    gid, valid = _group_of_row(group_sizes, lhs.shape[0])
    w = rhs[gid]
    out = jnp.einsum("mk,mnk->mn" if transpose_rhs else "mk,mkn->mn",
                     lhs, w.astype(lhs.dtype))
    return jnp.where(valid[:, None], out, 0)


def kernel_applicable(k: int, n: int) -> bool:
    """Whether the product of these widths takes the Pallas kernels: on a
    TPU, and both widths whole lane tiles."""
    return jax.default_backend() == "tpu" and k % 128 == 0 and n % 128 == 0


def grouped_matmul(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
                   tile: int = ROW_TILE, impl=None):
    """``lhs`` ``(rows, k)`` in groups of consecutive rows (``group_sizes``
    ``(G,)`` int32, summing to at most ``rows``) times ``rhs`` ``(G, k,
    n)`` (``(G, n, k)`` with ``transpose_rhs``) -> ``(rows, n)`` in
    ``lhs``'s type.  Rows past the groups are unspecified with the kernel
    (zeros in the ``jnp`` form).  ``impl``: ``"kernel"`` (the default where
    :func:`kernel_applicable`), ``"interpret"`` (the kernel in Pallas's
    interpreter, for the tests) or ``"jnp"`` (the default elsewhere)."""
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if impl is None:
        impl = "kernel" if kernel_applicable(k, n) else "jnp"
    if impl == "jnp":
        return _grouped_matmul_jnp(lhs, rhs, group_sizes, transpose_rhs)
    if lhs.shape[0] % tile:
        raise ValueError(f"{lhs.shape[0]} rows are no multiple of the row "
                         f"tile {tile}")
    from jax.experimental.pallas.ops.tpu import megablox

    kn = min(_TILE_KN, k, n)
    return megablox.gmm(lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
                        (tile, kn, kn), None, None, transpose_rhs,
                        impl == "interpret")


def sort_pairs(expert, held, num_groups: int):
    """``expert`` ``(P,)`` local expert of every selected pair and ``held``
    ``(P,)`` whether this share holds it -> ``(order, pos, group_sizes)``:
    sorted row ``r`` is pair ``order[r]`` (held pairs first, by expert,
    pairs of one expert in token order), pair ``p`` lies at row
    ``pos[p]``, and ``group_sizes[g]`` pairs selected held expert ``g``."""
    key = jnp.where(held, expert, num_groups).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32)
    sizes = (key[:, None] == jnp.arange(num_groups)).sum(0, dtype=jnp.int32)
    return order, pos, sizes


@jax.custom_vjp
def gather_rows(x, idx, back_idx, back_mask):
    """``x[idx]`` ``(R, ...)``.  The caller states the inverse map: row
    ``t`` of ``x`` was read by the output rows ``back_idx[t, :]`` where
    ``back_mask[t, :]``, so the cotangent is gathered, never scattered."""
    return x.at[idx].get(mode="promise_in_bounds")


def _gather_fwd(x, idx, back_idx, back_mask):
    return gather_rows(x, idx, back_idx, back_mask), (back_idx, back_mask)


def _gather_bwd(res, g):
    back_idx, back_mask = res
    got = jnp.where(back_mask[..., None],
                    g.at[back_idx].get(mode="promise_in_bounds"), 0)
    return got.sum(1, dtype=jnp.float32).astype(g.dtype), None, None, None


gather_rows.defvjp(_gather_fwd, _gather_bwd)


def movers_applicable(tokens: int, h: int, dtype) -> bool:
    """Whether the routed layer moves its rows by the Pallas movers: on a
    TPU, rows of a 2-byte type whose width is whole lane tiles, and tokens
    a whole number of token tiles (so every chunk and tile is whole)."""
    return (jax.default_backend() == "tpu" and h % 128 == 0
            and tokens % _TOKEN_TILE == 0 and jnp.dtype(dtype).itemsize == 2)


class _Route(NamedTuple):
    """Where the held pairs lie, for the movers (``P`` pairs, ``G`` held
    experts, ``T`` tokens in tiles of ``tt``)."""
    tok: jax.Array    # (P,) buffer row r's token; -1 past the held pairs
    off: jax.Array    # (G + 1,) buffer row where each held expert starts
    posg: jax.Array   # (G, T) buffer row of token t's pair with expert g, or -1
    cum: jax.Array    # ((T // tt + 1) * G,) expert g's pairs before token tile i
    multi: jax.Array  # (T // tt,) 1 where a token of tile i has two held pairs


def _route(mine, order, pos, sizes, cfg) -> _Route:
    """``mine`` ``(T, K, G)``: pair ``(t, k)`` is held and its expert is
    ``g``.  A token that selects one expert twice is one pair of it with
    the two weights summed: its later row carries the token, the earlier
    one is a row of zeros."""
    tokens, top_k, groups = mine.shape
    tt = cfg.tt
    pairs = tokens * top_k
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(sizes, dtype=jnp.int32)])
    posg = jnp.where(mine, pos.reshape(tokens, top_k, 1), -1).max(1)
    kept = (jnp.where(mine, posg[:, None, :], -1).max(2)
            == pos.reshape(tokens, top_k)).reshape(-1)[order]
    tok = jnp.where((jnp.arange(pairs) < off[-1]) & kept, order // top_k, -1)
    per_tile = mine.any(1).reshape(tokens // tt, tt, groups).sum(
        1, dtype=jnp.int32)
    cum = jnp.concatenate([jnp.zeros((1, groups), jnp.int32),
                           jnp.cumsum(per_tile, 0, dtype=jnp.int32)])
    multi = mine.any(1).sum(1).reshape(tokens // tt, tt).max(1) > 1
    return _Route(tok.astype(jnp.int32), off, posg.T.astype(jnp.int32),
                  cum.reshape(-1), multi.astype(jnp.int32))


def _column(v):
    """A ``(1, n)`` lane vector as an ``(n, 1)`` column."""
    return jnp.transpose(jnp.broadcast_to(v, (128, v.shape[1])))[:, :1]


def _lanes(c):
    """An ``(n, 1)`` column as a ``(1, n)`` lane vector."""
    return jnp.transpose(jnp.broadcast_to(c, (c.shape[0], 128)))[:1, :]


def _pick(onehot, rows):
    """``onehot @ rows`` in float32: each row of the result is a sum of
    ``rows``' rows scaled by the one-hot's entries, every product exact
    (a 2-byte type's on the MXU; float32 at full precision)."""
    full = lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    return lax.dot(onehot.astype(rows.dtype), rows, precision=full,
                   preferred_element_type=jnp.float32)


def _each_chunk(sources, slots, sems, state, groups: int, chunk: int,
                span, body):
    """``body(g, c, bufs)`` for each chunk ``c`` of ``span(i, g) = (lo, hi)``
    (``lo > hi``: none), expert ``g`` by expert, in grid step ``i``:
    ``bufs`` are VMEM refs holding rows ``[c * chunk, (c + 1) * chunk)`` of
    each source, copied from HBM while the chunk before is used, the first
    chunk of a step while the step before ends (``state``: an SMEM word
    that says which of the two slots it went to)."""
    i, steps = pl.program_id(0), pl.num_programs(0)

    def first(i, g):
        """The first expert from ``g`` on with a chunk in step ``i``."""
        return lax.while_loop(
            lambda g: (g < groups) & (span(i, jnp.minimum(g, groups - 1))[0]
                                      > span(i, jnp.minimum(
                                          g, groups - 1))[1]),
            lambda g: g + 1, g)

    def copies(c, slot):
        start = pl.multiple_of(c * chunk, chunk)
        return [pltpu.make_async_copy(src.at[pl.ds(start, chunk)],
                                      buf.at[slot], sems.at[j, slot])
                for j, (src, buf) in enumerate(zip(sources, slots))]

    def start(i, g, slot):
        @pl.when(g < groups)
        def _():
            for cp in copies(span(i, g)[0], slot):
                cp.start()

    g0 = first(i, 0)

    @pl.when(i == 0)
    def _():
        state[0] = 0
        start(i, g0, 0)

    def step(carry):
        g, c, slot = carry
        for cp in copies(c, slot):
            cp.wait()
        more = c < span(i, g)[1]
        ng = jnp.where(more, g, first(i, g + 1))
        nc = jnp.where(more, c + 1, span(i, jnp.minimum(ng, groups - 1))[0])

        @pl.when(more)
        def _():
            for cp in copies(nc, 1 - slot):
                cp.start()

        @pl.when(~more)
        def _():
            start(i, ng, 1 - slot)

        body(g, c, [buf.at[slot] for buf in slots])
        return ng, nc, 1 - slot

    g, _, slot = lax.while_loop(
        lambda carry: carry[0] < groups, step,
        (g0, span(i, jnp.minimum(g0, groups - 1))[0], state[0]))
    # The next step's first chunk goes into the slot this step left free.
    state[0] = slot

    @pl.when(i + 1 < steps)
    def _():
        start(i + 1, first(i + 1, 0), slot)


def _spread_kernel(n_ref, tok_ref, tokv_ref, *refs, tile: int,
                   scaled: bool):
    """Buffer rows ``[i * tile, (i + 1) * tile)``: row ``r`` = its token's
    source row (times its weight where ``scaled``), 0 past the held pairs;
    ``scaled`` also writes each row's dot with ``ys``'s (the weight's
    cotangent).  The source is float32 with a row a plane ``(T, 1, h)``,
    so that one row is one copy of whole storage tiles: the rows of step
    ``i + 1`` are copied while step ``i``'s are written out."""
    if scaled:
        w_ref, src, ys_ref, out_ref, dots_ref, stage, sems = refs
    else:
        src, out_ref, stage, sems = refs
    i, steps = pl.program_id(0), pl.num_programs(0)

    def start(i, slot):
        r0 = i * tile

        def eight(j, carry):
            for r in (j * 8 + u for u in range(8)):
                # A row with no token copies the source's first row.
                t = jnp.maximum(tok_ref[r0 + r], 0)
                pltpu.make_async_copy(src.at[pl.ds(t, 1)],
                                      stage.at[slot, pl.ds(r, 1)],
                                      sems.at[slot]).start()
            return carry

        lax.fori_loop(0, tile // 8, eight, 0)

    @pl.when(i == 0)
    def _():
        start(i, 0)

    @pl.when(i + 1 < steps)
    def _():
        start(i + 1, (i + 1) % 2)

    slot = i % 2
    # One wait for the step's rows: the semaphore counts what arrived, and
    # ``tile`` one-row copies add up to one copy of ``tile`` rows.
    pltpu.make_async_copy(src.at[pl.ds(0, tile)], stage.at[slot],
                          sems.at[slot]).wait()
    # A row with no token (past the held pairs, or a token's second pair
    # of one expert) copied the first row: it is selected away.
    got = jnp.where(_column(tokv_ref[0]) >= 0,
                    stage[slot].reshape(tile, -1), 0.0)
    if not scaled:
        out_ref[...] = got.astype(out_ref.dtype)
        return
    out_ref[...] = (got * _column(w_ref[0])).astype(out_ref.dtype)
    # The rows past the held pairs picked nothing; ys's rows there are
    # unspecified, so their dot is selected away, not multiplied.
    dots = jnp.sum(got * ys_ref[...].astype(jnp.float32), axis=1,
                   keepdims=True)
    held = i * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0) \
        < n_ref[0]
    dots_ref[0] = _lanes(jnp.where(held, dots, 0.0))


def _spread(src, route: _Route, cfg, weight=None, ys=None):
    """The buffer ``(P, h)``: row ``r < n`` (``n`` held pairs) is
    ``src[route.tok[r]]``, times ``weight[r]`` if given; rows ``[n, ceil(n
    / tile) * tile)`` are 0 and the tiles past them are unspecified.  With
    ``weight`` also ``(P // tile, 1, tile)``: row ``r``'s dot of
    ``src[route.tok[r]]`` with ``ys[r]`` (0 past the held pairs)."""
    tile = cfg.tile
    pairs, (tokens, h) = route.tok.shape[0], src.shape
    scaled = weight is not None
    lanes = partial(jnp.reshape, shape=(pairs // tile, 1, tile))
    vec = pl.BlockSpec((1, 1, tile), lambda i, n, tok: (i, 0, 0))
    rows = pl.BlockSpec((tile, h), lambda i, n, tok: (i, 0))
    buf = jax.ShapeDtypeStruct((pairs, h), src.dtype)
    planes = pl.BlockSpec(memory_space=pl.ANY)
    in_specs, args = [vec], [lanes(route.tok)]
    if scaled:
        in_specs += [vec, planes, rows]
        out_shape = (buf, jax.ShapeDtypeStruct((pairs // tile, 1, tile),
                                               jnp.float32))
        out_specs = (rows, vec)
    else:
        in_specs.append(planes)
        out_shape, out_specs = buf, rows
    args += [lanes(weight)] if scaled else []
    args.append(src.astype(jnp.float32).reshape(tokens, 1, h))
    args += [ys] if scaled else []
    n = route.off[-1:]
    return pl.pallas_call(
        partial(_spread_kernel, tile=tile, scaled=scaled),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # The grouped product's row tiles in use: a dynamic bound.
            grid=((n[0] + tile - 1) // tile,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((2, tile, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=48 << 20),
        name="pairs_spread_weighted" if scaled else "pairs_spread",
        interpret=cfg.interpret,
    )(n, route.tok, *args)


def _collect_kernel(off_ref, cum_ref, multi_ref, pos_ref, w_ref, *refs,
                    tt: int, chunk: int, sources: int):
    """Token tile ``i``: ``y[t] = sum_g w[g, t] * buf[pos[g, t]]`` over
    the experts whose pair with ``t`` is held, ``buf`` the sum of the
    ``sources`` buffers."""
    srcs, (out_ref, acc), slots, (sems, state) = (
        refs[:sources], refs[sources:sources + 2], refs[sources + 2:-2],
        refs[-2:])
    groups = off_ref.shape[0] - 1
    i = pl.program_id(0)
    n_held = off_ref[groups]
    acc[...] = jnp.zeros_like(acc)
    col = lax.broadcasted_iota(jnp.int32, (tt, chunk), 1)

    def span(i, g):
        # Expert g's pairs with tile i's tokens: one run of the buffer.
        lo = off_ref[g] + cum_ref[i * groups + g]
        hi = off_ref[g] + cum_ref[(i + 1) * groups + g]
        return (jnp.where(hi > lo, lo // chunk, 1),
                jnp.where(hi > lo, (hi - 1) // chunk, 0))

    def visit(g, c, bufs):
        c0 = c * chunk
        dtype = bufs[0].dtype

        # The chunk that holds the buffer's last held row: the rows past it
        # are unspecified (a NaN there would survive a zero weight), so
        # they are selected away first.
        @pl.when(c0 + chunk > n_held)
        def _():
            kept = lax.broadcasted_iota(jnp.int32, bufs[0].shape, 0) \
                < n_held - c0
            for rows in bufs:
                rows[...] = jnp.where(kept, rows[...].astype(jnp.float32),
                                      0.0).astype(dtype)

        pos = _column(pos_ref[pl.ds(g, 1), :])
        w = _column(w_ref[pl.ds(g, 1), :])
        hit = jnp.where(pos == c0 + col, w, 0.0)
        # A token takes one row of a chunk at most: each row of ``got`` is
        # one pair's product (the sources' sum, exact in float32).
        got = sum(_pick(hit, rows[...]) for rows in bufs)

        # Where a token of the tile has two held pairs, each pair's value is
        # rounded to the buffer's type before they are summed, as the jnp
        # form rounds its product (and its two sources' sum); alone, the
        # cast of the sum rounds it once, the same to the bit.
        @pl.when(multi_ref[i] == 0)
        def _():
            acc[...] += got

        @pl.when(multi_ref[i] != 0)
        def _():
            acc[...] += got.astype(dtype).astype(jnp.float32)

    _each_chunk(srcs, slots, sems, state, groups, chunk, span, visit)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _collect(sources, route: _Route, cfg, weight=None):
    """``(T, h)``: token ``t``'s row is ``sum_g weight[g, t] * buf[
    route.posg[g, t]]`` over its held pairs (0 without one), ``buf`` the
    sum of ``sources`` (each ``(P, h)``, read only at held pairs' rows);
    without ``weight`` every weight is 1."""
    if weight is None:
        weight = (route.posg >= 0).astype(jnp.float32)
    chunk, tt = cfg.chunk, cfg.tt
    groups, tokens = route.posg.shape
    h, dtype = sources[0].shape[1], sources[0].dtype
    by_token = pl.BlockSpec((groups, tt), lambda i, *_: (0, i))
    n = len(sources)
    return pl.pallas_call(
        partial(_collect_kernel, tt=tt, chunk=chunk, sources=n),
        out_shape=jax.ShapeDtypeStruct((tokens, h), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tokens // tt,),
            in_specs=[by_token, by_token]
            + [pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=pl.BlockSpec((tt, h), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tt, h), jnp.float32)]
            + [pltpu.VMEM((2, chunk, h), dtype)] * n
            + [pltpu.SemaphoreType.DMA((n, 2)), pltpu.SMEM((1,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=48 << 20),
        name="pairs_collect",
        interpret=cfg.interpret,
    )(route.off, route.cum, route.multi, route.posg,
      weight.astype(jnp.float32), *sources)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, route, cfg):
    return _spread(x, route, cfg)


def _dispatch_fwd(x, route, cfg):
    return _dispatch(x, route, cfg), route


def _dispatch_bwd(cfg, route, g):
    return _collect([g], route, cfg), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _cotangents_together(*args):
    """The identity.  Its cotangents leave together: the expert weights'
    (the grouped products' ``tgmm``) are computed before the layer input's
    goes on to the layer below, so that each layer's buffers (the rows
    moved in, the combine's cotangent rows) are freed in its own backward
    pass and not all held to its end."""
    return args


def _together_fwd(*args):
    return args, None


def _together_bwd(_, g):
    return lax.optimization_barrier(tuple(g))


_cotangents_together.defvjp(_together_fwd, _together_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(ys, wg, route, cfg):
    return _collect([ys], route, cfg, wg)


def _combine_fwd(ys, wg, route, cfg):
    return _combine(ys, wg, route, cfg), (ys, wg, route)


def _combine_bwd(cfg, res, g):
    ys, wg, route = res
    groups = wg.shape[0]
    # Buffer row r's weight: its expert's, at its token (0 past the pairs).
    row = jnp.arange(route.tok.shape[0])
    expert = (row[:, None] >= route.off[1:groups]).sum(1)
    w_row = jnp.where(route.tok >= 0,
                      wg[expert, jnp.maximum(route.tok, 0)], 0.0)
    g_ys, dots = _spread(g, route, cfg, w_row, ys)
    dots = dots.reshape(-1)
    g_wg = jnp.where(route.posg >= 0, dots[jnp.maximum(route.posg, 0)], 0.0)
    return g_ys, g_wg, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_ffn(x, expert, held, weight, gate, up, down, *, impl=None):
    """The held experts' part of a routed SwiGLU layer.

    ``x`` ``(T, h)`` tokens; ``expert`` ``(T, K)`` int32 local index of
    each selected expert, ``held`` ``(T, K)`` whether it is held here,
    ``weight`` ``(T, K)`` its routing weight; ``gate``/``up`` ``(G, h,
    f)``, ``down`` ``(G, f, h)``.  Returns ``(y (T, h), group_sizes (G,),
    rows_computed, rows_moved)``: ``y[t] = sum over held pairs of weight *
    down_e(silu(gate_e x_t) * up_e x_t)``; ``rows_moved`` the buffer rows
    the dispatch writes (the row tiles in use where the movers run, the
    whole buffer in the ``jnp`` form).

    ``impl``: ``"kernel"``, ``"interpret"`` (the Pallas movers and grouped
    product; in Pallas's interpreter for the tests) or ``"jnp"`` (XLA's
    gathers and the ``jnp`` product); by default the kernels where
    :func:`movers_applicable` and :func:`kernel_applicable` hold."""
    tokens, top_k = expert.shape
    pairs = tokens * top_k
    tile = math.gcd(pairs, ROW_TILE)
    movers = impl if impl is not None else (
        "kernel" if movers_applicable(tokens, x.shape[1], x.dtype) else "jnp")
    with jax.named_scope("blades/dispatch"):
        order, pos, sizes = sort_pairs(expert.reshape(pairs),
                                       held.reshape(pairs), gate.shape[0])
        if movers == "jnp":
            xs = gather_rows(x, order // top_k, pos.reshape(tokens, top_k),
                             held)
            moved = jnp.int32(pairs)
        else:
            cfg = _Tiles(tile, math.gcd(tokens, _CHUNK),
                         math.gcd(tokens, _TOKEN_TILE), movers == "interpret")
            mine = held[..., None] & (expert[..., None]
                                      == jnp.arange(gate.shape[0]))
            route = _route(mine, order, pos, sizes, cfg)
            x, gate, up, down = _cotangents_together(x, gate, up, down)
            xs = _dispatch(x, route, cfg)
            moved = rows_computed(sizes.sum(keepdims=True), tile)
    with jax.named_scope("blades/experts"):
        mm = partial(grouped_matmul, group_sizes=sizes, tile=tile, impl=impl)
        hs = jax.nn.silu(mm(xs, gate)) * mm(xs, up)
        ys = mm(hs, down)
    with jax.named_scope("blades/combine"):
        if movers == "jnp":
            # Pair p reads its row; sorted row r is read by pair order[r]
            # alone.
            got = gather_rows(ys, pos, order[:, None],
                              jnp.ones((pairs, 1), bool)).reshape(
                                  tokens, top_k, -1)
            # A pair whose expert is absent reads a row nothing wrote: take
            # its value out before anything multiplies it.
            got = jnp.where(held[..., None], got, 0)
            y = (weight[..., None].astype(got.dtype) * got).sum(
                1, dtype=jnp.float32).astype(x.dtype)
        else:
            # Each token's weight of each held expert, rounded to the rows'
            # type as the jnp form multiplies it.
            wg = jnp.where(mine, weight.astype(x.dtype)[..., None].astype(
                jnp.float32), 0.0).sum(1).T
            y = _combine(ys, wg, route, cfg)
    return y, sizes, rows_computed(sizes, tile), moved

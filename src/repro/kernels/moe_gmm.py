"""Pallas TPU grouped matmul over the experts a MoE layer holds.

``moe_gmm(x, w, group_sizes)``: ``x`` (P, d) holds rows sorted by group
(the routed (token, expert) pairs, sorted by held expert), ``w`` (E, d, f)
one matrix per group, ``group_sizes`` (E,) int32 how many rows each group
has. Row ``p`` of group ``g`` gives ``x[p] @ w[g]``; rows past the last
group (pairs no held expert takes) give 0. Nothing is dropped: every row
of every group is computed, however uneven the groups. A caller inside a
layer scan passes the whole stack, ``w`` (L, E, d, f), and the ``layer``:
the kernel reads that layer's matrices where they lie, where a slice of
the stack would be copied whole for the call.

The wrapper lays the rows out so that each group starts on a row tile of
``tm``: a group of ``n`` rows takes ``ceil(n / tm)`` tiles, and every tile
belongs to one group. The grid is (row tile, output tile, contraction
tile), contraction innermost into an f32 VMEM accumulator; the weight
block's index comes from the tile's group, read from a scalar-prefetched
table, so each tile streams its own expert's matrix. The row-tile axis is
sized for the worst case (every row in one group, plus one partial tile a
group); tiles past the ones in use repeat the last block index, so the
pipeline issues no copy for them, and compute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

#: preferred contraction and output tile: a (1024, 1024) bf16 weight block
#: is 2 MiB, long enough per grid step to hide the step's fixed cost
BLOCK_KN = 1024


def _tile(n: int, pref: int) -> int:
    """The largest of ``pref``, ``pref / 2``, ... 128 that divides ``n``;
    ``n`` itself when none does (a full-dimension block)."""
    t = pref
    while t >= 128:
        if n % t == 0:
            return t
        t //= 2
    return n


def row_tile(rows: int) -> int:
    """Rows per tile: 16 (a bf16 sublane tile) for decode-sized calls,
    128 (the MXU's edge) once there are more than 256 rows."""
    return 16 if rows <= 256 else 128


def vmem_bytes(tm: int, tk: int, tn: int, elem_bytes: int,
               w_bytes: int | None = None) -> int:
    """VMEM the kernel holds: the row, weight and output blocks,
    double-buffered, the f32 accumulator and one f32 product tile; with
    weights of another width (``w_bytes``), the weight block converted
    to the rows' dtype."""
    w_bytes = w_bytes or elem_bytes
    blocks = (tm * tk + tm * tn) * elem_bytes + tk * tn * w_bytes
    cast = tk * tn * elem_bytes if w_bytes != elem_bytes else 0
    return 2 * blocks + cast + 2 * tm * tn * 4


def layout(group_sizes, rows: int, tm: int):
    """Where the sorted rows go in the tile-aligned buffer, by gathers only.

    Returns ``(src, dest, tile_group, used)``: ``src`` (n_tiles * tm,) the
    input row each padded row takes, ``rows`` (out of range) for padding;
    ``dest`` (rows,) the padded row of each input row, ``n_tiles * tm``
    for rows past the last group; ``tile_group`` (n_tiles,) the group of
    each tile; ``used`` (1,) the tiles in use."""
    e = group_sizes.shape[0]
    n_tiles = -(-rows // tm) + e
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = -(-sizes // tm)
    tile_ends = jnp.cumsum(tiles)
    tile_starts = tile_ends - tiles
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(jnp.searchsorted(tile_ends, t, side="right"),
                             e - 1).astype(jnp.int32)
    used = tile_ends[-1:].astype(jnp.int32)
    # padded row -> input row
    pr = jnp.arange(n_tiles * tm, dtype=jnp.int32)
    pg = jnp.repeat(tile_group, tm)
    off = pr - tile_starts[pg] * tm
    src = jnp.where((pr // tm < used[0]) & (off < sizes[pg]),
                    starts[pg] + off, rows)
    # input row -> padded row
    r = jnp.arange(rows, dtype=jnp.int32)
    g = jnp.minimum(jnp.searchsorted(ends, r, side="right"), e - 1)
    dest = jnp.where(r < ends[-1], tile_starts[g] * tm + r - starts[g],
                     n_tiles * tm)
    return src, dest, tile_group, used


def _kernel(tile_group_ref, used_ref, layer_ref, x_ref, w_ref, o_ref,
            acc_ref):
    i, k = pl.program_id(0), pl.program_id(2)
    live = i < used_ref[0]

    @pl.when(live & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _acc():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0].astype(x_ref.dtype),
                                preferred_element_type=jnp.float32)

    @pl.when(live & (k == pl.num_programs(2) - 1))
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _gmm_tiles(xp, w, tile_group, used, layer, *, tm: int,
               interpret: bool | None = None):
    """The kernel on the tile-aligned buffer ``xp`` (n_tiles * tm, d) and
    layer ``layer`` (1,) of the stacked ``w`` (L, E, d, f), seen as the
    (L * E, d, f) it is in memory."""
    rows, d = xp.shape
    n_layers, e, _, f = w.shape
    w = w.reshape(n_layers * e, d, f)
    tk, tn = _tile(d, BLOCK_KN), _tile(f, BLOCK_KN)
    nn, nk = f // tn, d // tk

    def at(i, n, k, used):
        """Grid step -> block step; steps past the tiles in use repeat the
        last live step's blocks."""
        live = i < used[0]
        return (jnp.where(live, i, jnp.maximum(used[0] - 1, 0)),
                jnp.where(live, n, nn - 1), jnp.where(live, k, nk - 1))

    def x_map(i, n, k, tg, used, lay):
        i, n, k = at(i, n, k, used)
        return i, k

    def w_map(i, n, k, tg, used, lay):
        i, n, k = at(i, n, k, used)
        return lay[0] * e + tg[i], k, n

    def o_map(i, n, k, tg, used, lay):
        i, n, k = at(i, n, k, used)
        return i, n

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows // tm, nn, nk),
        in_specs=[pl.BlockSpec((tm, tk), x_map),
                  pl.BlockSpec((1, tk, tn), w_map)],
        out_specs=pl.BlockSpec((tm, tn), o_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    return pl.pallas_call(
        _kernel,
        name="moe_gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, f), xp.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(tm, tk, tn, xp.dtype.itemsize,
                                        w.dtype.itemsize) + (4 << 20)),
        interpret=resolve_interpret(interpret),
    )(tile_group, used, layer, xp, w)


def moe_gmm(x, w, group_sizes, *, layer=None, tm: int | None = None,
            interpret: bool | None = None):
    """x: (P, d) rows sorted by group; w: (E, d, f), or (L, E, d, f) with
    ``layer`` an int32 scalar; group_sizes: (E,) int32 summing to at most
    P. Returns (P, f) in ``x``'s dtype: row ``p`` of group ``g`` is
    ``x[p] @ w[layer][g]``, rows past the groups are 0. Weights narrower
    than ``x`` are widened a block at a time inside the kernel, never as
    a copy of the stack.
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if layer is None:
        w, layer = w[None], 0
    rows, d = x.shape
    tm = tm or row_tile(rows)
    src, dest, tile_group, used = layout(group_sizes, rows, tm)
    n_pad = src.shape[0]
    xp = jnp.where((src < rows)[:, None],
                   jnp.take(x, jnp.minimum(src, rows - 1), axis=0), 0)
    out = _gmm_tiles(xp, w, tile_group, used,
                     jnp.reshape(layer, (1,)).astype(jnp.int32), tm=tm,
                     interpret=interpret)
    got = jnp.take(out, jnp.minimum(dest, n_pad - 1), axis=0)
    return jnp.where((dest < n_pad)[:, None], got, 0).astype(x.dtype)

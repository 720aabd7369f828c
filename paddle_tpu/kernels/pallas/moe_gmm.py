"""Grouped expert matmul for a dropless mixture of experts (Pallas).

Rows arrive sorted by expert, each expert's group padded to a whole
number of ``tm``-row tiles (``tile_layout``), so that a tile belongs to
exactly one expert. The grid walks the tiles; the expert a tile belongs
to rides scalar prefetch and picks the weight block in the index map, so
consecutive tiles of one expert re-use the block already in VMEM and
every held expert's weights are read from HBM once a call. Tiles past
the last group (the padding's static upper bound) skip their compute.

TPU-native and not a megablox port: no tile spans two groups, so there
is no masking inside a tile and no group metadata beyond one int32 a
tile; K is kept whole in VMEM (the expert widths here are 768 and 2048),
so there is no accumulator across grid steps either.

- ``moe_gmm``        ``x [M, K] @ w[expert of the tile] [K, N]``
- ``moe_gmm_swiglu`` ``silu(x @ wg[e]) * (x @ wu[e])``: the gate and up
  projections of a SwiGLU expert in one pass over ``x``.
- ``moe_gmm_plain`` / ``moe_gmm_swiglu_plain``: the same products as
  ``jax.lax.ragged_dot`` over the same padded layout, the CPU's route
  and the kernels' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["moe_gmm", "moe_gmm_swiglu", "moe_gmm_plain",
           "moe_gmm_swiglu_plain", "tile_rows", "tile_layout"]

# i32-typed literals: under jax_enable_x64 a bare python number in an
# index map traces as a 64-bit constant Mosaic cannot legalize
_I0 = np.int32(0)
# a weight block [K, bn] may take this much VMEM (it is double-buffered)
_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024
_MIN_TILE, _MAX_TILE = 16, 128


def tile_rows(rows, experts):
    """Rows a tile holds (static): the power of two nearest below half
    the mean group, within [16, 128]. Half, because a group wastes half
    a tile of padding on average; 16 is bfloat16's sublane tile, 128 the
    MXU's height."""
    mean = max(int(rows) // max(int(experts), 1), 1)
    tm = _MIN_TILE
    while tm * 4 <= mean and tm < _MAX_TILE:
        tm *= 2
    return tm


def tile_layout(group_sizes, tm, rows):
    """Where each group sits once padded to whole tiles. ``group_sizes``
    [E] int32 (rows sorted by expert), ``rows`` their static total.
    Returns (padded row count M (static), padded group offsets [E],
    padded group sizes [E], expert of each tile [M / tm], number of
    tiles that hold rows [1])."""
    e = group_sizes.shape[0]
    m = (-(-int(rows) // tm) + e) * tm  # every group may waste tm - 1
    padded = -(-group_sizes // tm) * tm
    ends = jnp.cumsum(padded)
    tile_start = jnp.arange(m // tm, dtype=jnp.int32) * np.int32(tm)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"),
        e - 1).astype(jnp.int32)
    num_tiles = (ends[-1:] // tm).astype(jnp.int32)
    return m, (ends - padded).astype(jnp.int32), \
        padded.astype(jnp.int32), tile_expert, num_tiles


def _block_n(k, n, itemsize):
    """Widest block of the N axis whose [K, bn] weight tile fits the
    VMEM budget: N itself, else a 128-multiple that divides it."""
    if k * n * itemsize <= _WEIGHT_BLOCK_BYTES or n % 128:
        return n
    best = 128
    for bn in range(128, n, 128):
        if n % bn == 0 and k * bn * itemsize <= _WEIGHT_BLOCK_BYTES:
            best = bn
    return best


def _gmm_kernel(tile_expert_ref, num_tiles_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < num_tiles_ref[0])
    def _():
        o_ref[...] = jax.lax.dot(
            x_ref[...], w_ref[0],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _swiglu_kernel(tile_expert_ref, num_tiles_ref, x_ref, wg_ref, wu_ref,
                   o_ref):
    @pl.when(pl.program_id(1) < num_tiles_ref[0])
    def _():
        x = x_ref[...]
        gate = jax.lax.dot(x, wg_ref[0],
                           preferred_element_type=jnp.float32)
        up = jax.lax.dot(x, wu_ref[0],
                         preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(gate) * up).astype(o_ref.dtype)


def _call(kernel, name, x, weights, tile_expert, num_tiles, tm, interpret):
    m, k = x.shape
    n = weights[0].shape[2]
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    bn = _block_n(k, n, itemsize)
    if interpret is None:
        interpret = _interpret()
    # grid (N blocks, tiles): tiles innermost, so the weight block's
    # index changes only where the expert does
    w_spec = pl.BlockSpec((1, k, bn),
                          lambda nn, tt, te, nt: (te[tt], _I0, nn))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, m // tm),
        in_specs=[pl.BlockSpec((tm, k), lambda nn, tt, te, nt: (tt, _I0))]
        + [w_spec] * len(weights),
        out_specs=pl.BlockSpec((tm, bn), lambda nn, tt, te, nt: (tt, nn)))
    # double-buffered weight blocks, x and out tiles, and as much again
    # for the float32 products
    need = 2 * (len(weights) * k * bn * itemsize
                + tm * (k + bn) * jnp.dtype(x.dtype).itemsize) \
        + 4 * len(weights) * tm * bn * 4
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(need + (8 << 20), 32 << 20))),
        interpret=interpret, name=name,
    )(tile_expert, num_tiles, x, *weights)


@functools.partial(jax.jit, static_argnames=("tm", "interpret", "tag"))
def moe_gmm(x, w, tile_expert, num_tiles, *, tm, interpret=None, tag=""):
    """``x [M, K]`` (groups padded to ``tm``-row tiles) times
    ``w [E, K, N]``, each tile with its own expert's matrix
    (``tile_expert [M / tm]``); tiles from ``num_tiles[0]`` on are left
    unwritten. Returns [M, N] in ``x``'s dtype, float32 accumulation.
    The call is named ``moe_gmm<tag>`` in a device trace."""
    return _call(_gmm_kernel, "moe_gmm" + tag, x, (w,), tile_expert,
                 num_tiles, tm, interpret)


@functools.partial(jax.jit, static_argnames=("tm", "interpret", "tag"))
def moe_gmm_swiglu(x, wg, wu, tile_expert, num_tiles, *, tm,
                   interpret=None, tag=""):
    """``silu(x @ wg[e]) * (x @ wu[e])`` a tile, as :func:`moe_gmm`;
    named ``moe_gmm_swiglu<tag>``."""
    return _call(_swiglu_kernel, "moe_gmm_swiglu" + tag, x, (wg, wu),
                 tile_expert, num_tiles, tm, interpret)


def moe_gmm_plain(x, w, padded_sizes):
    """The plain sorted product :func:`moe_gmm` is checked against:
    ``jax.lax.ragged_dot`` over the padded groups. Rows past the last
    group come out zero."""
    return jax.lax.ragged_dot(
        x, w, padded_sizes,
        preferred_element_type=jnp.float32).astype(x.dtype)


def moe_gmm_swiglu_plain(x, wg, wu, padded_sizes):
    gate = jax.lax.ragged_dot(x, wg, padded_sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(x, wu, padded_sizes,
                            preferred_element_type=jnp.float32)
    return (jax.nn.silu(gate) * up).astype(x.dtype)

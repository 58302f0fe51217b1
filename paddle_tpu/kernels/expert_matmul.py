"""Grouped matrix product for a dropless expert layer: rows sorted by
expert, each group multiplied by its own expert's matrix, nothing dropped.

A sparse-expert layer sends every token to ``k`` of ``E`` experts.  With
the (token, expert) pairs sorted by expert the layer is a *grouped* matrix
product: rows ``[start_e, start_e + size_e)`` of one buffer times ``w[e]``.
The sizes are known only on the device, so the product has to be ragged: no
capacity, no padding to the fullest expert, no work for an expert nobody
chose.

Layout (``expert_layout``).  ``x [N, K]`` holds the groups in ascending
expert order, group ``e`` starting at a row that is a multiple of ``tile``
(``starts[e]``), so a tile of ``tile`` rows belongs to one expert.  ``N`` is
static: pairs rounded up to a tile plus one tile of slack an expert
(``buffer_rows``), so nothing can overflow whatever the routing.  Rows
between a group's end and the next group's start are padding: they are
computed on whatever the buffer holds and written as zeros.

The kernel walks a flat list of items ``(expert, column block, row tile)``,
expert by expert, a column block at a time and within it the expert's row
tiles: consecutive items of one (expert, column block) keep the weight
block's index, so Pallas does not fetch it again, and **each held expert's
weights are read at most once a call**, and not at all for an expert
without a pair.  The grid is ``(n,)`` with ``n`` the number of real items
(a dynamic bound): a step's cost is the experts that got a pair, not ``E``.
Each item is one full-depth product ``x_tile [tile, K] @ w[e][:, block]``
in float32 out of the unit; with ``w_up`` the item computes
``silu(x @ w[e]) * (x @ w_up[e])`` for the same column block (the SwiGLU's
first half: gate and up in one pass over the rows).  The group sizes ride
in scalar prefetch beside the list.

The matrices come as every layer's stack, ``w [L, E, K, n_out]``, with
``layer`` (a traced int32 scalar, or an int) naming the one to read, as the
attention kernels take their page pools: a slice ``w[layer]`` handed to a
custom call is a copy of a layer's experts (384 MB a matrix at 64 experts
of 3072 x 1024: XLA's plan, seen at the first AOT compile), a block index
is not.  ``[E, K, n_out]`` with ``layer`` left out is the same kernel.

``path=`` as every kernel here (``kernels/dispatch.py``): Mosaic on a TPU,
the ``jnp`` reference elsewhere, the interpreter for CPU tests of the body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.linalg import mxu_precision
from . import dispatch

__all__ = ["expert_matmul", "expert_layout", "buffer_rows"]

# bytes of one weight operand's column block in VMEM (two buffers each)
_BLOCK_BYTES = 3 << 20
_VMEM_LIMIT = 64 << 20


def buffer_rows(pairs, experts, tile):
    """Rows of the sorted buffer that hold ``pairs`` (token, expert) pairs
    over ``experts`` groups, each group padded to whole tiles, whatever the
    routing: static."""
    return (-(-pairs // tile) + experts) * tile


def expert_layout(group_sizes, tile):
    """Where each group starts in the sorted buffer: ``(starts [E], tiles
    [E])``, group ``e`` at rows ``starts[e] .. starts[e] + group_sizes[e]``
    in ``tiles[e]`` whole tiles (0 for an expert without a pair)."""
    tiles = -(-group_sizes // tile)
    ends = jnp.cumsum(tiles)
    return ((ends - tiles) * tile).astype(jnp.int32), tiles.astype(jnp.int32)


def _items(group_sizes, tile, blocks, n_tiles):
    """The flat work list: ``(expert, block, row tile, n, starts)``, the
    first three ``[blocks * n_tiles]`` int32 with the first ``n [1]`` in
    use, ordered expert, then column block, then the expert's tiles;
    ``starts [E]`` is ``expert_layout``'s."""
    starts, tiles = expert_layout(group_sizes, tile)
    counts = tiles * blocks
    ends = jnp.cumsum(counts)
    i = jnp.arange(blocks * n_tiles, dtype=jnp.int32)
    E = group_sizes.shape[0]
    # the expert of item i: how many experts end at or before it
    e = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1),
                    E - 1).astype(jnp.int32)
    rank = i - (ends - counts)[e]
    per = jnp.maximum(tiles[e], 1)
    block = jnp.clip(rank // per, 0, blocks - 1)
    row_tile = jnp.clip(starts[e] // tile + rank % per, 0, n_tiles - 1)
    return (e, block.astype(jnp.int32), row_tile.astype(jnp.int32),
            ends[-1:].astype(jnp.int32), starts)


def _row_experts(group_sizes, tile, n_rows):
    """``(expert of each buffer row [N], the row is a real pair [N])``."""
    starts, tiles = expert_layout(group_sizes, tile)
    ends = starts + tiles * tile
    r = jnp.arange(n_rows, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(r[:, None] >= ends[None, :], axis=1),
                    group_sizes.shape[0] - 1)
    return e, r - starts[e] < group_sizes[e]


def _expert_matmul_ref(x, group_sizes, w, w_up, layer, tile, out_dtype):
    """Every tile against its expert's whole matrix, gathered: the oracle
    (a tile's weights are materialised, so small sizes only)."""
    N, K = x.shape
    e, real = _row_experts(group_sizes, tile, N)
    tile_e = e.reshape(N // tile, tile)[:, 0]
    xt = x.reshape(N // tile, tile, K)
    precision = mxu_precision(x, w)
    mm = lambda m: jnp.einsum(
        "tmk,tkn->tmn", xt, m[layer, tile_e], precision=precision,
        preferred_element_type=jnp.float32)
    y = mm(w)
    if w_up is not None:
        y = jax.nn.silu(y) * mm(w_up)
    y = y.reshape(N, -1)
    return jnp.where(real[:, None], y, 0.0).astype(out_dtype)


def _kernel(e_ref, blk_ref, tile_ref, n_ref, size_ref, start_ref, layer_ref,
            x_ref, w_ref, *rest, tile, swiglu):
    del blk_ref, n_ref, layer_ref          # only the index maps read them
    up_ref, o_ref = rest if swiglu else (None, rest[0])
    i = pl.program_id(0)
    x = x_ref[...]
    precision = mxu_precision(x, w_ref)
    y = jnp.dot(x, w_ref[0, 0], precision=precision,
                preferred_element_type=jnp.float32)
    if swiglu:
        y = jax.nn.silu(y) * jnp.dot(x, up_ref[0, 0], precision=precision,
                                     preferred_element_type=jnp.float32)
    # rows past the group's end are padding: zeros, whatever they held
    e = e_ref[i]
    live = start_ref[e] + size_ref[e] - tile_ref[i] * tile
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    o_ref[...] = jnp.where(row < live, y, 0.0).astype(o_ref.dtype)


def _column_block(K, n_out, itemsize):
    """Columns of one weight block: the widest multiple of 128 dividing
    ``n_out`` whose ``[K, block]`` stays under ``_BLOCK_BYTES``."""
    best = None
    for block in range(128, n_out + 1, 128):
        if n_out % block == 0 and K * block * itemsize <= _BLOCK_BYTES:
            best = block
    return best or n_out


def _expert_matmul_kernel(x, group_sizes, w, w_up, layer, tile, out_dtype,
                          interpret):
    N, K = x.shape
    _, E, _, n_out = w.shape
    block = _column_block(K, n_out, w.dtype.itemsize)
    blocks, n_tiles = n_out // block, N // tile
    e, blk, row_tile, n, starts = _items(group_sizes, tile, blocks, n_tiles)
    swiglu = w_up is not None

    def x_map(i, e, blk, rt, *_):
        return (rt[i], 0)

    def w_map(i, e, blk, rt, n, sizes, starts, layer):
        return (layer[0], e[i], 0, blk[i])

    def o_map(i, e, blk, rt, *_):
        return (rt[i], blk[i])

    w_spec = pl.BlockSpec((1, 1, K, block), w_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n[0],),
        in_specs=[pl.BlockSpec((tile, K), x_map), w_spec]
        + ([w_spec] if swiglu else []),
        out_specs=pl.BlockSpec((tile, block), o_map),
    )
    kernel = functools.partial(_kernel, tile=tile, swiglu=swiglu)
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, n_out), out_dtype),
        interpret=interpret, name="expert_matmul", **kwargs,
    )(e, blk, row_tile, n, group_sizes, starts,
      jnp.asarray(layer, jnp.int32).reshape(1), x, w,
      *((w_up,) if swiglu else ()))


def expert_matmul(x, group_sizes, w, w_up=None, *, tile, layer=None,
                  out_dtype=None, path=None):
    """``y [N, n_out]``: rows of ``x [N, K]`` in the layout of
    ``expert_layout(group_sizes [E], tile)`` times their group's matrix
    ``w[layer] [E, K, n_out]``; with ``w_up`` (same shape)
    ``silu(x @ w[e]) * (x @ w_up[e])``.  ``w [L, E, K, n_out]`` comes with
    its ``layer``, ``[E, K, n_out]`` without.  Padding rows of a visited
    tile are zeros; tiles past the last group are **not written** (a
    caller reads only the rows it placed).  ``N`` is a multiple of
    ``tile``."""
    N = x.shape[0]
    if N % tile:
        raise ValueError(f"{N} buffer rows are not whole tiles of {tile}")
    if (w.ndim == 4) != (layer is not None):
        raise ValueError("a stacked [L, E, K, n_out] matrix comes with its "
                         "`layer`, one layer's without")
    if layer is None:
        w, layer = w[None], 0
        w_up = None if w_up is None else w_up[None]
    out_dtype = out_dtype or x.dtype
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    group_sizes = group_sizes.astype(jnp.int32)
    if path == dispatch.REFERENCE:
        return _expert_matmul_ref(x, group_sizes, w, w_up, layer, tile,
                                  out_dtype)
    return _expert_matmul_kernel(x, group_sizes, w, w_up, layer, tile,
                                 out_dtype,
                                 interpret=(path == dispatch.INTERPRET))

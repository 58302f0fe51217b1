"""Grouped matrix products for a dropless expert layer, by kernels that
move their own rows: tokens are fetched by id on the way in, weighted
results are added by id on the way out, and nothing is dropped.

A sparse-expert layer sends every token to ``k`` of ``E`` experts.  With
the (token, expert) pairs sorted by expert the layer is a *grouped* matrix
product: rows ``[start_e, start_e + size_e)`` of one buffer times ``w[e]``.
The sizes are known only on the device, so the product has to be ragged: no
capacity, no padding to the fullest expert, no work for an expert nobody
chose.

Layout (``expert_layout``).  The buffer's rows hold the groups in
ascending expert order, group ``e`` starting at a row that is a multiple of
``tile`` (``starts[e]``), so a tile of ``tile`` rows belongs to one expert
and the tiles that hold a pair are the first ``sum(tiles)`` of the buffer.
Its row count ``N`` is static: pairs rounded up to a tile plus one tile of
slack an expert (``buffer_rows``), so nothing can overflow whatever the
routing.  Rows between a group's end and the next group's start are
padding.  **No buffer of token rows is ever built**: what exists at ``[N]``
is ``rows``, the token id of each buffer row (int32, and float32 routing
weights beside it), and, between the two calls of a layer, ``h [N, F]``, of
which only visited tiles are written or read.

Two calls walk one work list (``_items``): items ``(column block, expert,
row tile)``, a column block at a time and within it the visited tiles in
buffer order, which is expert order.  Consecutive items of one (block,
expert) keep the weight block's index, so Pallas does not fetch it again:
**each held expert's weights are read at most once a call**, and not at
all for an expert without a pair.  The grid is ``(n,)`` with ``n`` the
number of real items (a dynamic bound): a step's cost is the tiles that
hold a pair, not ``N``.

- ``expert_matmul`` (*in*: gate and up).  ``u [T, K]`` stays in HBM; for
  an item the kernel copies the tile's **real** rows (``live`` of them,
  never the padding) from ``u`` by the ids in ``rows`` into one of two
  VMEM buffers, the next item's copies in flight while this one is
  multiplied (``_fetch_rows``: the pattern of ``paged_attention
  ._fetch_pages``).  One full-depth product ``x_tile [tile, K] @
  w[e][:, block]`` in float32 out of the unit; with ``w_up`` the item
  computes ``silu(x @ w[e]) * (x @ w_up[e])`` (the SwiGLU's first half:
  gate and up in one pass over the rows).  A copy addresses whole
  ``(8, 128)`` tiles of an array, not one row of it, so the call hands the
  kernel ``u`` as 32-bit slabs ``[T, 8, K / 8]`` (a token is one aligned
  piece; exact for bfloat16 and float32) and the product runs over the
  slab's eight column ranges.
- ``expert_matmul_add`` (*out*: down and combine).  The item's float32
  product, straight from the unit, is multiplied row by row by the row's
  routing weight and **added into its token's row** of a float32
  accumulator ``y [T, block]`` that stays in VMEM while a column block's
  items run (an output block whose index changes only with the column
  block; zeroed at its first visit).  Grid steps run in order on one core:
  the read-add-write needs no atomics, and a token's contributions arrive
  in ascending expert order in a row that starts at zero, so its result
  does not depend on its batch-mates.

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

__all__ = ["expert_matmul", "expert_matmul_add", "expert_layout",
           "buffer_rows", "visited_rows"]

# bytes of one weight operand's column block in VMEM (two buffers each)
_BLOCK_BYTES = 6 << 20
# bytes of ``expert_matmul_add``'s float32 accumulator block (two buffers)
_ACC_BYTES = 8 << 20
_VMEM_LIMIT = 64 << 20
# a token's row as a copy fetches it: [_SLAB, K / _SLAB] of 32 bits
_SLAB = 8


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


def visited_rows(group_sizes, tile):
    """Rows of the tiles that hold a pair: what the two calls fetch room
    for, multiply and add, padding included (``sum(group_sizes)`` of them
    are pairs)."""
    return jnp.sum(expert_layout(group_sizes, tile)[1]) * tile


def _tile_experts(group_sizes, tile, n_tiles):
    """``(expert [n_tiles], live [n_tiles], visited [1])``: the expert of
    each buffer tile, how many of its rows are real pairs, and how many
    tiles hold any."""
    starts, tiles = expert_layout(group_sizes, tile)
    ends = jnp.cumsum(tiles)
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    # the expert of tile t: how many experts end at or before it
    e = jnp.minimum(jnp.sum(t[:, None] >= ends[None, :], axis=1),
                    group_sizes.shape[0] - 1).astype(jnp.int32)
    live = jnp.clip(starts[e] + group_sizes[e] - t * tile, 0, tile)
    return e, live.astype(jnp.int32), ends[-1:].astype(jnp.int32)


def _items(group_sizes, tile, blocks, n_tiles):
    """The flat work list: ``(expert, block, row tile, n, live)``, the
    first three ``[blocks * n_tiles]`` int32 with the first ``n [1]`` in
    use, ordered column block, then the visited tiles in buffer order
    (which is expert order); ``live [n_tiles]`` is the real rows of each
    buffer tile.  With no pair at all the list still has one item a column
    block, on a tile without a live row: the call's output is then written
    (zeros), not left as it was."""
    e, live, visited = _tile_experts(group_sizes, tile, n_tiles)
    per = jnp.maximum(visited[0], 1)
    i = jnp.arange(blocks * n_tiles, dtype=jnp.int32)
    block = jnp.minimum(i // per, blocks - 1).astype(jnp.int32)
    row_tile = (i % per).astype(jnp.int32)
    n = (blocks * per).astype(jnp.int32).reshape(1)
    return e[row_tile], block, row_tile, n, live


def _row_experts(group_sizes, tile, n_rows):
    """``(expert of each buffer row [N], the row is a real pair [N])``."""
    e, live, _ = _tile_experts(group_sizes, tile, n_rows // tile)
    r = jnp.arange(n_rows, dtype=jnp.int32)
    return jnp.repeat(e, tile), r % tile < jnp.repeat(live, tile)


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


# ------------------------------------------------------------------ kernels


def _fetch_rows(slabs, buf, sem, slot, token_of, live, wait):
    """Start (or wait for) the copies of one item's first ``live`` rows out
    of HBM into buffer ``slot``, row ``r`` from token ``token_of(r)``.
    Rows past ``live`` are neither fetched nor waited for."""
    def one(r, carry):
        # a wait needs the copy's shape and semaphore, not its source
        copy = pltpu.make_async_copy(
            slabs.at[0 if wait else token_of(r)], buf.at[slot, r],
            sem.at[slot])
        copy.wait() if wait else copy.start()
        return carry

    jax.lax.fori_loop(0, live, one, 0)


def _fetch_kernel(e_ref, blk_ref, tile_ref, n_ref, live_ref, layer_ref,
                  rows_ref, slabs, w_ref, *rest, tile, swiglu, dtype):
    del e_ref, blk_ref, layer_ref          # only the index maps read them
    up_ref, o_ref, buf, sem = rest if swiglu else (None, *rest)
    i = pl.program_id(0)
    n, n_max = n_ref[0], tile_ref.shape[0]
    slot = i % 2

    def fetch(item, slot, wait):
        """``item``'s live rows, by their token ids, into buffer ``slot``."""
        t = tile_ref[item]
        _fetch_rows(slabs, buf, sem, slot,
                    lambda r: rows_ref[t * tile + r], live_ref[t], wait)

    @pl.when(i == 0)
    def _prime():
        fetch(0, 0, wait=False)

    @pl.when(i + 1 < n)
    def _next():
        fetch(jnp.minimum(i + 1, n_max - 1), 1 - slot, wait=False)

    fetch(i, slot, wait=True)
    pieces, width = buf.shape[2:]

    def product(m_ref):
        # x @ m over the slab's column ranges: columns s * width ... of x
        # are piece s of every row
        y = None
        for s in range(pieces):
            x = buf[slot, :, s, :].astype(dtype)
            part = jnp.dot(x, m_ref[0, 0, s * width:(s + 1) * width, :],
                           precision=mxu_precision(x, m_ref),
                           preferred_element_type=jnp.float32)
            y = part if y is None else y + part
        return y

    y = product(w_ref)
    if swiglu:
        y = jax.nn.silu(y) * product(up_ref)
    # rows past the group's end are padding: zeros, whatever they held
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    live = live_ref[tile_ref[i]]
    o_ref[...] = jnp.where(row < live, y, 0.0).astype(o_ref.dtype)


def _add_kernel(e_ref, blk_ref, tile_ref, n_ref, live_ref, layer_ref,
                rows_ref, scale_ref, h_ref, w_ref, y_ref, p_ref, *, tile):
    del e_ref, n_ref, layer_ref            # only the index maps read them
    i = pl.program_id(0)

    @pl.when((i == 0) | (blk_ref[jnp.maximum(i - 1, 0)] != blk_ref[i]))
    def _first_visit():
        y_ref[...] = jnp.zeros_like(y_ref)

    h = h_ref[...]
    p_ref[...] = jnp.dot(h, w_ref[0, 0], precision=mxu_precision(h, w_ref),
                         preferred_element_type=jnp.float32)
    t = tile_ref[i]

    def add(r, carry):
        token = pl.ds(rows_ref[t * tile + r], 1)
        y_ref[token, :] += scale_ref[t * tile + r] * p_ref[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, live_ref[t], add, 0)


def _column_block(K, n_out, itemsize, acc_rows=0):
    """Columns of one weight block: the widest multiple of 128 dividing
    ``n_out`` whose ``[K, block]`` stays under ``_BLOCK_BYTES`` and, where
    a float32 accumulator of ``acc_rows`` rows rides beside it, whose
    ``[acc_rows, block]`` stays under ``_ACC_BYTES``."""
    best = None
    for block in range(128, n_out + 1, 128):
        if (n_out % block == 0 and K * block * itemsize <= _BLOCK_BYTES
                and acc_rows * block * 4 <= _ACC_BYTES):
            best = block
    return best or n_out


def _w_map(i, e, blk, rt, n, live, layer, *_):
    return (layer[0], e[i], 0, blk[i])


def _pallas_call(kernel, name, prefetch, grid, in_specs, out_specs,
                 out_shape, scratch_shapes, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=prefetch, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes)
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret, name=name, **kwargs)


def _fetch_call(u, rows, group_sizes, w, w_up, layer, tile, out_dtype,
                interpret):
    N, (T, K) = rows.shape[0], u.shape
    n_out = w.shape[-1]
    block = _column_block(K, n_out, w.dtype.itemsize)
    items = _items(group_sizes, tile, n_out // block, N // tile)
    swiglu = w_up is not None
    w_spec = pl.BlockSpec((1, 1, K, block), _w_map)
    call = _pallas_call(
        functools.partial(_fetch_kernel, tile=tile, swiglu=swiglu,
                          dtype=u.dtype),
        "expert_matmul", 7, (items[3][0],),
        [pl.BlockSpec(memory_space=pl.ANY), w_spec]
        + ([w_spec] if swiglu else []),
        pl.BlockSpec((tile, block), lambda i, e, blk, rt, *_: (rt[i], blk[i])),
        jax.ShapeDtypeStruct((N, n_out), out_dtype),
        [pltpu.VMEM((2, tile, _SLAB, K // _SLAB), jnp.float32),
         pltpu.SemaphoreType.DMA((2,))],
        interpret)
    slabs = u.astype(jnp.float32).reshape(T, _SLAB, K // _SLAB)
    return call(*items, layer, rows, slabs, w, *((w_up,) if swiglu else ()))


def _add_call(h, rows, row_weights, group_sizes, w, layer, tile, tokens,
              interpret):
    N, K = h.shape
    n_out = w.shape[-1]
    block = _column_block(K, n_out, w.dtype.itemsize, acc_rows=tokens)
    items = _items(group_sizes, tile, n_out // block, N // tile)
    call = _pallas_call(
        functools.partial(_add_kernel, tile=tile),
        "expert_matmul_add", 8, (items[3][0],),
        [pl.BlockSpec((tile, K), lambda i, e, blk, rt, *_: (rt[i], 0)),
         pl.BlockSpec((1, 1, K, block), _w_map)],
        pl.BlockSpec((tokens, block), lambda i, e, blk, *_: (0, blk[i])),
        jax.ShapeDtypeStruct((tokens, n_out), jnp.float32),
        [pltpu.VMEM((tile, block), jnp.float32)],
        interpret)
    return call(*items, layer, rows, row_weights, h, w)


# ------------------------------------------------------------- public entry


def _stacked(w, w_up, layer):
    """``(w [L, E, K, n_out], w_up, layer [1] int32)`` from either form."""
    if (w.ndim == 4) != (layer is not None):
        raise ValueError("a stacked [L, E, K, n_out] matrix comes with its "
                         "`layer`, one layer's without")
    if layer is None:
        w, layer = w[None], 0
        w_up = None if w_up is None else w_up[None]
    return w, w_up, jnp.asarray(layer, jnp.int32).reshape(1)


def _whole_tiles(rows, tile):
    if rows.shape[0] % tile:
        raise ValueError(f"{rows.shape[0]} buffer rows are not whole tiles "
                         f"of {tile}")


def expert_matmul(u, rows, group_sizes, w, w_up=None, *, tile, layer=None,
                  out_dtype=None, path=None):
    """``h [N, n_out]``: buffer row ``r``, in the layout of
    ``expert_layout(group_sizes [E], tile)``, is ``u[rows[r]]`` (``u [T,
    K]``, ``rows [N]`` int32 token ids) times its group's matrix
    ``w[layer] [E, K, n_out]``; with ``w_up`` (same shape)
    ``silu(x @ w[e]) * (x @ w_up[e])``.  ``w [L, E, K, n_out]`` comes with
    its ``layer``, ``[E, K, n_out]`` without.  Only the rows that are pairs
    are fetched (``rows`` of padding is not read); padding rows of a
    visited tile are zeros; tiles past the last group are **not written**.
    ``N`` is a multiple of ``tile``, ``K`` of 8."""
    _whole_tiles(rows, tile)
    w, w_up, layer = _stacked(w, w_up, layer)
    out_dtype = out_dtype or u.dtype
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    group_sizes = group_sizes.astype(jnp.int32)
    if path == dispatch.REFERENCE:
        return _expert_matmul_ref(jnp.take(u, rows, axis=0), group_sizes, w,
                                  w_up, layer[0], tile, out_dtype)
    if u.shape[1] % _SLAB:
        raise ValueError(f"rows of {u.shape[1]} are not {_SLAB} pieces")
    return _fetch_call(u, rows, group_sizes, w, w_up, layer, tile, out_dtype,
                       interpret=(path == dispatch.INTERPRET))


def expert_matmul_add(h, rows, row_weights, group_sizes, w, *, tokens, tile,
                      layer=None, path=None):
    """``y [tokens, n_out]`` float32, from zero: for every buffer row ``r``
    that is a pair, ``y[rows[r]] += row_weights[r] * (h[r] @ w[e_r])``,
    in ascending ``r`` (so, for a token, in ascending expert order), the
    product, the weight and the sum in float32.  ``h [N, K]`` in the layout
    of ``expert_layout(group_sizes, tile)`` (what ``expert_matmul`` wrote),
    ``rows [N]`` int32 and ``row_weights [N]`` float32 per buffer row,
    ``w`` and ``layer`` as ``expert_matmul``'s.  A token without a pair,
    and every token when there is no pair at all, gets zeros."""
    _whole_tiles(rows, tile)
    w, _, layer = _stacked(w, None, layer)
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    group_sizes = group_sizes.astype(jnp.int32)
    row_weights = row_weights.astype(jnp.float32)
    if path == dispatch.REFERENCE:
        out = _expert_matmul_ref(h, group_sizes, w, None, layer[0], tile,
                                 jnp.float32)
        real = _row_experts(group_sizes, tile, h.shape[0])[1]
        return jnp.zeros((tokens, w.shape[-1]), jnp.float32).at[
            jnp.where(real, rows, tokens)].add(
            out * row_weights[:, None], mode="drop")
    return _add_call(h, rows, row_weights, group_sizes, w, layer, tile,
                     tokens, interpret=(path == dispatch.INTERPRET))

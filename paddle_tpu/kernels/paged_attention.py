"""Ragged paged attention — one fused prefill+decode kernel over a
block-paged KV cache.

The serving engine (paddle_tpu/serving) stores K/V in fixed-size pages so
sequences of very different lengths share one physical pool without
padding ("Ragged Paged Attention", arXiv:2604.15464 — the TPU analog of
vLLM's PagedAttention).  Each batch row is at an *arbitrary* point in its
life: a mid-prefill prompt chunk of ``query_len`` tokens, or a decode
step (the degenerate ``query_len == 1`` chunk).  One kernel serves both,
which is what lets the engine schedule prompt chunks as ordinary rows
next to decoding rows instead of running prefill as a separate
batch-stalling pass.

Row semantics: row ``b`` contributes ``query_lens[b]`` query tokens whose
keys/values have just been appended to its pages, so its chunk occupies
absolute positions ``context_lens[b] - query_lens[b] ..
context_lens[b] - 1``.  Query token ``t`` attends causally to every kv
position ``<= context_lens[b] - query_lens[b] + t``.  ``query_lens[b] ==
0`` marks an idle row (output zeros).

Two implementations with one contract (``path=`` picks one; the default
comes from ``kernels.dispatch``: the kernel on a TPU, the reference
elsewhere):

- ``_ragged_attention_ref`` — pure-jnp gather + fp32 softmax.  Serves CPU
  tests and is the numerics oracle.
- the Pallas kernel — a grid as long as the work.  A *work item* is one
  tile of a live row's context: (batch row, tile of ``_pages_per_item``
  pages), at least ``_KEY_TILE`` key positions.  ``ragged_work_items``
  lists them from the two length vectors (row by row, a row's tiles
  ascending, nothing for an idle row), the list rides in scalar prefetch
  with the page table, the lengths and the layer, and the grid is
  ``(n,)``, a dynamic bound: no grid step is spent on a page that does
  not exist.  The pools stay in HBM (``memory_space=pl.ANY``); an item's
  pages are copied ``[layer, table[row, j]] -> VMEM`` by
  ``make_async_copy`` into one of two buffers, the next item's pages in
  flight while this one is multiplied, and pages past the row's context
  are neither fetched nor waited for (their positions are masked).  The
  tiles arrive token-major and are turned head-major in VMEM
  (``swapaxes``: the heads are the products' batch).  Both products take
  their operands as stored (bf16 into the unit, float32 out of it: a
  bf16 product is exact in float32); the softmax, its
  running maximum and sum and the accumulator are float32 scratch carried
  across a row's items, flash-attention style, with the causal mask
  applied relative to the row's context offset.  Two bodies are compiled
  and a row's query count picks one per item: the first ``_SMALL_Q``
  query slots (every decode row) or the chunk's full width.

Layouts:
  q            [B, Q, H, hd]        Q = max query tokens per row, padded
  k/v_pages    [L, P, page_size, H, hd] every layer's page pool, stacked
               and token-major (one position's heads are contiguous: the
               model writes a token's keys as one 4 KiB row), with
               ``layer`` (a traced int32 scalar) naming the one to read:
               the serving step hands the kernel its whole donated pool
               and the copies take ``[layer, page]`` out of HBM, so no
               layer's pages are ever sliced out or copied.
               [P, page_size, H, hd] (one layer's pool, ``layer`` left
               out) is the same kernel under a free ``[None]``.
  page_tables  [B, max_pages] int32  physical page id per logical page
  query_lens   [B] int32             valid query tokens (0 = idle row)
  context_lens [B] int32             kv tokens incl. this chunk
Returns [B, Q, H, hd] in q.dtype; padded query slots and idle rows
return zeros.

A decode-only call (one query token per row) is the Q == 1 case of the
same entry: ``query_lens = context_lens > 0``.

Grouped heads and selected pages (``selected=``, block-sparse attention
with grouped-query heads).  The pool is then head-major,
``[L, P, Hkv, page_size, hd]`` with ``Hkv`` dividing ``H``: one page of
one key/value head is one contiguous ``[page_size, hd]`` block, shared by
the ``H / Hkv`` query heads of its group.  ``selected = (sel_blocks,
dense_len)``: ``sel_blocks [B, Hkv, Q, K]`` int32 names, for every query
token and group, the logical pages (blocks of ``page_size`` positions)
that token attends over, -1 for none; a token whose context
(position + 1) is at most ``dense_len`` ignores its list and attends
causally over every position (``sel_blocks=None``: every token does).
A third entry, ``sel_mask [B, Hkv, Q, W]`` bool, may give the same lists
as flags over the ``W`` logical pages (a superset is fine: it only decides
which pages are fetched, the lists decide what a token reads); without it
the flags are scattered from the lists.
So one call serves dense rows, sparse decode rows and sparse prefill
chunks, each chunk token with its own blocks.  The kernel walks a flat
list of (row, group, 128-token query tile, up to ``pages`` listed pages)
items built from the lists with plain ``jnp`` (``listed_work_items``): a
page that no token of a tile selected is never fetched, the rest are masked
per token inside the kernel, and the grid is as long as the list (a dynamic
grid bound), so a decode row costs its K pages and not the width of the
page table.  ``pages`` follows from the page size alone: whole pages
covering ``_LISTED_KEY_TILE`` = 512 key positions, 8 pages of 64, one page
of 512 (settled on the chip, PERF.md §6 PR 36).  A segment's pages go into
its items in ascending order, so only its last item may be partly filled.
The pools stay in HBM and an item's pages are copied ``[layer, table[row,
lpage], group] -> VMEM`` into one of two buffers, the next item's copies in
flight while this one is multiplied — ``_fetch_pages``, the equal-heads
kernel's own fetch — and a slot of an item that holds no page is neither
fetched nor waited for, is masked out of the scores and meets zeros or old
pool values in the value buffer.  Both products run once an item over its
``pages * page_size`` positions, and the running maximum, sum and the
accumulator's rescale once an item; the per-token rules (causal edge,
``dense_len``, the token's own list, ``window``) are applied page by page
of the item.  This path is chosen statically by ``selected is not None``.
A step that wants to count the list builds it itself and hands it in
(``items=``).

A lower edge (``window=``, static, with the grouped mode).  A sliding-window
layer's token at position ``p`` reads positions ``p - window < j <= p``.
The work list then holds, for a tile of query tokens, only the pages its
tokens' windows reach (the pages behind them are never fetched: their
entries in the page table may be stale, the cache manager has given the
pages back), and the kernel masks the edge per token.  ``H / Hkv`` need
not be a power of two (9 query heads a group is fine: the group is a
leading axis of the tiles).

Queries packed in tiles (``q_tiles=``, with the grouped mode and no lists).
``[B, Q, H, hd]`` pads every row to the widest chunk: at 64 rows and chunks
of 1024 that is 65,536 query slots for a step's 1,088 tokens, and writing,
turning and reading them took more of a call than its pages did (2.5 ms of
a window layer's call against 0.04 ms of keys and values: chip run, PR 35).
With ``q_tiles = (tile_rows [NT], tile_index [NT])`` the queries come as
``[NT, tile, H, hd]``: tile ``n`` holds the query slots ``tile_index[n] *
tile ...`` of batch row ``tile_rows[n]`` (``B`` marks an unused tile), one
tile a decode row and one more for every ``tile`` tokens of a chunk
(``models/ragged.py`` ``RaggedView.pad_tiles``), and the result comes back
in the same layout.  The items are (tile, group, page); the body is the
grouped mode's without the lists.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.linalg import mxu_precision
from . import dispatch

__all__ = ["ragged_paged_attention", "ragged_work_items",
           "listed_work_items"]

_NEG_INF = -1e30


# key positions one work item of the equal-heads kernel covers (whole
# pages: at least this many), and the query slots its narrow body computes
_KEY_TILE = 128
_SMALL_Q = 16
# key positions one work item of the grouped-heads kernel covers (whole
# pages: 8 of 64, one of 512).  The wide body's float32 score tile is then
# [2048, 512] at 16 heads a group, inside the default VMEM limit; 1,024
# positions need it raised (17 MB) and took 13% less on the chip for the
# whole call at the long-context cell's shapes, 256 40% more (PERF.md §6
# PR 36)
_LISTED_KEY_TILE = 512


def _stacked(k_pages, v_pages, layer):
    """(k_pages, v_pages, layer) with the pool in its stacked layout: one
    layer's pool is a stack of one, read at layer 0."""
    if k_pages.ndim == 4:
        return k_pages[None], v_pages[None], 0
    return k_pages, v_pages, layer


# ---------------------------------------------------------------- reference


def _ragged_attention_ref(q, k_pages, v_pages, page_tables, query_lens,
                          context_lens, scale, layer=None):
    """Gather-then-mask oracle: [B, max_kv] dense view of the pages with
    the per-row causal mask applied at each query token's absolute
    position."""
    B, Q, H, hd = q.shape
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    page_size = k_pages.shape[2]
    max_pages = page_tables.shape[1]
    # one gather of the rows' pages out of the stacked pool: taking
    # pool[layer] first would materialize a whole layer's pages
    k = k_pages[layer, page_tables]                 # [B, M, ps, H, hd]
    v = v_pages[layer, page_tables]
    k = k.reshape(B, max_pages * page_size, H, hd)
    v = v.reshape(B, max_pages * page_size, H, hd)
    s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    t = jnp.arange(max_pages * page_size)
    tq = jnp.arange(Q)
    # query token tq of row b sits at absolute position ctx - q_len + tq
    pos = (context_lens - query_lens)[:, None] + tq[None, :]       # [B, Q]
    ok = ((t[None, None, :] <= pos[:, :, None])
          & (tq[None, :, None] < query_lens[:, None, None]))
    s = jnp.where(ok[:, None], s, _NEG_INF)
    # fp32 softmax; fully-masked rows (padded query slots / idle rows)
    # yield uniform junk — zeroed below rather than divided by 0
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bthd->bqhd", p, v.astype(jnp.float32))
    out = jnp.where((tq[None, :] < query_lens[:, None])[:, :, None, None],
                    out, 0.0)
    return out.astype(q.dtype)


# ------------------------------------------------------------------- kernel


def _pages_per_item(page_size, max_pages, key_tile=_KEY_TILE):
    """Pages in one work item: whole pages covering ``key_tile`` key
    positions, and never more than a row's page table holds."""
    return max(1, min(max_pages, -(-key_tile // page_size)))


def _fetch_pages(pools, bufs, sem, slot, page_of, live, pages, page_size,
                 wait):
    """Start (or wait for) the copies of one item's first ``live`` pages,
    keys and values, out of HBM into buffer ``slot``, page ``j`` at
    positions ``j * page_size ...``; ``page_of(j)`` is the index of the
    item's ``j``-th page in a pool.  Pages past ``live`` are neither
    fetched nor waited for."""
    for j in range(pages):
        @pl.when(j < live)
        def _():
            src = page_of(j)
            at = pl.ds(j * page_size, page_size)
            for p, (hbm, buf) in enumerate(zip(pools, bufs)):
                copy = pltpu.make_async_copy(
                    hbm.at[src], buf.at[slot, at], sem.at[p, slot])
                copy.wait() if wait else copy.start()


def ragged_work_items(query_lens, context_lens, page_size, max_pages):
    """The equal-heads kernel's grid, as a list: one item for every tile of
    ``_pages_per_item`` pages that a live row's context reaches, row by
    row and in ascending order within a row; an idle row has none.
    Returns ``(rows, tiles, n)``: the item's batch row and its tile within
    that row, both ``[B * tiles_per_row]`` int32 with the first ``n [1]``
    in use.  It depends on the two length vectors alone, so a step builds
    it once and hands it to every layer's call (``items=``)."""
    B = query_lens.shape[0]
    tile = _pages_per_item(page_size, max_pages) * page_size
    per_row = -(-max_pages * page_size // tile)
    # a live row always has an item: the one that writes its output
    counts = jnp.where(query_lens > 0,
                       jnp.clip(-(-context_lens // tile), 1, per_row), 0)
    ends = jnp.cumsum(counts)
    i = jnp.arange(B * per_row, dtype=jnp.int32)
    # the row of item i: how many rows end at or before it
    rows = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), B - 1)
    tiles = i - (ends - counts)[rows]
    return (rows.astype(jnp.int32), tiles.astype(jnp.int32),
            ends[-1:].astype(jnp.int32))


def _ragged_kernel(row_ref, tile_ref, n_ref, tbl_ref, qlen_ref, ctx_ref,
                   layer_ref, q_ref, kp_hbm, vp_hbm, o_ref, k_buf, v_buf,
                   sem, acc_ref, m_ref, l_ref, *, scale, page_size, pages,
                   small):
    i = pl.program_id(0)
    n = n_ref[0]
    layer = layer_ref[0]
    n_max, max_pages = row_ref.shape[0], tbl_ref.shape[1]
    Q = q_ref.shape[1]
    row = row_ref[i]
    first = (i == 0) | (row_ref[jnp.maximum(i - 1, 0)] != row)
    last = (i == n - 1) | (row_ref[jnp.minimum(i + 1, n_max - 1)] != row)
    q_len, ctx = qlen_ref[row], ctx_ref[row]
    start = tile_ref[i] * (pages * page_size)    # the item's first position
    slot = i % 2

    def fetch(item, slot, wait):
        """``item``'s pages into buffer ``slot``: the pages its row's
        context reaches and no others."""
        r = row_ref[item]
        page0 = tile_ref[item] * pages
        live = (ctx_ref[r] + page_size - 1) // page_size - page0
        _fetch_pages(
            (kp_hbm, vp_hbm), (k_buf, v_buf), sem, slot,
            lambda j: (layer,
                       tbl_ref[r, jnp.minimum(page0 + j, max_pages - 1)]),
            live, pages, page_size, wait)

    @pl.when(i == 0)
    def _prime():
        # positions no page was fetched for are masked out of the scores,
        # but 0 x (whatever the buffer held) has to be 0 in the second
        # product: after this the buffers only ever hold pool values
        v_buf[:] = jnp.zeros_like(v_buf)
        fetch(0, 0, wait=False)

    @pl.when(i + 1 < n)
    def _next():
        fetch(jnp.minimum(i + 1, n_max - 1), 1 - slot, wait=False)

    fetch(i, slot, wait=True)

    def item(R):
        """The item against the row's first ``R`` query slots (static): a
        decode row pays for ``small`` slots, not for the chunk's width."""
        @pl.when(first)
        def _init():
            acc_ref[:, :R] = jnp.zeros_like(acc_ref[:, :R])
            m_ref[:, :R] = jnp.full_like(m_ref[:, :R], _NEG_INF)
            l_ref[:, :R] = jnp.zeros_like(l_ref[:, :R])

        # queries, keys and values are stored token-major, [.., H, hd],
        # as the model's projection and its page scatter write them; the
        # unit wants the heads as the batch, so the tiles are turned in
        # VMEM (keys and values: 0.2 us an item)
        q = jnp.swapaxes(q_ref[0, :R], 0, 1)         # [H, R, hd]
        k = jnp.swapaxes(k_buf[slot], 0, 1)          # [H, T, hd]
        v = jnp.swapaxes(v_buf[slot], 0, 1)
        T = k.shape[1]
        # operands as they are stored (a bf16 product is exact in
        # float32), float32 out of the unit and through the softmax;
        # float32 operands get the unit's true-float32 passes, the
        # framework's rule (ops/linalg.py mxu_precision)
        precision = mxu_precision(q, k)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32) * scale      # [H, R, T]
        tq = jax.lax.broadcasted_iota(jnp.int32, (1, R, T), 1)
        kv = start + jax.lax.broadcasted_iota(jnp.int32, (1, R, T), 2)
        # causal relative to the row's context offset: query tq sits at
        # absolute position ctx - q_len + tq
        ok = (kv <= ctx - q_len + tq) & (tq < q_len)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:, :R]                        # [H, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :R] = l_ref[:, :R] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
        acc_ref[:, :R] = acc_ref[:, :R] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[:, :R] = m_new

        @pl.when(last)
        def _final():
            l = l_ref[:, :R]
            o = acc_ref[:, :R] / jnp.where(l == 0.0, 1.0, l)
            # padded query slots accumulated garbage behind the mask with
            # m == -inf; zero them so the kernel matches the ref everywhere
            tq = jax.lax.broadcasted_iota(jnp.int32, (1, R, 1), 1)
            o = jnp.where(tq < q_len, o, 0.0).astype(o_ref.dtype)
            o_ref[0, :R] = jnp.swapaxes(o, 0, 1)
            if R < Q:
                o_ref[0, R:] = jnp.zeros_like(o_ref[0, R:])

    if small < Q:
        pl.when(q_len <= small)(lambda: item(small))
        pl.when(q_len > small)(lambda: item(Q))
    else:
        item(Q)


def _ragged_attention_kernel(q, k_pages, v_pages, page_tables, query_lens,
                             context_lens, scale, interpret, layer=None,
                             items=None):
    B, Q, H, hd = q.shape
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    page_size = k_pages.shape[2]
    max_pages = page_tables.shape[1]
    pages = _pages_per_item(page_size, max_pages)
    if items is None:
        items = ragged_work_items(query_lens, context_lens, page_size,
                                  max_pages)
    rows, tiles, n = items
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_block(i, rows, *_):
        return (rows[i], 0, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    tile = (2, pages * page_size, H, hd)         # two buffers of one item
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n[0],),
        in_specs=[pl.BlockSpec((1, Q, H, hd), q_block), in_hbm, in_hbm],
        out_specs=pl.BlockSpec((1, Q, H, hd), q_block),
        scratch_shapes=[
            pltpu.VMEM(tile, k_pages.dtype),
            pltpu.VMEM(tile, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H, Q, hd), jnp.float32),
            pltpu.VMEM((H, Q, 1), jnp.float32),
            pltpu.VMEM((H, Q, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page_size, pages=pages,
                               small=min(_SMALL_Q, Q))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, H, hd), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(rows, tiles, n, page_tables, query_lens, context_lens, layer, q,
      k_pages, v_pages)
    # an idle row has no item: nothing wrote its block
    return jnp.where((query_lens > 0)[:, None, None, None], out,
                     jnp.zeros((), q.dtype))


# ------------------------------------------ grouped heads, selected pages


def _token_positions(Q, query_lens, context_lens):
    """(pos [B, Q] absolute position of each padded query slot, valid)."""
    tq = jnp.arange(Q)
    pos = (context_lens - query_lens)[:, None] + tq[None, :]
    return pos, tq[None, :] < query_lens[:, None]


def _selected_onehot(sel_blocks, W):
    """[B, Hkv, Q, W] bool: logical page w is in the token's list."""
    B, Hkv, Q, K = sel_blocks.shape
    idx = jnp.where(sel_blocks < 0, W, sel_blocks)       # -1 => dropped
    b, h, t = jnp.meshgrid(jnp.arange(B), jnp.arange(Hkv), jnp.arange(Q),
                           indexing="ij")
    return jnp.zeros((B, Hkv, Q, W), bool).at[
        b[..., None], h[..., None], t[..., None], idx].set(True, mode="drop")


def _listed_attention_ref(q, k_pages, v_pages, page_tables, query_lens,
                          context_lens, scale, layer, sel_blocks, dense_len,
                          window=None):
    """Gather-then-mask oracle of the grouped / selected mode."""
    B, Q, H, hd = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    G, W = H // Hkv, page_tables.shape[1]
    T = W * page_size
    gather = lambda pool: pool[layer, page_tables].transpose(
        0, 2, 1, 3, 4).reshape(B, Hkv, T, hd).astype(jnp.float32)
    k, v = gather(k_pages), gather(v_pages)
    qg = q.astype(jnp.float32).reshape(B, Q, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bhtd->bhgqt", qg, k) * scale
    pos, valid = _token_positions(Q, query_lens, context_lens)
    t = jnp.arange(T)
    ok = (t[None, None, :] <= pos[:, :, None]) & valid[:, :, None]
    if window is not None:
        ok = ok & (t[None, None, :] > pos[:, :, None] - window)
    ok = jnp.broadcast_to(ok[:, None], (B, Hkv, Q, T))
    if sel_blocks is not None:
        allowed = (_selected_onehot(sel_blocks, W)
                   | (pos + 1 <= dense_len)[:, None, :, None])
        ok = ok & jnp.repeat(allowed, page_size, axis=-1)
    s = jnp.where(ok[:, :, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqt,bhtd->bqhgd", p, v).reshape(B, Q, H, hd)
    return jnp.where(valid[:, :, None, None], out, 0.0).astype(q.dtype)


def _work_items(visit, n_max):
    """The flat list of the ``True`` entries of ``visit [S, W]``, segment
    by segment and in ascending order within one: ``(segment, w, n)``,
    the first two ``[n_max]`` int32 with the first ``n`` in use."""
    S, W = visit.shape
    counts = visit.sum(-1).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    i = jnp.arange(n_max, dtype=jnp.int32)
    # the segment of item i: how many segments end at or before it (a
    # compare and a sum: a binary search was 8 passes, 1.35 ms a call)
    seg = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1),
                      S - 1).astype(jnp.int32)
    rank = i - (ends - counts)[seg]
    # the True columns of each segment first, in ascending order
    order = jnp.argsort(~visit, axis=-1, stable=True).astype(jnp.int32)
    w = order[seg, jnp.clip(rank, 0, W - 1)]
    return seg, w, ends[-1]


def _paged_work_items(visit, n_max, pages):
    """``_work_items`` with up to ``pages`` entries of one segment an item:
    segment by segment, a segment's entries in ascending order,
    ``ceil(count / pages)`` items a segment, so only its last one may be
    partly filled.  Returns ``(seg [n_max], w [n_max * pages], count
    [n_max], n)`` int32 with the first ``n`` items in use: item ``i`` is
    ``count[i]`` entries of segment ``seg[i]``, ``w[i * pages : i * pages +
    count[i]]`` (a flat array: it rides in scalar memory).  (The tiled
    mode keeps the list of single entries above until its body takes items
    of several pages too.)"""
    S, W = visit.shape
    counts = visit.sum(-1).astype(jnp.int32)
    per_seg = -(-counts // pages)
    ends = jnp.cumsum(per_seg)
    i = jnp.arange(n_max, dtype=jnp.int32)
    seg = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1),
                      S - 1).astype(jnp.int32)
    rank = (i - (ends - per_seg)[seg]) * pages    # of the item's first entry
    order = jnp.argsort(~visit, axis=-1, stable=True).astype(jnp.int32)
    at = rank[:, None] + jnp.arange(pages, dtype=jnp.int32)[None, :]
    w = order[seg[:, None], jnp.clip(at, 0, W - 1)]
    count = jnp.clip(counts[seg] - rank, 0, pages)
    return seg, w.reshape(-1), count, ends[-1]


def listed_work_items(query_lens, context_lens, page_size, max_pages, max_q,
                      groups, selected=(None, 2 ** 30), total_q=None,
                      window=None):
    """The grouped-heads kernel's grid, as a list: one item for every (row,
    key/value group, 128-token query tile) and every ``pages`` logical
    pages, in ascending order, that some token of the tile reads (its list,
    or within ``dense_len`` everything up to its own position, from the
    ``window``'s edge on).  ``selected``, ``total_q`` and ``window`` are
    ``ragged_paged_attention``'s, ``max_q`` its padded query width ``Q``,
    ``groups`` the pool's ``Hkv``.  Returns ``(seg, lpages, count, n)``:
    per item its segment ``(row * groups + group) * tiles + tile``, its
    logical pages ``lpages[i * pages : i * pages + count[i]]`` and how
    many they are, with the first ``n [1]`` items in use; ``pages`` follows
    from the page size (``_LISTED_KEY_TILE`` key positions an item).  A
    step hands it to the layer's call (``items=``) and may count it:
    ``n`` items holding ``count.sum()`` pages."""
    B, Q, Hkv, W = query_lens.shape[0], max_q, groups, max_pages
    sel_blocks, dense_len, *sel_mask = selected
    tile = min(128, Q)
    if Q % tile:
        raise ValueError(f"chunk width {Q} is not a multiple of {tile}")
    tiles = Q // tile
    pages = _pages_per_item(page_size, W, _LISTED_KEY_TILE)
    pos, valid = _token_positions(Q, query_lens, context_lens)
    # the pages a token reads: its list, or (a dense token) all up to its own
    w = jnp.arange(W)
    reach = w[None, None, :] <= pos[:, :, None] // page_size
    if window is not None:
        # the first position the token's window holds, and its page
        reach = reach & (w[None, None, :] >= jnp.maximum(
            pos - window + 1, 0)[:, :, None] // page_size)
    reads = reach[:, None]
    if sel_blocks is not None:
        # (the caller's mask where it has one: scattering K indices a token
        # into W flags took 5.6 ms a call at 16 x 2 x 512 x 64 into 544)
        listed = sel_mask[0] if sel_mask else _selected_onehot(sel_blocks, W)
        reads = listed | (reads & (pos + 1 <= dense_len)[:, None, :, None])
    reads = jnp.broadcast_to(reads & valid[:, None, :, None],
                             (B, Hkv, Q, W))
    visit = reads.reshape(B, Hkv, tiles, tile, W).any(3)
    # pages one tile can reach: all of them, or its tokens' windows
    per_tile = W if window is None else min(
        W, (window + tile - 2) // page_size + 2)
    # tiles that hold a token: one a row and one more per `tile` tokens
    n_max = Hkv * min(B + -(-(total_q or B * Q) // tile),
                      B * tiles) * -(-per_tile // pages)
    seg, lpages, count, n = _paged_work_items(
        visit.reshape(B * Hkv * tiles, W), n_max, pages)
    return seg, lpages, count, n.reshape(1).astype(jnp.int32)


def _listed_kernel(seg_ref, lp_ref, cnt_ref, n_ref, tbl_ref, qlen_ref,
                   ctx_ref, layer_ref, q_ref, sel_ref, kp_hbm, vp_hbm, o_ref,
                   k_buf, v_buf, sem, acc_ref, m_ref, l_ref, *, scale,
                   page_size, pages, groups, tiles, tile, small, dense_len,
                   window):
    i = pl.program_id(0)
    n = n_ref[0]
    layer = layer_ref[0]
    n_max = seg_ref.shape[0]
    seg = seg_ref[i]
    first = (i == 0) | (seg_ref[jnp.maximum(i - 1, 0)] != seg)
    last = (i == n - 1) | (seg_ref[jnp.minimum(i + 1, n_max - 1)] != seg)
    b = seg // (groups * tiles)
    qt = seg % tiles
    q_len, ctx = qlen_ref[b], ctx_ref[b]
    in_tile = q_len - qt * tile       # query tokens of the row in this tile
    count = cnt_ref[i]
    slot = i % 2
    T = pages * page_size

    def fetch(item, slot, wait):
        """``item``'s listed pages of its row and group into buffer
        ``slot``."""
        s = seg_ref[item]
        r, g = s // (groups * tiles), (s // tiles) % groups
        _fetch_pages(
            (kp_hbm, vp_hbm), (k_buf, v_buf), sem, slot,
            lambda j: (layer, tbl_ref[r, lp_ref[item * pages + j]], g),
            cnt_ref[item], pages, page_size, wait)

    @pl.when(i == 0)
    def _prime():
        # a slot of an item that holds no page is masked out of the scores,
        # but 0 x (whatever the buffer held) has to be 0 in the second
        # product: after this the buffers only ever hold pool values
        v_buf[:] = jnp.zeros_like(v_buf)
        fetch(0, 0, wait=False)

    @pl.when(i + 1 < n)
    def _next():
        fetch(jnp.minimum(i + 1, n_max - 1), 1 - slot, wait=False)

    fetch(i, slot, wait=True)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def update(R):
        """The item's pages against the tile's first ``R`` query slots
        (static): a decode row pays for ``small`` slots, not for the
        tile."""
        G = q_ref.shape[2]
        hd = q_ref.shape[4]
        q = q_ref[0, 0, :, :R, :].reshape(G * R, hd)
        k = k_buf[slot]                              # [T, hd]
        v = v_buf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = s.reshape(G, R, T)
        tq = qt * tile + jax.lax.broadcasted_iota(jnp.int32, (1, R, 1), 1)
        at = ctx - q_len + tq                        # absolute position
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
        sel = sel_ref[0, 0, :R, :]
        # page by page of the item: the positions its columns hold (a
        # slot with no page lies behind no token) and whether the token
        # listed that page
        kv = jnp.full((1, 1, T), 2 ** 30, jnp.int32)
        member = jnp.zeros((1, R, T), jnp.bool_)
        for j in range(pages):
            lpage = lp_ref[i * pages + j]
            here = ((col >= j * page_size) & (col < (j + 1) * page_size)
                    & (j < count))
            kv = jnp.where(here, (lpage - j) * page_size + col, kv)
            listed = jnp.any(sel == lpage, axis=-1, keepdims=True)[None]
            member = member | (listed & here)
        ok = ((kv <= at) & (tq < q_len)
              & ((at + 1 <= dense_len) | member))
        if window is not None:
            ok = ok & (kv > at - window)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:, :R]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a slot with nothing allowed yet keeps m at -inf: exp(s - m)
        # would be 1 there, so mask the probabilities too
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :R] = l_ref[:, :R] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
        pv = jnp.dot(p.reshape(G * R, T).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        acc_ref[:, :R] = acc_ref[:, :R] * corr + pv.reshape(G, R, hd)
        m_ref[:, :R] = m_new

    if small < tile:
        pl.when(in_tile <= small)(lambda: update(small))
        pl.when(in_tile > small)(lambda: update(tile))
    else:
        update(tile)

    @pl.when(last)
    def _final():
        l = l_ref[:]
        o = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _listed_attention_kernel(q, k_pages, v_pages, page_tables, query_lens,
                             context_lens, scale, layer, sel_blocks,
                             dense_len, items, interpret, window=None):
    """``items`` is ``listed_work_items`` of the same lengths, lists and
    ``window``."""
    B, Q, H, hd = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    G, W = H // Hkv, page_tables.shape[1]
    tile = min(128, Q)
    tiles, small = Q // tile, min(16, tile)
    pages = _pages_per_item(page_size, W, _LISTED_KEY_TILE)
    seg, lpages, count, n = items
    if sel_blocks is None:
        sel_blocks = jnp.full((B, Hkv, Q, 1), -1, jnp.int32)
        dense_len = 2 ** 30
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row_of(s):
        return s // (Hkv * tiles), (s // tiles) % Hkv, s % tiles

    def q_block(i, seg, *_):
        b, g, qt = row_of(seg[i])
        return (b, g, 0, qt, 0)

    def sel_block(i, seg, *_):
        b, g, qt = row_of(seg[i])
        return (b, g, qt, 0)

    K = sel_blocks.shape[-1]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = (2, pages * page_size, hd)             # two buffers of one item
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(n[0],),
        in_specs=[
            pl.BlockSpec((1, 1, G, tile, hd), q_block),
            pl.BlockSpec((1, 1, tile, K), sel_block),
            in_hbm, in_hbm,
        ],
        out_specs=pl.BlockSpec((1, 1, G, tile, hd), q_block),
        scratch_shapes=[
            pltpu.VMEM(buf, k_pages.dtype),
            pltpu.VMEM(buf, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((G, tile, hd), jnp.float32),
            pltpu.VMEM((G, tile, 1), jnp.float32),
            pltpu.VMEM((G, tile, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _listed_kernel, scale=scale, page_size=page_size, pages=pages,
        groups=Hkv, tiles=tiles, tile=tile, small=small,
        dense_len=dense_len, window=window)
    q5 = q.reshape(B, Q, Hkv, G, hd).transpose(0, 2, 3, 1, 4)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q5.shape, q.dtype),
        interpret=interpret,
        # a window layer's calls under a name of their own: a trace then
        # tells them from the full layers' (either name holds the other's)
        name="ragged_paged_attention" + ("" if window is None
                                         else "_window"),
    )(seg, lpages, count, n, page_tables, query_lens, context_lens, layer,
      q5, sel_blocks, k_pages, v_pages)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Q, H, hd)
    # tiles that no item visited were never written
    valid = _token_positions(Q, query_lens, context_lens)[1]
    return jnp.where(valid[:, :, None, None], out, jnp.zeros_like(out))


# ---------------------------------- grouped heads, queries packed in tiles


def _tile_positions(tile, tile_rows, tile_index, query_lens, context_lens):
    """For tile-packed queries: ``(row [NT] clamped into range, pos [NT,
    tile] absolute position of each slot, valid)``; tile ``n`` holds the
    query slots ``tile_index[n] * tile ...`` of batch row ``tile_rows[n]``
    (``B``: an unused tile)."""
    B = query_lens.shape[0]
    row = jnp.minimum(tile_rows, B - 1)
    q_len, ctx = query_lens[row], context_lens[row]
    tq = tile_index[:, None] * tile + jnp.arange(tile)[None, :]
    pos = (ctx - q_len)[:, None] + tq
    valid = (tile_rows < B)[:, None] & (tq < q_len[:, None])
    return row, pos, valid


def _tiled_attention_ref(q, k_pages, v_pages, page_tables, query_lens,
                         context_lens, scale, layer, tile_rows, tile_index,
                         window):
    """Gather-then-mask oracle of the tile-packed mode: every tile against
    its row's whole table."""
    NT, tile, H, hd = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    G, W = H // Hkv, page_tables.shape[1]
    T = W * page_size
    row, pos, valid = _tile_positions(tile, tile_rows, tile_index,
                                      query_lens, context_lens)
    gather = lambda pool: pool[layer, page_tables[row]].transpose(
        0, 2, 1, 3, 4).reshape(NT, Hkv, T, hd).astype(jnp.float32)
    k, v = gather(k_pages), gather(v_pages)
    qg = q.astype(jnp.float32).reshape(NT, tile, Hkv, G, hd)
    s = jnp.einsum("nqhgd,nhtd->nhgqt", qg, k) * scale
    t = jnp.arange(T)
    ok = (t[None, None, :] <= pos[:, :, None]) & valid[:, :, None]
    if window is not None:
        ok = ok & (t[None, None, :] > pos[:, :, None] - window)
    s = jnp.where(ok[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("nhgqt,nhtd->nqhgd", p, v).reshape(NT, tile, H, hd)
    return jnp.where(valid[:, :, None, None], out, 0.0).astype(q.dtype)


def _tiled_kernel(seg_ref, lp_ref, n_ref, trow_ref, tidx_ref, tbl_ref,
                  qlen_ref, ctx_ref, layer_ref, q_ref, kp_ref, vp_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, page_size, groups, tile,
                  small, n_max, window):
    """One (query tile, key/value head, page) item: ``_listed_kernel``
    without the per-token lists, the tile's row and place in it read from
    scalar prefetch."""
    del tbl_ref, layer_ref            # only the page index_maps read them
    i = pl.program_id(0)
    seg = seg_ref[i]
    first = (i == 0) | (seg_ref[jnp.maximum(i - 1, 0)] != seg)
    last = (i == n_ref[0] - 1) | (seg_ref[jnp.minimum(i + 1, n_max - 1)]
                                  != seg)
    tid = seg // groups
    b, qt = trow_ref[tid], tidx_ref[tid]
    lpage = lp_ref[i]
    q_len, ctx = qlen_ref[b], ctx_ref[b]
    start = lpage * page_size
    in_tile = q_len - qt * tile       # query tokens of the row in this tile

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def update(R):
        """The page against the tile's first ``R`` query slots (static):
        a decode row pays for ``small`` slots, not for the tile."""
        G = q_ref.shape[2]
        hd = q_ref.shape[4]
        q = q_ref[0, 0, :, :R, :].reshape(G * R, hd)
        k = kp_ref[0, 0, 0]                          # [ps, hd]
        v = vp_ref[0, 0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = s.reshape(G, R, page_size)
        tq = qt * tile + jax.lax.broadcasted_iota(jnp.int32, (1, R, 1), 1)
        kv = start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size),
                                              2)
        at = ctx - q_len + tq                        # absolute position
        ok = (kv <= at) & (tq < q_len)
        if window is not None:
            ok = ok & (kv > at - window)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:, :R]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a slot with nothing allowed yet keeps m at -inf: exp(s - m)
        # would be 1 there, so mask the probabilities too
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :R] = l_ref[:, :R] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
        pv = jnp.dot(p.reshape(G * R, page_size).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        acc_ref[:, :R] = acc_ref[:, :R] * corr + pv.reshape(G, R, hd)
        m_ref[:, :R] = m_new

    if small < tile:
        pl.when(in_tile <= small)(lambda: update(small))
        pl.when(in_tile > small)(lambda: update(tile))
    else:
        update(tile)

    @pl.when(last)
    def _final():
        l = l_ref[:]
        o = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _tiled_attention_kernel(q, k_pages, v_pages, page_tables, query_lens,
                            context_lens, scale, layer, tile_rows,
                            tile_index, interpret, window):
    NT, tile, H, hd = q.shape
    Hkv, page_size = k_pages.shape[2], k_pages.shape[3]
    G, W = H // Hkv, page_tables.shape[1]
    small = min(16, tile)
    row, pos, valid = _tile_positions(tile, tile_rows, tile_index,
                                      query_lens, context_lens)
    w = jnp.arange(W)
    reach = w[None, None, :] <= pos[:, :, None] // page_size
    if window is not None:
        reach = reach & (w[None, None, :] >= jnp.maximum(
            pos - window + 1, 0)[:, :, None] // page_size)
    visit = (reach & valid[:, :, None]).any(1)                   # [NT, W]
    visit = jnp.broadcast_to(visit[:, None], (NT, Hkv, W))
    # pages one tile can reach: all of them, or its tokens' windows
    per_tile = W if window is None else min(
        W, (window + tile - 2) // page_size + 2)
    n_max = NT * Hkv * per_tile
    seg, lpage, n = _work_items(visit.reshape(NT * Hkv, W), n_max)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_block(i, seg, lp, n, trow, tidx, tbl, ql, cl, lyr):
        return (seg[i] // Hkv, seg[i] % Hkv, 0, 0, 0)

    def page_block(i, seg, lp, n, trow, tidx, tbl, ql, cl, lyr):
        return (lyr[0], tbl[trow[seg[i] // Hkv], lp[i]], seg[i] % Hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1, G, tile, hd), q_block),
            pl.BlockSpec((1, 1, 1, page_size, hd), page_block),
            pl.BlockSpec((1, 1, 1, page_size, hd), page_block),
        ],
        out_specs=pl.BlockSpec((1, 1, G, tile, hd), q_block),
        scratch_shapes=[
            pltpu.VMEM((G, tile, hd), jnp.float32),
            pltpu.VMEM((G, tile, 1), jnp.float32),
            pltpu.VMEM((G, tile, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _tiled_kernel, scale=scale, page_size=page_size, groups=Hkv,
        tile=tile, small=small, n_max=n_max, window=window)
    q5 = q.reshape(NT, tile, Hkv, G, hd).transpose(0, 2, 3, 1, 4)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q5.shape, q.dtype),
        interpret=interpret,
        # a window layer's calls under a name of their own: a trace then
        # tells them from the full layers' (either name holds the other's)
        name="ragged_paged_attention" + ("" if window is None
                                         else "_window"),
    )(seg, lpage, n.reshape(1).astype(jnp.int32), row.astype(jnp.int32),
      tile_index.astype(jnp.int32), page_tables, query_lens, context_lens,
      layer, q5, k_pages, v_pages)
    out = out.transpose(0, 3, 1, 2, 4).reshape(NT, tile, H, hd)
    # tiles that no item visited were never written
    return jnp.where(valid[:, :, None, None], out, jnp.zeros_like(out))


# -------------------------------------------------------------- public API


def ragged_paged_attention(q, k_pages, v_pages, page_tables, query_lens,
                           context_lens, scale=None, path=None, layer=None,
                           selected=None, total_q=None, items=None,
                           window=None, q_tiles=None):
    """Fused prefill+decode attention over a paged KV cache (see module
    docstring for layouts).  ``layer`` (a traced int32 scalar) comes with
    a stacked ``[L, P, page_size, H, hd]`` pool and names the layer whose
    pages are read.  ``path`` is one of ``dispatch.MOSAIC`` /
    ``INTERPRET`` / ``REFERENCE``; ``None`` takes the Mosaic kernel on a
    TPU and the jnp gather reference elsewhere (identical contract, fp32
    softmax in both).  ``items`` is ``ragged_work_items`` of the same
    lengths, for a caller that makes several calls on them (a step's
    layers), or in the grouped-heads mode ``listed_work_items`` of the
    same lengths and lists, for a caller that counts it; left out, the
    kernel builds it.  ``selected=(sel_blocks,
    dense_len)`` takes the grouped-heads / selected-pages mode over a
    head-major stacked pool ``[L, P, Hkv, page_size, hd]``; ``total_q``
    (static) then bounds the query tokens of all rows together, which
    bounds its work list; ``window`` (static, with ``selected``) is a
    sliding-window layer's lower edge; ``q_tiles = (tile_rows,
    tile_index)`` (with ``selected=(None, ...)``) takes ``q`` packed in
    tiles, ``[NT, tile, H, hd]``, and returns that layout."""
    if (k_pages.ndim == 5) != (layer is not None):
        raise ValueError("a stacked [L, P, page_size, H, hd] pool comes "
                         "with its `layer`, a one-layer pool without")
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    page_tables = page_tables.astype(jnp.int32)
    query_lens = query_lens.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    if window is not None and selected is None:
        raise ValueError("`window` comes with the grouped-heads mode "
                         "(`selected=`)")
    if q_tiles is not None:
        if selected is None or selected[0] is not None:
            raise ValueError("`q_tiles` comes with the grouped-heads mode "
                             "and no lists (`selected=(None, ...)`)")
        if layer is None:
            k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        tile_rows, tile_index = q_tiles
        call = _tiled_attention_ref if path == dispatch.REFERENCE \
            else functools.partial(_tiled_attention_kernel,
                                   interpret=(path == dispatch.INTERPRET))
        return call(q, k_pages, v_pages, page_tables, query_lens,
                    context_lens, scale, layer, tile_rows, tile_index,
                    window=window)
    if selected is not None:
        sel_blocks, dense_len = selected[:2]
        if layer is None:
            k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        if path == dispatch.REFERENCE:
            return _listed_attention_ref(
                q, k_pages, v_pages, page_tables, query_lens, context_lens,
                scale, layer, sel_blocks, dense_len, window)
        if items is None:
            items = listed_work_items(
                query_lens, context_lens, k_pages.shape[3],
                page_tables.shape[1], q.shape[1], k_pages.shape[2],
                selected, total_q, window)
        return _listed_attention_kernel(
            q, k_pages, v_pages, page_tables, query_lens, context_lens,
            scale, layer, sel_blocks, dense_len, items,
            interpret=(path == dispatch.INTERPRET), window=window)
    if path == dispatch.REFERENCE:
        return _ragged_attention_ref(q, k_pages, v_pages, page_tables,
                                     query_lens, context_lens, scale, layer)
    return _ragged_attention_kernel(q, k_pages, v_pages, page_tables,
                                    query_lens, context_lens, scale,
                                    interpret=(path == dispatch.INTERPRET),
                                    layer=layer, items=items)

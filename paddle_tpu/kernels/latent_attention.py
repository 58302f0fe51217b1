"""Absorbed latent attention over rows fetched one by one, by position.

Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434) caches
one row a position for all heads: the compressed latent ``c_kv [Dc]`` and
one rotary key ``k_rope [Dr]`` shared by every head.  A head's key is
``[c_kv W_UK,h | k_rope]`` and its value ``c_kv W_UV,h``; with the two
matrices absorbed into the query and the output (``q W_UK,h^T`` beside
the rotary query, and ``W_UV,h`` after the sum), every head scores the
same rows and sums the same rows, so one query is one product
``[heads, Dc + Dr] x [Dc + Dr, rows]`` and one ``[heads, rows] x [rows,
Dc]``: the heads share the keys, the rows are read once a query.

Which rows a query reads is the caller's:

- **listed** (``listed=[N, R]``): the positions of each query's rows in
  its row's context, the first ``counts[n]`` of them real, in any order —
  a learned indexer's choice (``models/mla_sparse.py``);
- **window** (``listed=None``): the ``counts[n]`` positions that end at
  ``first[n] + counts[n] - 1``, consecutive — a sliding window.

A work item is a query.  Its rows come through its batch row's page
table into one of two VMEM buffers of ``_ROWS`` rows, the next block's
copies in flight while this one is multiplied (the pattern of
``expert_matmul._fetch_rows``, which fetches tokens by id): a listed row a
copy, a window's rows a whole page a copy (the window's positions are
consecutive; the rows of its first and last page outside it are masked).
A block's copies are waited for at once where the block is full, a copy
at a time where it is not; a block past the query's rows is neither
fetched nor computed.  Scores are kept as a running maximum and sum (online softmax,
float32).  A copy addresses whole ``(8, 128)`` tiles of an array in HBM,
not one row of a 2-D one, so the pool keeps a position's row as float32
slabs ``[S, 128]`` (``latent_slab``): ``S = 8`` sublanes hold the full
layers' ``512 + 64`` values, 16 the window layers' ``1024 + 64``; the
products run over the slab's used sublanes, a ``[rows, 128]`` strided
read each.

The grid is ``(N / _QUERIES,)``: a grid step holds ``_QUERIES`` queries,
so that a step's block of listed positions is an aligned ``[8, R]`` piece
of the int32 list (scalar memory, fetched by the pipeline).

**Staged** (listed mode with ``tiles=``): a copy a row is some 57 ns of
issuing, not of bytes (a ``[8, 128]`` float32 row moves in 5 ns), so where a
tile of one batch row's queries holds two or more live ones their rows
come another way, a second ``pallas_call`` (``latent_attention_staged``)
that shares no body with the first.  Its grid step is a tile: the row's
context is copied into VMEM a whole page a copy, in blocks of ``_STAGE``
positions (the next block's pages in flight while this one is used), and
each query picks the rows it chose out of the staged block, one vector
load and store a row, into a buffer of its own that blocks fill in turn,
``_ROWS`` rows a time for the same two products and online softmax.  Its
list comes sorted by position, with the bounds of each block's run counted
beside it in XLA, so a block's rows of a query are one run of its list;
the sum runs in position order, not the list's.
The tile's own queries come in and leave by copies of their own, and its
output is the per-row call's, aliased: the rows of the queries the per-row
call left at zero are overwritten, the others stay.  The split
(:func:`staged_queries`) follows from the two costs: staging a tile's
context is ``C x 4 KiB / 819 GB/s``, at most some 175 us at ``C`` =
34,816, where copying rows one by one is ``n x min(C, 2,048) x 57 ns``,
some 117 us a query: one query alone is cheaper by its rows, two or more
by the pages.

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

__all__ = ["latent_attention", "latent_attention_ref", "latent_slab",
           "pack_latent_rows", "staged_queries", "LANES", "STAGED_TILE"]

LANES = 128
_QUERIES = 8            # queries a grid step: an aligned block of the list
_ROWS = 128             # rows of a query's product: fetched, or picked
_VMEM_LIMIT = 64 << 20
_NEG_INF = -1e30
STAGED_TILE = 16        # query slots of a staged tile, one batch row's
_STAGE = 2048           # positions of a staged block, whole pages
_PICKS = 8              # rows picked a loop iteration, written out
_PAST = 1 << 30         # a sorted list's entries past its count


def latent_slab(width):
    """Sublanes of the float32 slab ``[S, 128]`` that holds a cached row of
    ``width`` values: whole ``(8, 128)`` tiles."""
    return 8 * -(-width // (8 * LANES))


def pack_latent_rows(x):
    """``x [T, width]`` as the pool's rows, ``[T, S, 128]`` float32, the
    values in order and zeros after them."""
    T, width = x.shape
    S = latent_slab(width)
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, S * LANES - width)))
    return x.reshape(T, S, LANES)


def _positions(n, counts, first, listed):
    """``[N, R]`` positions of each query's rows and the mask of the real
    ones."""
    if listed is not None:
        r = jnp.arange(listed.shape[1], dtype=jnp.int32)[None, :]
        return listed, r < counts[:, None]
    r = jnp.arange(n, dtype=jnp.int32)[None, :]
    return first[:, None] + r, r < counts[:, None]


def latent_attention_ref(q, pool, page_tables, rows, counts, *, layer,
                         scale, latent, first=None, listed=None, window=None):
    """The ``jnp`` oracle of :func:`latent_attention`: each query's rows
    gathered from the pool, masked to its count, softmax in float32."""
    N, H, Wq = q.shape
    page_size = pool.shape[2]
    n = listed.shape[1] if listed is not None else window
    pos, ok = _positions(n, counts, first, listed)
    pos = jnp.where(ok, pos, 0)
    row = jnp.clip(rows, 0, page_tables.shape[0] - 1)
    page = jnp.take_along_axis(page_tables[row], pos // page_size, axis=1)
    kv = pool[layer, page, pos % page_size]          # [N, R, S, 128]
    kv = kv.reshape(N, n, -1)[..., :Wq]
    cdt = q.dtype
    s = jnp.einsum("nhw,nrw->nhr", q, kv.astype(cdt),
                   precision=mxu_precision(q),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("nhr,nrc->nhc", p.astype(cdt),
                     kv[..., :latent].astype(cdt),
                     precision=mxu_precision(q),
                     preferred_element_type=jnp.float32)
    return jnp.where((counts > 0)[:, None, None], out, 0.0)


def _attend(q, lanes, mask, m_ref, l_ref, acc_ref, *, latent, dtype):
    """A block of rows into one query's online softmax: ``q [H, Wq]``,
    ``lanes(j)`` the rows' ``j``-th 128 lanes ``[R, 128]`` float32,
    ``mask(s)`` the scaled scores ``[H, R]`` with the rows not read at
    ``_NEG_INF``; ``m``, ``l`` and ``acc`` the query's running maximum, sum
    and weighted latent."""
    s = None
    for j in range(q.shape[1] // LANES):
        kj = lanes(j).astype(dtype)                           # [R, 128]
        qj = q[:, j * LANES:(j + 1) * LANES]
        part = jax.lax.dot_general(
            qj, kj, (((1,), (1,)), ((), ())),
            precision=mxu_precision(qj, kj),
            preferred_element_type=jnp.float32)               # [H, R]
        s = part if s is None else s + part
    s = mask(s)
    m_prev = m_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    p = p.astype(dtype)
    for j in range(latent // LANES):
        vj = lanes(j).astype(dtype)
        at = pl.ds(j * LANES, LANES)
        acc_ref[:, at] = alpha * acc_ref[:, at] + jnp.dot(
            p, vj, precision=mxu_precision(p, vj),
            preferred_element_type=jnp.float32)


def _kernel(rows_ref, counts_ref, first_ref, layer_ref, tables_ref, q_ref,
            pool_hbm, *rest, listed, latent, scale, page_size, dtype):
    if listed:
        listed_ref, o_ref, buf, sem, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, buf, sem, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(0)
    G = o_ref.shape[0]
    R = buf.shape[1]
    per_block = R // page_size              # a window's pages in a block
    base = i * G
    layer = layer_ref[0]

    @pl.when(i == 0)
    def _clear():
        # rows past a block's count keep what an earlier block left;
        # zeroed once, they hold finite numbers that a zero weight cancels
        buf[...] = jnp.zeros_like(buf)

    o_ref[...] = jnp.zeros_like(o_ref)

    def start(g):
        """The position of the query's first buffer row: a listed query's
        rows are its list's order (0), a window's start at the first page
        it reaches."""
        if listed:
            return jnp.int32(0)
        return first_ref[base + g] // page_size * page_size

    def span(g):
        """Buffer rows the query's blocks cover."""
        count = counts_ref[base + g]
        if listed:
            return count
        return jnp.where(count > 0, first_ref[base + g] + count - start(g),
                         0)

    # the blocks of the step's queries, one after another: query g owns
    # blocks ends[g - 1] .. ends[g] - 1
    ends, total = [], jnp.int32(0)
    for g in range(G):
        total = total + (span(g) + R - 1) // R
        ends.append(total)

    def locate(k):
        g, begin = jnp.int32(0), jnp.int32(0)
        for e in ends[:-1]:
            past = k >= e
            g = g + past.astype(jnp.int32)
            begin = jnp.where(past, e, begin)
        return g, k - begin

    def fetch(k, slot, wait):
        g, b = locate(k)
        live = jnp.minimum(R, span(g) - b * R)
        table_row = rows_ref[base + g]
        if listed:
            piece, n = 1, live

            def src(r):
                p = listed_ref[g, b * R + r]
                return pool_hbm.at[layer, tables_ref[table_row,
                                                     p // page_size],
                                   p % page_size]
        else:
            piece, n = page_size, (live + page_size - 1) // page_size
            first_page = start(g) // page_size + b * per_block

            def src(j):
                return pool_hbm.at[layer, tables_ref[table_row,
                                                     first_page + j]]

        def one(j, carry):
            dst = buf.at[slot, pl.ds(j * piece, piece)] if piece > 1 \
                else buf.at[slot, j]
            # a wait needs the copy's shape and semaphore, not its source
            at = (pool_hbm.at[0, 0] if piece > 1 else pool_hbm.at[0, 0, 0]) \
                if wait else src(j)
            copy = pltpu.make_async_copy(at, dst, sem.at[slot])
            copy.wait() if wait else copy.start()
            return carry

        if not wait:
            jax.lax.fori_loop(0, n, one, 0)
            return
        # a full block: one wait for all of its copies (a semaphore counts
        # bytes); a partial one copy by copy

        @pl.when(live == R)
        def _all():
            pltpu.make_async_copy(buf.at[1 - slot], buf.at[slot],
                                  sem.at[slot]).wait()

        @pl.when(live < R)
        def _each():
            jax.lax.fori_loop(0, n, one, 0)

    @pl.when(total > 0)
    def _prime():
        fetch(0, 0, wait=False)

    def block(k, carry):
        slot = k % 2

        @pl.when(k + 1 < total)
        def _next():
            fetch(k + 1, 1 - slot, wait=False)

        fetch(k, slot, wait=True)
        g, b = locate(k)
        count = counts_ref[base + g]

        @pl.when(b == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def mask(s):
            r = b * R + jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
            if listed:
                ok = r < count
            else:
                pos = start(g) + r
                lo = first_ref[base + g]
                ok = (pos >= lo) & (pos < lo + count)
            return jnp.where(ok, s * scale, _NEG_INF)

        _attend(q_ref[g], lambda j: buf[slot, :, j, :], mask, m_ref, l_ref,
                acc_ref, latent=latent, dtype=dtype)

        @pl.when(b * R + R >= span(g))
        def _finish():
            o_ref[g] = acc_ref[...] / l_ref[...][:, :1]

        return carry

    jax.lax.fori_loop(0, total, block, 0)


def _staged_kernel(layer_ref, tables_ref, tile_rows_ref, tokens_ref,
                   pages_ref, listed_ref, bounds_ref, q_hbm, pool_hbm,
                   base_hbm, o_hbm, blk, pick, q_buf, m_ref, l_ref, acc_ref,
                   fill, sem, *, latent, scale, page_size, dtype):
    del base_hbm                    # o_hbm itself: the other rows stay
    t = pl.program_id(0)
    G, K, R = q_buf.shape[0], blk.shape[1], _ROWS
    last = listed_ref.shape[1] - 1
    per_block = K // page_size
    pages, layer, row = pages_ref[t], layer_ref[0], tile_rows_ref[t]

    @pl.when(t == 0)
    def _clear():
        # a picked row past a run's end comes from anywhere in the block,
        # some of it never staged: zeroed once, every row is finite and a
        # zero weight cancels it
        blk[...] = jnp.zeros_like(blk)
        pick[...] = jnp.zeros_like(pick)

    def token(g):
        return tokens_ref[t * G + g]

    def each_query(body):
        def one(g, carry):
            @pl.when(token(g) >= 0)
            def _live():
                body(g)
            return carry
        jax.lax.fori_loop(0, G, one, 0)

    def query_in(g, wait):
        src, dst = (q_hbm.at[0], q_buf.at[0]) if wait \
            else (q_hbm.at[token(g)], q_buf.at[g])
        copy = pltpu.make_async_copy(src, dst, sem.at[2])
        copy.wait() if wait else copy.start()

    def result_out(g, wait):
        src, dst = (acc_ref.at[0], o_hbm.at[0]) if wait \
            else (acc_ref.at[g], o_hbm.at[token(g)])
        copy = pltpu.make_async_copy(src, dst, sem.at[2])
        copy.wait() if wait else copy.start()

    def stage(b, slot, wait):
        """Block ``b``'s pages of the row, a copy a page."""
        first = b * per_block

        def one(j, carry):
            # a wait needs the copy's shape and semaphore, not its source
            src = pool_hbm.at[0, 0] if wait else pool_hbm.at[
                layer, tables_ref[row, first + j]]
            copy = pltpu.make_async_copy(
                src, blk.at[slot, pl.ds(j * page_size, page_size)],
                sem.at[slot])
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(per_block, pages - first), one, 0)

    def attend(g, n):
        """The first ``n`` rows of query ``g``'s picks into its softmax."""
        ok = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) < n
        _attend(q_buf[g], lambda j: pick[g, pl.ds(0, R), j, :],
                lambda s: jnp.where(ok, s * scale, _NEG_INF), m_ref.at[g],
                l_ref.at[g], acc_ref.at[g], latent=latent, dtype=dtype)

    def block(b, carry):
        slot = b % 2

        @pl.when((b + 1) * per_block < pages)
        def _next():
            stage(b + 1, 1 - slot, wait=False)

        stage(b, slot, wait=True)

        def segment(g, carry):
            # the run of query g's sorted list that lies in block b, picked
            # behind the rows earlier blocks left, a product each time
            # ``R`` are ready: a query's products are its rows / R, not
            # one more for each block its list ends a run in
            lo, hi = bounds_ref[g, b], bounds_ref[g, b + 1]

            def more(first):
                f = fill[g]
                n = jnp.minimum(R - f, hi - first)

                def picks(k, carry):
                    # a row past the run is some row of the block (its
                    # position wrapped into it), overwritten or masked
                    for u in range(_PICKS):
                        r = k * _PICKS + u
                        p = listed_ref[g, jnp.minimum(first + r, last)]
                        pick[g, f + r] = blk[slot, p & (K - 1)]
                    return carry

                jax.lax.fori_loop(0, (n + _PICKS - 1) // _PICKS, picks, 0)
                full = f + n == R

                @pl.when(full)
                def _product():
                    attend(g, R)

                fill[g] = jnp.where(full, 0, f + n)
                return first + n

            jax.lax.while_loop(lambda first: first < hi, more, lo)
            return carry

        jax.lax.fori_loop(0, G, segment, 0)
        return carry

    @pl.when(pages > 0)
    def _tile():
        each_query(lambda g: query_in(g, wait=False))
        stage(0, 0, wait=False)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for g in range(G):
            fill[g] = 0
        each_query(lambda g: query_in(g, wait=True))
        jax.lax.fori_loop(0, (pages + per_block - 1) // per_block, block, 0)

        def finish(g):
            @pl.when(fill[g] > 0)
            def _rest():
                attend(g, fill[g])

            acc_ref[g] = acc_ref[g] / l_ref[g][:, :1]
            result_out(g, wait=False)

        each_query(finish)
        each_query(lambda g: result_out(g, wait=True))


def staged_queries(counts, tiles):
    """``[N]`` bool: the queries of a tile with two or more live queries
    (``counts > 0``), which the staged call reads; ``tiles = (tile_rows
    [NT], tokens [NT, n])``, each tile's batch row and the query of each of
    its slots (``-1``: none)."""
    tokens = tiles[1]
    at = jnp.maximum(tokens, 0)
    live = (tokens >= 0) & (counts[at] > 0)
    many = jnp.sum(live.astype(jnp.int32), axis=1, keepdims=True) >= 2
    drop = jnp.where(live, tokens, counts.shape[0])
    return jnp.zeros(counts.shape, bool).at[drop].set(
        jnp.broadcast_to(many, drop.shape), mode="drop")


def _staged(q, pool, page_tables, counts, listed, tiles, layer, out, *,
            scale, latent, interpret):
    """The staged call over the queries with ``counts > 0``, the rows of
    ``out [n, H, latent]`` of the others left as they are."""
    tile_rows, tokens = tiles
    NT, G = tokens.shape
    N, R = listed.shape
    H, Wq = q.shape[1:]
    page_size, S = pool.shape[2], pool.shape[3]
    K = _STAGE
    if K % page_size or K & (K - 1):
        raise ValueError(f"a staged block of {K} positions: a power of two "
                         f"of whole pages of {page_size}")
    blocks = -(-page_tables.shape[1] * page_size // K)
    # each list by position, the entries past its count last; the run of
    # it in block b is bounds[b] .. bounds[b + 1], counted here and not by
    # a cursor in the kernel: a cursor would walk every list a second time
    # on the scalar unit, which the picks keep busy.  Not stable: the keys
    # are distinct but for the equal padding, and a stable sort of [527,
    # 2048] took the v5e compiler 7 s where this one takes 1
    real = jnp.arange(R, dtype=jnp.int32)[None, :] < counts[:, None]
    listed = jax.lax.sort(jnp.where(real, listed, _PAST), dimension=1,
                          is_stable=False)
    edges = jnp.arange(blocks + 1, dtype=jnp.int32) * K
    bounds = jnp.sum((listed[:, :, None] < edges).astype(jnp.int32), axis=1)
    top = jnp.take_along_axis(listed, jnp.maximum(counts - 1, 0)[:, None],
                              axis=1)[:, 0]
    reach = jnp.where(counts > 0, top // page_size + 1, 0)   # pages
    at = jnp.maximum(tokens, 0)
    live = (tokens >= 0) & (counts[at] > 0)
    tokens = jnp.where(live, tokens, -1)
    pages = jnp.max(jnp.where(live, reach[at], 0), axis=1)
    listed = listed[at].reshape(NT * G, R)
    bounds = jnp.where(live[..., None], bounds[at], 0).reshape(
        NT * G, blocks + 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(NT,),
        in_specs=[pl.BlockSpec((G, R), lambda t, *_: (t, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((G, blocks + 1), lambda t, *_: (t, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((2, K, S, LANES), jnp.float32),
                        pltpu.VMEM((G, _ROWS + _PICKS, S, LANES),
                                   jnp.float32),
                        pltpu.VMEM((G, H, Wq), q.dtype),
                        pltpu.VMEM((G, H, LANES), jnp.float32),
                        pltpu.VMEM((G, H, LANES), jnp.float32),
                        pltpu.VMEM((G, H, latent), jnp.float32),
                        pltpu.SMEM((G,), jnp.int32),
                        pltpu.SemaphoreType.DMA((3,))])
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)}
    call = pl.pallas_call(
        functools.partial(_staged_kernel, latent=latent, scale=scale,
                          page_size=page_size, dtype=q.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out.shape, jnp.float32),
        input_output_aliases={9: 0}, interpret=interpret,
        name="latent_attention_staged", **kwargs)
    return call(layer, page_tables.astype(jnp.int32),
                tile_rows.astype(jnp.int32),
                tokens.reshape(-1).astype(jnp.int32), pages.astype(jnp.int32),
                listed, bounds, q, pool, out)


def latent_attention(q, pool, page_tables, rows, counts, *, layer, scale,
                     latent, first=None, listed=None, window=None,
                     tiles=None, path=None):
    """Each query's attention over its rows of a latent page pool, with the
    key and value matrices absorbed: ``[N, H, latent]`` float32.

    ``q [N, H, Wq]``: per query and head the latent part (``q_nope
    W_UK,h^T``, ``latent`` wide) and the rotary part, zero-padded to whole
    lanes (``Wq`` a multiple of 128, at most the pool's slab).  ``pool [L,
    P, page, S, 128]`` float32, a position's row as :func:`pack_latent_rows`
    lays it, layer ``layer`` read.  ``page_tables [B, max_pages]`` maps a
    batch row's logical page to its physical one; ``rows [N]`` is each
    query's batch row and ``counts [N]`` the rows it reads (0: none, output
    zeros).  Listed mode: ``listed [N, R]`` the positions, the first
    ``counts[n]`` real.  Window mode: ``first [N]`` the first position and
    ``window`` (static) the most rows any query reads.  ``scale`` multiplies
    the scores.  ``tiles = (tile_rows [NT], tokens [NT, n])`` (listed mode):
    the queries in tiles of one batch row each (``tokens`` the query of a
    slot, ``-1`` none); the queries of a tile with two or more live ones
    are read by the staged call (:func:`staged_queries`), the rest one row
    a copy."""
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    if (listed is None) == (first is None):
        raise ValueError("name the rows by listed= or by first= (a window)")
    if listed is None and window is None:
        raise ValueError("a window's rows need window=")
    if tiles is not None and listed is None:
        raise ValueError("tiles= stages a listed query's rows")
    N, H, Wq = q.shape
    S = pool.shape[3]
    if Wq % LANES or Wq > S * LANES or latent % LANES or latent > Wq:
        raise ValueError(f"query width {Wq}, latent {latent}, slab {S}")
    if _ROWS % pool.shape[2]:
        raise ValueError(f"a block of {_ROWS} rows holds whole pages of "
                         f"{pool.shape[2]}")
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if path == dispatch.REFERENCE:
        return latent_attention_ref(q, pool, page_tables, rows, counts,
                                    layer=layer[0], scale=scale,
                                    latent=latent, first=first,
                                    listed=listed, window=window)
    if tiles is not None:
        staged = staged_queries(counts, tiles)
        staged_args = (q, pool, page_tables, jnp.where(staged, counts, 0),
                       listed.astype(jnp.int32), tiles, layer)
        counts = jnp.where(staged, 0, counts)
    G = _QUERIES
    pad = -N % G
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        rows = jnp.pad(rows, (0, pad))
        counts = jnp.pad(counts, (0, pad))
        first = None if first is None else jnp.pad(first, (0, pad))
        listed = None if listed is None else jnp.pad(listed,
                                                     ((0, pad), (0, 0)))
    if first is None:
        first = jnp.zeros_like(counts)
    n = N + pad
    in_specs = [pl.BlockSpec((G, H, Wq), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [q, pool]
    if listed is not None:
        in_specs.append(pl.BlockSpec((G, listed.shape[1]),
                                     lambda i, *_: (i, 0),
                                     memory_space=pltpu.SMEM))
        args.append(listed.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(n // G,), in_specs=in_specs,
        out_specs=pl.BlockSpec((G, H, latent), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, _ROWS, S, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((H, LANES), jnp.float32),
                        pltpu.VMEM((H, LANES), jnp.float32),
                        pltpu.VMEM((H, latent), jnp.float32)])
    interpret = path == dispatch.INTERPRET
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT)}
    call = pl.pallas_call(
        functools.partial(_kernel, listed=listed is not None, latent=latent,
                          scale=scale, page_size=pool.shape[2],
                          dtype=q.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, H, latent), jnp.float32),
        interpret=interpret, name="latent_attention", **kwargs)
    out = call(rows.astype(jnp.int32), counts.astype(jnp.int32),
               first.astype(jnp.int32), layer,
               page_tables.astype(jnp.int32), *args)
    if tiles is not None:
        out = _staged(*staged_args, out, scale=scale, latent=latent,
                      interpret=interpret)
    return out[:N]

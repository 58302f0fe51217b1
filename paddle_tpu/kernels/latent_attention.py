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
           "pack_latent_rows", "LANES"]

LANES = 128
_QUERIES = 8            # queries a grid step: an aligned block of the list
_ROWS = 128             # rows a fetched block of one query
_VMEM_LIMIT = 64 << 20
_NEG_INF = -1e30


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


def _kernel(rows_ref, counts_ref, first_ref, layer_ref, tables_ref, q_ref,
            pool_hbm, *rest, listed, latent, scale, page_size, dtype):
    if listed:
        listed_ref, o_ref, buf, sem, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, buf, sem, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(0)
    G = o_ref.shape[0]
    R, used = buf.shape[1], q_ref.shape[2] // LANES
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

        q = q_ref[g]                                          # [H, Wq]
        s = None
        for j in range(used):
            kj = buf[slot, :, j, :].astype(dtype)             # [R, 128]
            qj = q[:, j * LANES:(j + 1) * LANES]
            part = jax.lax.dot_general(
                qj, kj, (((1,), (1,)), ((), ())),
                precision=mxu_precision(qj, kj),
                preferred_element_type=jnp.float32)           # [H, R]
            s = part if s is None else s + part
        r = b * R + jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
        if listed:
            ok = r < count
        else:
            pos = start(g) + r
            lo = first_ref[base + g]
            ok = (pos >= lo) & (pos < lo + count)
        s = jnp.where(ok, s * scale, _NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        p = p.astype(dtype)
        for j in range(latent // LANES):
            vj = buf[slot, :, j, :].astype(dtype)
            at = pl.ds(j * LANES, LANES)
            acc_ref[:, at] = alpha * acc_ref[:, at] + jnp.dot(
                p, vj, precision=mxu_precision(p, vj),
                preferred_element_type=jnp.float32)

        @pl.when(b * R + R >= span(g))
        def _finish():
            o_ref[g] = acc_ref[...] / l_ref[...][:, :1]

        return carry

    jax.lax.fori_loop(0, total, block, 0)


def latent_attention(q, pool, page_tables, rows, counts, *, layer, scale,
                     latent, first=None, listed=None, window=None,
                     path=None):
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
    the scores."""
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    if (listed is None) == (first is None):
        raise ValueError("name the rows by listed= or by first= (a window)")
    if listed is None and window is None:
        raise ValueError("a window's rows need window=")
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
    return out[:N]

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
- the Pallas kernel — grid (batch, pages_per_seq); the page table, the
  two length vectors and the layer ride in scalar-prefetch
  (PrefetchScalarGridSpec) so the BlockSpec index_map DMAs exactly the
  pages each row owns.  Page steps are the innermost (sequential) grid
  axis; VMEM scratch carries the online-softmax state (per query token ×
  head) across them, flash-attention style, with the causal mask applied
  relative to each row's context offset.

Layouts:
  q            [B, Q, H, hd]        Q = max query tokens per row, padded
  k/v_pages    [L, P, page_size, H, hd] every layer's page pool, stacked,
               with ``layer`` (a traced int32 scalar) naming the one to
               read: the serving step hands the kernel its whole donated
               pool and the index_map picks ``[layer, page]`` blocks out
               of HBM, so no layer's pages are ever sliced out or copied.
               [P, page_size, H, hd] (one layer's pool, ``layer`` left
               out) is the same kernel under a free ``[None]``.
  page_tables  [B, max_pages] int32  physical page id per logical page
  query_lens   [B] int32             valid query tokens (0 = idle row)
  context_lens [B] int32             kv tokens incl. this chunk
Returns [B, Q, H, hd] in q.dtype; padded query slots and idle rows
return zeros.

``paged_attention`` (the original decode-only entry: one query token per
row, ``seq_lens`` masking) is the Q == 1 degenerate case of the same
entry.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

__all__ = ["paged_attention", "ragged_paged_attention"]

_NEG_INF = -1e30


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


def _ragged_kernel(tbl_ref, qlen_ref, ctx_ref, layer_ref, q_ref, kp_ref,
                   vp_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, page_size,
                   num_pages):
    del layer_ref                     # only the page index_maps read it
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_len = qlen_ref[b]
    ctx = ctx_ref[b]
    start = j * page_size

    @pl.when((start < ctx) & (q_len > 0))
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [Q, H, hd]
        k = kp_ref[0, 0].astype(jnp.float32)        # [ps, H, hd]
        v = vp_ref[0, 0].astype(jnp.float32)
        Q = q.shape[0]
        # s[h, tq, t] = q[tq, h, :] . k[t, h, :]  (batch over heads)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * scale  # [H, Q, ps]
        tq = jax.lax.broadcasted_iota(jnp.int32, (1, Q, page_size), 1)
        kv = start + jax.lax.broadcasted_iota(jnp.int32, (1, Q, page_size),
                                              2)
        # causal relative to the row's context offset: query tq sits at
        # absolute position ctx - q_len + tq
        ok = (kv <= ctx - q_len + tq) & (tq < q_len)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:]                            # [H, Q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [H, Q, ps]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # acc[h, tq, d] += p[h, tq, :] . v[:, h, d]
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == num_pages - 1)
    def _final():
        Q = acc_ref.shape[1]
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[:] / l_safe                      # [H, Q, hd]
        # padded query slots accumulated garbage behind the mask with
        # m == -inf; zero them so the kernel matches the ref everywhere
        tq = jax.lax.broadcasted_iota(jnp.int32, (1, Q, 1), 1)
        o = jnp.where(tq < q_len, o, 0.0)
        o_ref[0] = o.transpose(1, 0, 2).astype(o_ref.dtype)


def _ragged_attention_kernel(q, k_pages, v_pages, page_tables, query_lens,
                             context_lens, scale, interpret, layer=None):
    B, Q, H, hd = q.shape
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    page_size = k_pages.shape[2]
    max_pages = page_tables.shape[1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def q_block(b, j, tbl, ql, cl, lyr):
        return (b, 0, 0, 0)

    def page_block(b, j, tbl, ql, cl, lyr):
        return (lyr[0], tbl[b, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Q, H, hd), q_block),
            pl.BlockSpec((1, 1, page_size, H, hd), page_block),
            pl.BlockSpec((1, 1, page_size, H, hd), page_block),
        ],
        out_specs=pl.BlockSpec((1, Q, H, hd), q_block),
        scratch_shapes=[
            pltpu.VMEM((H, Q, hd), jnp.float32),
            pltpu.VMEM((H, Q, 1), jnp.float32),
            pltpu.VMEM((H, Q, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page_size, num_pages=max_pages)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q, H, hd), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(page_tables, query_lens, context_lens, layer, q, k_pages, v_pages)


# -------------------------------------------------------------- public API


def ragged_paged_attention(q, k_pages, v_pages, page_tables, query_lens,
                           context_lens, scale=None, path=None, layer=None):
    """Fused prefill+decode attention over a paged KV cache (see module
    docstring for layouts).  ``layer`` (a traced int32 scalar) comes with
    a stacked ``[L, P, page_size, H, hd]`` pool and names the layer whose
    pages are read.  ``path`` is one of ``dispatch.MOSAIC`` /
    ``INTERPRET`` / ``REFERENCE``; ``None`` takes the Mosaic kernel on a
    TPU and the jnp gather reference elsewhere (identical contract, fp32
    softmax in both)."""
    if (k_pages.ndim == 5) != (layer is not None):
        raise ValueError("a stacked [L, P, page_size, H, hd] pool comes "
                         "with its `layer`, a one-layer pool without")
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    page_tables = page_tables.astype(jnp.int32)
    query_lens = query_lens.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    if path == dispatch.REFERENCE:
        return _ragged_attention_ref(q, k_pages, v_pages, page_tables,
                                     query_lens, context_lens, scale, layer)
    return _ragged_attention_kernel(q, k_pages, v_pages, page_tables,
                                    query_lens, context_lens, scale,
                                    interpret=(path == dispatch.INTERPRET),
                                    layer=layer)


def paged_attention(q, k_pages, v_pages, page_tables, seq_lens, scale=None,
                    path=None):
    """Single-token decode attention over a paged KV cache: q [B, H, hd],
    one query token per sequence attending over its first ``seq_lens``
    kv tokens (0 marks an inactive slot) — the query_len == 1 degenerate
    row of ``ragged_paged_attention``, kept as a stable API for
    decode-only callers and tests."""
    seq_lens = seq_lens.astype(jnp.int32)
    return ragged_paged_attention(
        q[:, None], k_pages, v_pages, page_tables,
        (seq_lens > 0).astype(jnp.int32), seq_lens, scale, path)[:, 0]

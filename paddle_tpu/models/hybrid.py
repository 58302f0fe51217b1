"""Hybrid decoder: block-sparse grouped-query attention layers beside
lightning (decayed linear) attention layers, in one fixed pattern.

The second decoder family of the repo, written for serving.  What it has
that ``models/gpt.py`` has not: RMSNorm, a gated SwiGLU MLP, grouped-query
attention with per-head RMSNorm on queries and keys and no positions in the
sparse layers, rotary positions in the lightning layers, a sigmoid output
gate on every mixer, an untied head, and the three muP scalars (embedding
scale, depth-scaled residual branches, logit divisor).  Its layers are of
two kinds in a static order (``mixer_types``), so its parameters are two
stacks, one per kind, and the step walks the order with static indices
into them: one ``lax.scan`` over one block does not describe it.

Sparse layers (InfLLM-v2 style block selection, arXiv:2506.07900).  A query
whose context (position + 1) is at most ``dense_len`` attends causally over
everything.  Past that it attends over ``topk`` blocks of ``block_size``
positions, chosen per query token and per key/value group: keys are
compressed to the mean of every ``kernel_size`` consecutive keys at stride
``kernel_stride``; each head softmaxes its scores against the compressed
keys whose span lies wholly at or before the token; the group's heads are
summed; a block scores the maximum over the spans that overlap it; block
``< init_blocks`` and the blocks that cover the last ``window_size``
positions are always taken, the best-scoring others fill up to ``topk``.
The cache therefore holds pages of keys and values for the sparse layers
(``block_size`` is the page size), pages of compressed keys beside them
(without which every step would re-read every key), and for each
lightning layer one ``[heads, hd, hd]`` float32 state per batch row.

``hybrid_ragged_step`` takes the scheduler's ``RaggedBatch``
(``models/ragged.py``), as ``gpt_ragged_step`` does; all four state pools
are carried in place.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .ragged import RaggedBatch, RaggedView

__all__ = ["HybridConfig", "hybrid_init", "hybrid_ragged_step",
           "hybrid_state_spec", "HYBRID_CONFIGS", "SPARSE", "LIGHTNING"]

SPARSE = "sparse"
LIGHTNING = "lightning"
_SELECT_TILE = 16       # tokens of one row scored together in `select`
_FORCED = 1e9           # a forced block's score: above any sum of softmaxes


@dataclasses.dataclass(unsafe_hash=True)
class HybridConfig:
    vocab_size: int = 1024           # rows of the embedding and the head
    max_seq_len: int = 256
    hidden: int = 64
    ffn_hidden: int = 128
    mixer_types: tuple = (SPARSE, LIGHTNING, LIGHTNING, SPARSE)
    # sparse (grouped-query) layers
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    # lightning layers
    lightning_heads: int = 4
    lightning_head_dim: int = 16
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_depth: int = 32              # the depth under the root, as published
    logit_divisor: float = 16.0      # hidden / dim_model_base
    # block selection
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    lightning_decay: bool = True     # False: lambda = 1 (a control)
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types)
        if set(self.mixer_types) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types {self.mixer_types}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads does not divide num_heads")
        if self.block_size % self.kernel_stride:
            raise ValueError("kernel_stride does not divide block_size")
        if not 0 < self.kernel_size - self.kernel_stride <= self.block_size:
            raise ValueError("a span reaches at most one block back")

    @property
    def num_layers(self):
        return len(self.mixer_types)

    def count(self, kind):
        return sum(1 for m in self.mixer_types if m == kind)

    @property
    def residual_scale(self):
        return self.scale_depth / math.sqrt(self.mup_depth)

    @property
    def spans_per_page(self):
        return self.block_size // self.kernel_stride

    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


HYBRID_CONFIGS = {
    # MiniCPM-SALA (openbmb/MiniCPM-SALA config.json) at published widths,
    # layers 9..16 of its 32: one pipeline stage of four
    "minicpm-sala-8l": HybridConfig(
        vocab_size=73472, max_seq_len=34816, hidden=4096, ffn_hidden=16384,
        mixer_types=(SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,),
        num_heads=32, num_kv_heads=2, head_dim=128, lightning_heads=32,
        lightning_head_dim=128, logit_divisor=16.0),
    "tiny": HybridConfig(
        kernel_size=2, kernel_stride=1, block_size=4, topk=6,
        window_size=8, dense_len=32, logit_divisor=4.0, dtype="float32"),
}


# ------------------------------------------------------------------ params


def hybrid_init(cfg: HybridConfig, key=None, dtype=None):
    """The parameter pytree: ``sparse`` and ``lightning`` hold their
    layers stacked on axis 0, in the order the layers of that kind have
    in ``mixer_types``."""
    key = key if key is not None else jax.random.key(0)
    dt = dtype or cfg.jdtype()
    D, F, V = cfg.hidden, cfg.ffn_hidden, cfg.vocab_size
    keys = iter(jax.random.split(key, 32))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dt)

    def stack(n, hq, hkv, hd, out_norm):
        p = {"ln1": jnp.ones((n, D), dt), "ln2": jnp.ones((n, D), dt),
             "q_w": w(n, D, hq * hd), "k_w": w(n, D, hkv * hd),
             "v_w": w(n, D, hkv * hd), "gate_w": w(n, D, hq * hd),
             "o_w": w(n, hq * hd, D),
             "q_norm": jnp.ones((n, hd), dt), "k_norm": jnp.ones((n, hd), dt),
             "mlp_gate_w": w(n, D, F), "mlp_up_w": w(n, D, F),
             "mlp_down_w": w(n, F, D)}
        if out_norm:
            p["o_norm"] = jnp.ones((n, hd), dt)
        return p

    return {
        "wte": w(V, D),
        SPARSE: stack(cfg.count(SPARSE), cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, False),
        LIGHTNING: stack(cfg.count(LIGHTNING), cfg.lightning_heads,
                         cfg.lightning_heads, cfg.lightning_head_dim, True),
        "norm_f": jnp.ones((D,), dt),
        "lm_head": w(D, V),
    }


def hybrid_state_spec(cfg: HybridConfig, *, num_pages, page_size,
                      max_batch_size):
    """What the cache manager has to hold for this model, in the order the
    step takes and returns it: ``(name, shape, dtype, kind)`` with kind
    ``"pages"`` (axis 1 is the physical page) or ``"slots"`` (axis 1 is
    the batch row)."""
    if page_size != cfg.block_size:
        raise ValueError(
            f"page_size {page_size} must be the model's block_size "
            f"{cfg.block_size}: a selected block is a page")
    ls, ll = cfg.count(SPARSE), cfg.count(LIGHTNING)
    kv = (ls, num_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
    hl, hd = cfg.lightning_heads, cfg.lightning_head_dim
    return [
        ("k_pages", kv, cfg.jdtype(), "pages"),
        ("v_pages", kv, cfg.jdtype(), "pages"),
        ("kc_pages", (ls, num_pages, cfg.spans_per_page, cfg.num_kv_heads,
                      cfg.head_dim), cfg.jdtype(), "pages"),
        ("lin_state", (ll, max_batch_size, hl, hd, hd), jnp.float32,
         "slots"),
    ]


# ------------------------------------------------------------------- pieces


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotary positions over the whole head, halves rotated against each
    other (the ``rotate_half`` convention); ``x [T, H, hd]``, ``pos [T]``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]          # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _gated_mlp(h, gate_w, up_w, down_w, gate_scale=None):
    """``W_down(silu(gate_scale * W_gate h) * (W_up h))`` on ``h [T, D]``."""
    a = jnp.einsum("td,df->tf", h, gate_w)
    if gate_scale is not None:
        a = a * gate_scale
    b = jnp.einsum("td,df->tf", h, up_w)
    return jnp.einsum("tf,fd->td", jax.nn.silu(a) * b, down_w)


def _mlp(cfg, p, i, x):
    return _gated_mlp(_rms(x, p["ln2"][i], cfg.rms_eps), p["mlp_gate_w"][i],
                      p["mlp_up_w"][i], p["mlp_down_w"][i])


def _write_compressed(cfg, kc, kp, layer, tables, query_lens, context_lens,
                      max_q):
    """Write the compressed keys whose span this step completed: the mean
    of ``kernel_size`` cached keys, into slot ``j % spans_per_page`` of
    the page that holds the span's first key.  A span that reaches into
    the next page is completed when that page's first keys arrive."""
    B, P = tables.shape[0], kp.shape[1]
    ks, st, ps = cfg.kernel_size, cfg.kernel_stride, cfg.block_size
    p0, p1 = context_lens - query_lens, context_lens
    j_lo = jnp.maximum(-(-(p0 - ks + 1) // st), 0)
    j = j_lo[:, None] + jnp.arange(max_q // st + 1)[None, :]        # [B, C]
    end = j * st + ks - 1
    done = (end >= p0[:, None]) & (end < p1[:, None])
    pos = j[..., None] * st + jnp.arange(ks)                     # [B, C, ks]
    width = tables.shape[1]
    page = jnp.take_along_axis(
        tables[:, None, :], jnp.minimum(pos // ps, width - 1), axis=2)
    # every index explicit: a gather of [hd] rows, which leaves the pool in
    # the layout the attention kernel reads (a slice over the heads in the
    # middle made XLA re-lay the whole pool around every use)
    heads = jnp.arange(kp.shape[2])
    keys = kp[layer, page[..., None], heads,
              (pos % ps)[..., None]]                  # [B, C, ks, Hkv, hd]
    kbar = jnp.mean(keys.astype(jnp.float32), axis=2).astype(kc.dtype)
    home = jnp.take_along_axis(
        tables, jnp.minimum(j * st // ps, width - 1), axis=1)
    home = jnp.where(done, home, P)                      # OOB => dropped
    return kc.at[layer, home, j % cfg.spans_per_page].set(kbar, mode="drop")


def _select_tiles(view: RaggedView):
    """The packed tokens regrouped into tiles of ``_SELECT_TILE`` tokens
    of one row each, so that a tile is scored against one row's
    compressed keys: ``(number of tiles, tile of token, lane of token)``
    with invalid tokens sent to tile ``NT`` (out of bounds)."""
    n, slots = _SELECT_TILE, view.batch.slots
    nt = view.B + -(-view.T // n)
    per_row = -(-view.batch.query_lens // n)
    first = jnp.cumsum(per_row) - per_row
    tile = jnp.take(first, view.row) + slots // n
    return nt, jnp.where(view.valid, tile, nt), slots % n


def _select_blocks(cfg, q, kc, layer, tables, tile_row, tile_pos, tile,
                   lane, nt):
    """Per query token and group, the ``topk`` logical blocks it attends
    over (-1 where fewer exist), ``[T, Hkv, topk]`` int32, and the same as
    flags over the logical blocks, ``[T, Hkv, W]`` bool (every block that
    scores no less than the last one taken: a superset at a tie)."""
    T, H, hd = q.shape
    Hkv, G = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    n, W = _SELECT_TILE, tables.shape[1]
    spp, st, ks, bs = (cfg.spans_per_page, cfg.kernel_stride,
                       cfg.kernel_size, cfg.block_size)
    J = W * spp
    q_t = jnp.zeros((nt, n, Hkv, G, hd), q.dtype).at[tile, lane].set(
        q.reshape(T, Hkv, G, hd), mode="drop")
    kbar = kc[layer, tables[tile_row]]            # [NT, W, spp, Hkv, hd]
    kbar = kbar.reshape(nt, J, Hkv, hd)
    s = jnp.einsum("nthgd,njhd->nhgtj", q_t, kbar,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    span_end = jnp.arange(J) * st + ks - 1
    whole = span_end[None, None, :] <= tile_pos[:, :, None]    # [NT, n, J]
    s = jnp.where(whole[:, None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(whole[:, None, None],
                  jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    z = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.sum(e / jnp.where(z == 0.0, 1.0, z), axis=2)   # [NT, Hkv, n, J]
    # a block scores the best span that overlaps it: its own, and the last
    # of the block before that reach into it
    reach = (ks - 1) // st
    by_block = p.reshape(nt, Hkv, n, W, spp)
    own = jnp.max(by_block, axis=-1)
    before = jnp.max(by_block[..., spp - reach:], axis=-1)
    before = jnp.pad(before[..., :-1], ((0, 0),) * 3 + ((1, 0),))
    score = jnp.maximum(own, before)                       # [NT, Hkv, n, W]
    w = jnp.arange(W)
    at = tile_pos[:, None, :, None]
    forced = (w < cfg.init_blocks) | (
        w >= jnp.maximum(at + 1 - cfg.window_size, 0) // bs)
    key = jnp.where(w <= at // bs, jnp.where(forced, _FORCED, score), -1.0)
    top, idx = jax.lax.top_k(key, min(cfg.topk, W))
    idx = jnp.where(top < 0.0, -1, idx).astype(jnp.int32)
    flags = (key >= top[..., -1:]) & (key >= 0.0)
    at_tile = jnp.minimum(tile, nt - 1)
    return (idx.transpose(0, 2, 1, 3)[at_tile, lane],     # [T, Hkv, K]
            flags.transpose(0, 2, 1, 3)[at_tile, lane])   # [T, Hkv, W]


# --------------------------------------------------------------- the step


def hybrid_ragged_step(cfg: HybridConfig, params, batch: RaggedBatch,
                       k_pages, v_pages, kc_pages, lin_state, *,
                       max_q=None, attn_path=None, dense_only=False):
    """Unified ragged step of the hybrid decoder over its four state
    pools; ``batch`` is the scheduler's ``RaggedBatch``
    (``models/ragged.py``).  A row whose chunk starts at position 0 (a
    newly admitted or recomputed request: ``view.fresh``) starts its
    lightning layers from a zero state, inside the step.
    ``dense_only`` (static) leaves block selection out: every query
    attends over its whole context (a control for the benchmark).

    Returns ``(logits [B, V], k_pages, v_pages, kc_pages, lin_state, stats
    [2] int32)``: ``stats`` is the work items of the sparse layers'
    attention calls, over all of them, and the pages those items hold (an
    item is up to ``pages`` listed pages of one row, group and query tile:
    their ratio is how full the items were)."""
    from ..kernels.lightning_attention import (lightning_attention,
                                               lightning_slopes)
    from ..kernels.paged_attention import (listed_work_items,
                                           ragged_paged_attention)

    tokens, query_lens, context_lens, page_tables = (
        batch.tokens, batch.query_lens, batch.context_lens,
        batch.page_tables)
    T = tokens.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hl, hdl = cfg.lightning_heads, cfg.lightning_head_dim
    c = cfg.residual_scale
    view = RaggedView(batch, max_q=max_q, max_seq_len=cfg.max_seq_len,
                      num_pages=k_pages.shape[1], page_size=k_pages.shape[3])
    pos = view.pos
    slopes = lightning_slopes(Hl) if cfg.lightning_decay \
        else jnp.zeros((Hl,), jnp.float32)

    # `select` scores tokens in tiles of one row each
    with jax.named_scope("select_tiles"):
        nt, tile, lane = _select_tiles(view)
        tile_row = jnp.zeros((nt,), jnp.int32).at[tile].set(view.row,
                                                            mode="drop")
        tile_pos = jnp.full((nt, _SELECT_TILE), -1, jnp.int32).at[
            tile, lane].set(pos, mode="drop")

    with jax.named_scope("embed"):
        x = (jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
             * cfg.scale_emb).astype(cfg.jdtype())                 # [T, D]

    def sparse_layer(x, kp, vp, kc, i):
        p = params[SPARSE]
        with jax.named_scope("sparse_attn"):
            h = _rms(x, p["ln1"][i], cfg.rms_eps)
            q = jnp.einsum("td,de->te", h, p["q_w"][i]).reshape(T, H, hd)
            k = jnp.einsum("td,de->te", h, p["k_w"][i]).reshape(T, Hkv, hd)
            v = jnp.einsum("td,de->te", h, p["v_w"][i]).reshape(T, Hkv, hd)
            q = _rms(q, p["q_norm"][i], cfg.rms_eps)
            k = _rms(k, p["k_norm"][i], cfg.rms_eps)
            with jax.named_scope("kv_write"):
                # one [hd] row per (token, head): the pool keeps the
                # head-major layout the kernel reads
                at = (i, view.page[:, None], jnp.arange(Hkv)[None, :],
                      view.slot_in_page[:, None])
                kp = kp.at[at].set(k.astype(kp.dtype), mode="drop")
                vp = vp.at[at].set(v.astype(vp.dtype), mode="drop")
                kc = _write_compressed(cfg, kc, kp, i, page_tables,
                                       query_lens, context_lens, view.Q)
            selected = (None, cfg.dense_len)
            if not dense_only:
                with jax.named_scope("select"):
                    sel_tok, flag_tok = _select_blocks(
                        cfg, q, kc, i, page_tables, tile_row, tile_pos,
                        tile, lane, nt)
                    # the kernel reads the lists by [row, group, token]
                    selected = (
                        view.pad(sel_tok, -1).transpose(0, 2, 1, 3),
                        cfg.dense_len,
                        view.pad(flag_tok, False).transpose(0, 2, 1, 3))
            # the kernel's work list, built here so that the step can
            # count it
            with jax.named_scope("work_list"):
                items = listed_work_items(
                    query_lens, context_lens, kp.shape[3],
                    page_tables.shape[1], view.Q, Hkv, selected, total_q=T)
            attn = ragged_paged_attention(
                view.pad(q), kp, vp, page_tables, query_lens, context_lens,
                path=attn_path, layer=jnp.int32(i),
                selected=selected, total_q=T, items=items)
            attn = view.unpad(attn).reshape(T, H * hd).astype(x.dtype)
            gate = jax.nn.sigmoid(jnp.einsum("td,de->te", h, p["gate_w"][i]))
            x = x + c * jnp.einsum("te,ed->td", gate * attn, p["o_w"][i])
        with jax.named_scope("mlp"):
            x = x + c * _mlp(cfg, p, i, x)
        _, _, count, n = items
        return (x.astype(cfg.jdtype()), kp, vp, kc,
                jnp.stack([n[0], jnp.sum(count)]))

    def lightning_layer(x, state, i):
        p = params[LIGHTNING]
        with jax.named_scope("lightning"):
            h = _rms(x, p["ln1"][i], cfg.rms_eps)
            proj = lambda name: jnp.einsum(
                "td,de->te", h, p[name][i]).reshape(T, Hl, hdl)
            q = _rope(_rms(proj("q_w"), p["q_norm"][i], cfg.rms_eps), pos,
                      cfg.rope_theta)
            k = _rope(_rms(proj("k_w"), p["k_norm"][i], cfg.rms_eps), pos,
                      cfg.rope_theta)
            v = proj("v_w")
            q = (q.astype(jnp.float32) / math.sqrt(hdl)).astype(q.dtype)
            # the kernel is head-major: [B, Hl, Q, hd] in and out
            pad = lambda a: view.pad(a).transpose(0, 2, 1, 3)
            with jax.named_scope("state_write"):
                o, state = lightning_attention(
                    pad(q), pad(k), pad(v), state, slopes, query_lens,
                    view.fresh, layer=jnp.int32(i), path=attn_path)
            o = view.unpad(o, q_axis=2)                      # [T, Hl, hd]
            o = _rms(o, p["o_norm"][i], cfg.rms_eps).reshape(T, Hl * hdl)
            gate = jax.nn.sigmoid(jnp.einsum("td,de->te", h, p["gate_w"][i]))
            x = x + c * jnp.einsum("te,ed->td", gate * o.astype(x.dtype),
                                   p["o_w"][i])
        with jax.named_scope("mlp"):
            x = x + c * _mlp(cfg, p, i, x)
        return x.astype(cfg.jdtype()), state

    seen = {SPARSE: 0, LIGHTNING: 0}
    stats = jnp.zeros((2,), jnp.int32)
    for kind in cfg.mixer_types:
        i = seen[kind]
        seen[kind] += 1
        if kind == SPARSE:
            x, k_pages, v_pages, kc_pages, counted = sparse_layer(
                x, k_pages, v_pages, kc_pages, i)
            stats = stats + counted
        else:
            x, lin_state = lightning_layer(x, lin_state, i)

    with jax.named_scope("lm_head"):
        x = _rms(x, params["norm_f"], cfg.rms_eps)
        logits = jnp.einsum("bd,dv->bv", view.last(x),
                            params["lm_head"]) / cfg.logit_divisor
    return logits, k_pages, v_pages, kc_pages, lin_state, stats

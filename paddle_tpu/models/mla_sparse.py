"""Latent-attention decoder with a learned token-level indexer, latent
sliding-window layers and sigmoid-routed experts, written for serving.

The sixth decoder family of the repo (dots3-note, ``model_type``
``dots3_note``).  What it has that the five before it have not:

- **Latent attention (MLA, arXiv:2405.04434) in every layer.**  A position
  is cached as one row shared by all heads: the normalised latent ``c_kv``
  and one rotary key ``k_rope``.  Attention runs absorbed
  (``kernels/latent_attention.py``): the query's no-position part through
  ``W_UK`` into latent space beside its rotary part, the kernel's latent
  output through ``W_UV``.  Both latents are rescaled after their norms by
  ``sqrt(hidden / rank)`` (``lora_rescale``).  Two layer kinds, each with
  its own ranks, heads and head sizes, so the parameters are one stack per
  kind: full layers (``kv_rank`` 512, 128 heads) and sliding ones
  (``swa_kv_rank`` 1024, 64 heads, the last ``window`` positions).  Every
  head's output is gated by a sigmoid of the layer's normalised input.
- **A learned indexer chooses, token by token, what a full layer reads**
  (DeepSeek Sparse Attention, the indexer of DeepSeek-V3.2-Exp): one
  128-wide index key a position (LayerNorm, rotary on its first part),
  ``index_heads`` index queries a token, ``I[t, s] = sum_j w[t, j]
  ReLU(q_j . k_s)``; the token reads the exact top ``index_topk`` positions
  of its context (``lax.top_k``).  The index keys are a page pool of their
  own, scored through the row's page table for every position of its
  context.
- **A dropless sparse-expert feed-forward** (``models/experts.py``) chosen
  by sigmoid with a selection bias, a shared expert added unweighted, and
  of the routed experts the ones this holder owns (``experts_held``); layer
  0 runs a plain gated MLP.

Three pools (``mla_sparse_state_spec``): the full layers' latent rows and
index keys (kind ``"pages"``, the row's whole page table) and the sliding
layers' latent rows (kind ``"window_pages"``, given back behind the
window).  A latent row is float32 slabs ``[S, 128]``, the layout the
kernel fetches one row at a time (``latent_slab``).

``mla_sparse_ragged_step`` takes the scheduler's ``RaggedBatch``
(``models/ragged.py``) like the other steps; the layers differ in shape,
so the step is a Python loop over them.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..kernels.expert_matmul import visited_rows
from ..kernels.latent_attention import (LANES, STAGED_TILE,
                                        latent_attention, latent_slab,
                                        pack_latent_rows, staged_queries)
from .experts import dropless_experts, expert_tile, route_top_k
from .hybrid import _gated_mlp, _rms
from .ragged import RaggedBatch, RaggedView

__all__ = ["MLASparseConfig", "mla_sparse_init", "mla_sparse_ragged_step",
           "mla_sparse_state_spec", "MLA_SPARSE_CONFIGS", "FULL", "SLIDING",
           "DENSE", "SPARSE"]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
_INDEX_TILE = 16        # tokens of one row scored together by the indexer
_LN_EPS = 1e-6          # the indexer's LayerNorm


@dataclasses.dataclass(frozen=True)
class MLA:
    """One layer kind's latent attention: heads, the two ranks, head
    sizes and rotary theta."""
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    theta: float

    @property
    def row_width(self):
        """Values of a cached row: the latent and the rotary key."""
        return self.kv_rank + self.rope_dim

    @property
    def query_width(self):
        """The absorbed query's width, whole lanes: what the kernel reads
        of a row."""
        return LANES * -(-self.row_width // LANES)


@dataclasses.dataclass(unsafe_hash=True)
class MLASparseConfig:
    vocab_size: int = 512
    max_seq_len: int = 256
    hidden: int = 64
    layer_types: tuple = (FULL, FULL, SLIDING, SLIDING, SLIDING)
    num_dense_layers: int = 1
    full: MLA = MLA(4, 32, 128, 16, 16, 16, 8e7)
    sliding: MLA = MLA(4, 32, 256, 24, 16, 16, 5e4)
    window: int = 9
    index_heads: int = 4
    index_dim: int = 32
    index_topk: int = 24
    lora_rescale: bool = True
    dense_ffn: int = 128
    expert_ffn: int = 32                        # routed and shared experts
    num_experts: int = 16                       # the router's outputs
    experts_held: tuple = (0, 4)                # (first, count) held here
    top_k: int = 4
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.experts_held = tuple(self.experts_held)
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {self.layer_types}")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers {self.num_dense_layers}")
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        for m in (self.full, self.sliding):
            if m.kv_rank % LANES:
                raise ValueError(f"a latent of {m.kv_rank}: whole lanes")
        if self.full.rope_dim > self.index_dim:
            raise ValueError("the indexer rotates rope_dim of its dims")

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def mlp_types(self):
        return tuple(DENSE if i < self.num_dense_layers else SPARSE
                     for i in range(self.num_layers))

    def count(self, kind):
        return sum(k == kind for k in self.layer_types + self.mlp_types)

    def mla(self, kind):
        return self.full if kind == FULL else self.sliding

    def scales(self, kind):
        """``(s_q, s_kv)``: each latent times it after its norm."""
        m = self.mla(kind)
        if not self.lora_rescale:
            return 1.0, 1.0
        return (math.sqrt(self.hidden / m.q_rank),
                math.sqrt(self.hidden / m.kv_rank))

    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


MLA_SPARSE_CONFIGS = {
    # dots3-note-prev (dots-studio/dots3-note-prev config.json) at published
    # widths: layers 0..4 of 46 (the dense full layer, then one period of
    # full, sliding, sliding, sliding), 32 of 256 experts, 19,008 of
    # 152,064 ids: one chip of the eight that share a layer
    "dots3-note-prev-5l": MLASparseConfig(
        vocab_size=19008, max_seq_len=34816, hidden=5120,
        full=MLA(128, 1024, 512, 128, 64, 128, 8e7),
        sliding=MLA(64, 1024, 1024, 192, 64, 128, 5e4), window=513,
        index_heads=64, index_dim=128, index_topk=2048, dense_ffn=13824,
        expert_ffn=1536, num_experts=256, experts_held=(0, 32), top_k=8),
}


# ------------------------------------------------------------------ params


def mla_sparse_init(cfg: MLASparseConfig, key=None, dtype=None):
    """The parameter pytree: one stack per attention kind (``full`` with
    its indexer, ``sliding``) and per feed-forward kind (``dense``,
    ``sparse``), a layer at its index within its kind.  Matrices
    normal(0, 0.02), the router's normal(0, 1 / sqrt(hidden)): logits of
    deviation about 1 on a normalised input, so the top-k is not a tie."""
    key = key if key is not None else jax.random.key(0)
    dt = dtype or cfg.jdtype()
    D, V = cfg.hidden, cfg.vocab_size
    keys = iter(jax.random.split(key, 48))

    def w(*shape, std=0.02, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, dt)
    params = {"wte": w(V, D, std=1.0), "norm_f": ones(D), "lm_head": w(D, V)}
    for kind in (FULL, SLIDING):
        n, m = cfg.count(kind), cfg.mla(kind)
        H = m.heads
        params[kind] = {
            "ln1": ones(n, D), "q_a": w(n, D, m.q_rank),
            "q_norm": ones(n, m.q_rank),
            "q_b": w(n, m.q_rank, H * (m.nope_dim + m.rope_dim)),
            "kv_a": w(n, D, m.kv_rank + m.rope_dim),
            "kv_norm": ones(n, m.kv_rank),
            "k_b": w(n, m.kv_rank, H, m.nope_dim),
            "v_b": w(n, m.kv_rank, H, m.v_dim),
            "g_w": w(n, D, H), "o_w": w(n, H * m.v_dim, D)}
    n, Hi, di = cfg.count(FULL), cfg.index_heads, cfg.index_dim
    params[FULL].update({
        "iq": w(n, cfg.full.q_rank, Hi * di), "ik": w(n, D, di),
        "ik_norm": ones(n, di), "ik_bias": jnp.zeros((n, di), jnp.float32),
        "iw": w(n, D, Hi)})
    n = cfg.count(DENSE)
    params[DENSE] = {
        "ln2": ones(n, D), "gate_w": w(n, D, cfg.dense_ffn),
        "up_w": w(n, D, cfg.dense_ffn), "down_w": w(n, cfg.dense_ffn, D)}
    n, E, F = cfg.count(SPARSE), cfg.experts_held[1], cfg.expert_ffn
    params[SPARSE] = {
        "ln2": ones(n, D),
        "router_w": w(n, D, cfg.num_experts, std=1 / math.sqrt(D)),
        "expert_bias": w(n, cfg.num_experts, std=0.05, dtype=jnp.float32),
        "shared_gate_w": w(n, D, F), "shared_up_w": w(n, D, F),
        "shared_down_w": w(n, F, D),
        "gate_w": w(n, E, D, F), "up_w": w(n, E, D, F),
        "down_w": w(n, E, F, D)}
    return params


def mla_sparse_state_spec(cfg: MLASparseConfig, *, num_pages, page_size,
                          max_batch_size, num_window_pages):
    """The pools, in the order the step takes and returns them: the full
    layers' latent rows and index keys (kind ``"pages"``) and the sliding
    layers' latent rows (kind ``"window_pages"``).  A latent row is a
    position's ``[c_kv | k_rope]`` as float32 slabs ``[S, 128]``, one row
    a position shared by every head; an index key is a position's
    ``index_dim`` values in the model's dtype."""
    del max_batch_size
    full, sliding = cfg.full, cfg.sliding
    return [
        ("latent_pages", (cfg.count(FULL), num_pages, page_size,
                          latent_slab(full.row_width), LANES),
         jnp.float32, "pages"),
        ("index_pages", (cfg.count(FULL), num_pages, page_size,
                         cfg.index_dim), cfg.jdtype(), "pages"),
        ("window_latent_pages", (cfg.count(SLIDING), num_window_pages,
                                 page_size, latent_slab(sliding.row_width),
                                 LANES), jnp.float32, "window_pages")]


# ------------------------------------------------------------------ pieces


def _rope_first(x, pos, theta, rot):
    """The first ``rot`` dimensions of ``x [T, H, d]`` rotated at ``pos
    [T]`` (halves against each other, ``rotate_half``), the rest passed
    through; float32 arithmetic, ``x``'s dtype out."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., : rot // 2], xf[..., rot // 2: rot], xf[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1).astype(x.dtype)


def _layer_norm(x, g, b):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + _LN_EPS) * g.astype(jnp.float32)
            + b.astype(jnp.float32))


def _mm(a, b, eq):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def index_scores(cfg, view: RaggedView, page_tables, index_pool, layer, qi,
                 wi):
    """The indexer's scores ``[T, max_pages * page_size]`` float32 of every
    packed token against every position of its row's context, ``-inf``
    past the token's own position: ``sum_j wi[t, j] ReLU(qi[t, j] .
    k[s])``.  Tokens in tiles of ``_INDEX_TILE`` of one row each: a tile's
    keys are its row's pages, gathered once; an unused tile computes
    nothing."""
    B, page_size = view.B, index_pool.shape[2]
    width = page_tables.shape[1] * page_size
    tile_rows, _ = view.tiles(_INDEX_TILE)
    q_t = view.pad_tiles(qi, _INDEX_TILE)                 # [NT, n, Hi, di]
    w_t = view.pad_tiles(wi, _INDEX_TILE)                 # [NT, n, Hi]
    pos_t = view.pad_tiles(view.pos, _INDEX_TILE, fill=-1)
    positions = jnp.arange(width, dtype=jnp.int32)

    def tile(args):
        q, w, row, pos = args

        def score():
            keys = index_pool[layer, page_tables[row]].reshape(width, -1)
            s = jax.nn.relu(_mm(q, keys, "qjd,sd->qjs"))
            s = jnp.einsum("qjs,qj->qs", s, w)
            return jnp.where(positions[None, :] <= pos[:, None], s,
                             -jnp.inf)

        return jax.lax.cond(
            row < B, score,
            lambda: jnp.full((_INDEX_TILE, width), -jnp.inf, jnp.float32))

    scores = jax.lax.map(tile, (q_t, w_t, jnp.minimum(tile_rows, B), pos_t))
    return view.unpad_tiles(scores)


def _selected(scores, valid, pos, k, page_size):
    """``(positions [T, k] int32, counts [T], pages)``: each token's exact
    top ``min(k, pos + 1)`` positions (the first ``counts`` of its list),
    and the pages over all valid tokens that hold a chosen position (a
    position scored at or above the token's k-th score)."""
    top, listed = jax.lax.top_k(scores, k)
    # the sort's results are kept, not sorted again for a later reader
    # (XLA's rematerialisation ran each layer's sort twice, measured on one
    # v5e chip)
    kth, listed = jax.lax.optimization_barrier((top[:, k - 1:], listed))
    counts = jnp.where(valid, jnp.minimum(pos + 1, k), 0)
    T, width = scores.shape
    chosen = (scores >= kth) & (scores > -jnp.inf)
    hit = jnp.any(chosen.reshape(T, width // page_size, page_size), axis=2)
    pages = jnp.sum(jnp.where(valid[:, None], hit, False).astype(jnp.int32))
    return listed.astype(jnp.int32), counts.astype(jnp.int32), pages


def _decode_scores(view: RaggedView, page_tables, index_pool, layer, qi,
                   wi):
    """The indexer's scores of the first ``B`` packed tokens alone, ``[B,
    width]``: in a step of decode rows only they are every live token (one
    a row, packed in order), and each scores its own row's keys."""
    B, page_size = view.B, index_pool.shape[2]
    width = page_tables.shape[1] * page_size
    row = view.row[:B]
    keys = index_pool[layer, page_tables[row]].reshape(B, width, -1)
    s = jax.nn.relu(_mm(qi[:B], keys, "bjd,bsd->bjs"))
    s = jnp.einsum("bjs,bj->bs", s, wi[:B])
    positions = jnp.arange(width, dtype=jnp.int32)
    return jnp.where(positions[None, :] <= view.pos[:B, None], s, -jnp.inf)


# --------------------------------------------------------------- the step


def mla_sparse_ragged_step(cfg: MLASparseConfig, params, batch: RaggedBatch,
                           latent_pages, index_pages, window_latent_pages,
                           *, max_q=None, attn_path=None):
    """Unified ragged step over the three pools; ``batch`` carries both
    page tables (a ``WindowRaggedBatch``).

    Returns ``(logits [B, V] float32, latent_pages, index_pages,
    window_latent_pages, stats [8] int32)``: ``stats`` is the (token,
    expert) pairs the held experts computed over all sparse layers, the
    most any one held expert got in one layer, the (layer, held expert)s
    that got a pair at all, the rows of the tiles the expert kernels
    visited, the latent rows the full layers' attention read, the pages
    those rows lay in (a page counted once a token and layer), the latent
    rows the sliding layers fetched, and of the full layers' rows those
    the staged call picked out of VMEM (a tile of two or more live
    queries: a prompt's chunk)."""
    tokens = batch.tokens
    T = tokens.shape[0]
    f32, dtype = jnp.float32, cfg.jdtype()
    page_size = latent_pages.shape[2]
    pair_tile = expert_tile(T, cfg.top_k, cfg.num_experts, dtype)
    view = RaggedView(batch, max_q=max_q, max_seq_len=cfg.max_seq_len,
                      num_pages=latent_pages.shape[1], page_size=page_size,
                      num_window_pages=window_latent_pages.shape[1])
    pos, valid = view.pos, view.valid
    # the indexer's tiling made here, at the step's level: made first inside
    # a branch of the cond below, the view's cache would keep that branch's
    # values for the next layer
    view.tiles(_INDEX_TILE)
    width = batch.page_tables.shape[1] * page_size
    topk = min(cfg.index_topk, width)
    stats = dict.fromkeys(("rows", "pages", "window_rows", "staged"),
                          jnp.zeros((), jnp.int32))
    # the full layers' queries in tiles of one row each, for the kernel's
    # staged call
    tiles = (view.tiles(STAGED_TILE)[0],
             view.pad_tiles(jnp.arange(T, dtype=jnp.int32), STAGED_TILE,
                            fill=-1))

    def attention(x, kind, i, pool, index_pool):
        p, m = params[kind], cfg.mla(kind)
        H, dn, dr = m.heads, m.nope_dim, m.rope_dim
        s_q, s_kv = cfg.scales(kind)
        sliding = kind == SLIDING
        tables = batch.window_page_tables if sliding else batch.page_tables
        with jax.named_scope("window_attn" if sliding else "full_attn"):
            u = _rms(x, p["ln1"][i], cfg.rms_eps)
            cq = (_rms(_mm(u, p["q_a"][i], "td,dr->tr"), p["q_norm"][i],
                       cfg.rms_eps) * s_q).astype(dtype)
            q = _mm(cq, p["q_b"][i], "tr,re->te").reshape(T, H, dn + dr)
            q_rope = _rope_first(q[..., dn:], pos, m.theta, dr)
            kv = _mm(u, p["kv_a"][i], "td,dr->tr")
            c_kv = _rms(kv[:, : m.kv_rank], p["kv_norm"][i],
                        cfg.rms_eps) * s_kv
            k_rope = _rope_first(kv[:, None, m.kv_rank:], pos, m.theta,
                                 dr)[:, 0]
            with jax.named_scope("latent_write"):
                page = view.window_page if sliding else view.page
                pool = pool.at[i, page, view.slot_in_page].set(
                    pack_latent_rows(jnp.concatenate([c_kv, k_rope], 1)),
                    mode="drop")
            if sliding:
                counts = jnp.where(valid, jnp.minimum(pos + 1, cfg.window),
                                   0)
                rows_of = dict(first=pos - counts + 1, window=cfg.window)
                stats["window_rows"] = stats["window_rows"] + jnp.sum(counts)
            else:
                index_pool, rows_of = indexer(u, cq, i, index_pool)
                counts = rows_of.pop("counts")
                stats["rows"] = stats["rows"] + jnp.sum(counts)
                stats["staged"] = stats["staged"] + jnp.sum(jnp.where(
                    staged_queries(counts, tiles), counts, 0))
                rows_of["tiles"] = tiles
            with jax.named_scope("latent_attn"):
                q_lat = _mm(q[..., :dn].astype(dtype), p["k_b"][i],
                            "thd,chd->thc")
                qk = jnp.concatenate([q_lat, q_rope.astype(f32)], axis=-1)
                qk = jnp.pad(qk, ((0, 0), (0, 0),
                                  (0, m.query_width - m.row_width)))
                out = latent_attention(
                    qk.astype(dtype), pool, tables, view.row, counts,
                    layer=i, scale=1.0 / math.sqrt(dn + dr),
                    latent=m.kv_rank, path=attn_path, **rows_of)
                heads = _mm(out.astype(dtype), p["v_b"][i], "thc,chd->thd")
            gate = jax.nn.sigmoid(_mm(u, p["g_w"][i], "td,dh->th"))
            heads = (heads * gate[..., None]).astype(dtype)
            o = _mm(heads.reshape(T, H * m.v_dim), p["o_w"][i], "te,ed->td")
        return (x.astype(f32) + o).astype(dtype), pool, index_pool

    def indexer(u, cq, i, index_pool):
        """Writes the tokens' index keys; returns the pool and what the
        kernel reads: the listed positions and their counts."""
        p, dr = params[FULL], cfg.full.rope_dim
        Hi, di = cfg.index_heads, cfg.index_dim
        with jax.named_scope("indexer"):
            k = _layer_norm(_mm(u, p["ik"][i], "td,de->te"),
                            p["ik_norm"][i], p["ik_bias"][i])
            k = _rope_first(k[:, None], pos, cfg.full.theta, dr)[:, 0]
            index_pool = index_pool.at[i, view.page, view.slot_in_page].set(
                k.astype(index_pool.dtype), mode="drop")
            qi = _mm(cq, p["iq"][i], "tr,re->te").reshape(T, Hi, di)
            qi = _rope_first(qi, pos, cfg.full.theta, dr).astype(dtype)
            wi = _mm(u, p["iw"][i], "td,dj->tj") / math.sqrt(Hi * di)

        def chunks():
            with jax.named_scope("indexer"):
                scores = index_scores(cfg, view, batch.page_tables,
                                      index_pool, i, qi, wi)
            with jax.named_scope("index_topk"):
                return _selected(scores, valid, pos, topk, page_size)

        def decodes():
            # B tokens scored and ranked, not T: the rest are padding
            B = view.B
            with jax.named_scope("indexer"):
                scores = _decode_scores(view, batch.page_tables, index_pool,
                                        i, qi, wi)
            with jax.named_scope("index_topk"):
                listed, counts, pages = _selected(
                    scores, valid[:B], pos[:B], topk, page_size)
            pad = ((0, T - B),)
            return (jnp.pad(listed, pad + ((0, 0),)), jnp.pad(counts, pad),
                    pages)

        decoding = jnp.all(batch.query_lens <= 1) if T > view.B else False
        listed, counts, pages = jax.lax.cond(decoding, decodes, chunks) \
            if T > view.B else chunks()
        stats["pages"] = stats["pages"] + pages
        return index_pool, dict(listed=listed, counts=counts)

    def sparse_mlp(x, j):
        p = params[SPARSE]
        with jax.named_scope("mlp"):
            u = _rms(x, p["ln2"][j], cfg.rms_eps)
            with jax.named_scope("router"):
                logits = jnp.einsum("td,de->te", u.astype(f32),
                                    p["router_w"][j].astype(f32),
                                    precision=jax.lax.Precision.HIGHEST)
                weights, experts = route_top_k(
                    logits, cfg.top_k, score="sigmoid",
                    bias=p["expert_bias"][j], scale=cfg.routed_scale)
            with jax.named_scope("shared_expert"):
                shared = _gated_mlp(u, p["shared_gate_w"][j],
                                    p["shared_up_w"][j],
                                    p["shared_down_w"][j])
        with jax.named_scope("experts"):
            routed, sizes = dropless_experts(
                u, weights, experts, p["gate_w"], p["up_w"], p["down_w"],
                layer=j, experts_held=cfg.experts_held,
                num_experts=cfg.num_experts, valid=valid, tile=pair_tile,
                path=attn_path)
        y = x.astype(f32) + shared.astype(f32) + routed
        return y.astype(dtype), sizes

    def dense_mlp(x, j):
        p = params[DENSE]
        with jax.named_scope("mlp"):
            h = _gated_mlp(_rms(x, p["ln2"][j], cfg.rms_eps), p["gate_w"][j],
                           p["up_w"][j], p["down_w"][j])
        return (x.astype(f32) + h.astype(f32)).astype(dtype)

    with jax.named_scope("embed"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(dtype)  # [T, D]
    seen = dict.fromkeys((FULL, SLIDING, DENSE, SPARSE), 0)
    pairs = fullest = active = tile_rows = jnp.zeros((), jnp.int32)
    for kind, mlp in zip(cfg.layer_types, cfg.mlp_types):
        i, j = seen[kind], seen[mlp]
        seen[kind] += 1
        seen[mlp] += 1
        if kind == SLIDING:
            x, window_latent_pages, _ = attention(
                x, kind, i, window_latent_pages, None)
        else:
            x, latent_pages, index_pages = attention(
                x, kind, i, latent_pages, index_pages)
        if mlp == SPARSE:
            x, sizes = sparse_mlp(x, j)
            pairs = pairs + jnp.sum(sizes)
            fullest = jnp.maximum(fullest, jnp.max(sizes))
            active = active + jnp.sum((sizes > 0).astype(jnp.int32))
            tile_rows = tile_rows + visited_rows(sizes, pair_tile)
        else:
            x = dense_mlp(x, j)

    with jax.named_scope("lm_head"):
        x = _rms(x, params["norm_f"], cfg.rms_eps)
        logits = jnp.einsum("bd,dv->bv", view.last(x), params["lm_head"],
                            preferred_element_type=f32)
    return (logits, latent_pages, index_pages, window_latent_pages,
            jnp.stack([pairs, fullest, active, tile_rows, stats["rows"],
                       stats["pages"], stats["window_rows"],
                       stats["staged"]]))

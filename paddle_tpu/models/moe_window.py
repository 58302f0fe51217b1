"""Sparse-expert decoder with sliding-window layers beside full-attention
layers, written for serving.

The fourth decoder family of the repo (Laguna, ``model_type`` ``laguna``).
Two things set it apart from the three before it:

- **Two kinds of attention layer with their own shapes.**  A full layer
  attends causally over the whole context; a sliding layer over the last
  ``window`` positions only, and it has *more query heads* (72 against 48
  over the same 8 key/value heads: groups of 9 and of 6).  Each kind has
  its own rotary table: YaRN-scaled frequencies on half the head in full
  layers, plain ones on the whole head in sliding layers.  Every head's
  output is gated by a sigmoid of a linear map of the layer's normalised
  input before the output projection.  So the parameters are one stack per
  *kind*, and the page pools are two groups: full layers' pools follow the
  row's whole page table, window layers' pools a second table whose pages
  behind the window the cache manager has taken back
  (``serving/kv_cache.py``, kind ``"window_pages"``).  Both are read
  through the grouped-heads mode of ``ragged_paged_attention``, the window
  layers with its lower edge.
- **A dropless sparse-expert feed-forward** (``models/experts.py``): a
  router over all ``num_experts`` experts, the ``top_k`` largest softmax
  scores renormalised and scaled, a shared expert added ungated, and of the
  routed experts the ones this holder owns (``experts_held``).  Leading
  layers named ``dense`` run a plain gated MLP instead.

``moe_window_ragged_step`` takes the scheduler's ``RaggedBatch``
(``models/ragged.py``) like the other three steps.  The layers differ in
shape, so the step is a Python loop over them, not a ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.expert_matmul import visited_rows
from .experts import dropless_experts, expert_tile, route_top_k
from .hybrid import _gated_mlp, _rms
from .ragged import RaggedBatch, RaggedView

__all__ = ["MoEWindowConfig", "moe_window_init", "moe_window_ragged_step",
           "moe_window_state_spec", "MOE_WINDOW_CONFIGS", "FULL",
           "SLIDING", "DENSE", "SPARSE"]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(unsafe_hash=True)
class MoEWindowConfig:
    vocab_size: int = 512
    max_seq_len: int = 256
    hidden: int = 64
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING)
    mlp_types: tuple = (DENSE, SPARSE, SPARSE, SPARSE)
    heads_per_layer: tuple = (4, 6, 6, 6)       # query heads, by layer
    num_kv_heads: int = 2
    head_dim: int = 16
    window: int = 16
    dense_ffn: int = 128
    expert_ffn: int = 32
    shared_ffn: int = 32
    num_experts: int = 16                       # the router's outputs
    experts_held: tuple = (0, 4)                # (first, count) held here
    top_k: int = 4
    routed_scale: float = 2.5
    norm_topk: bool = True
    # rotary positions: full layers (YaRN on part of the head), sliding
    # layers (plain, on part of the head)
    full_theta: float = 500000.0
    full_rotary: float = 0.5
    yarn_factor: float = 128.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    sliding_theta: float = 10000.0
    sliding_rotary: float = 1.0
    rms_eps: float = 1e-6
    # a control for the benchmark: True leaves each token's last choice out
    drop_last_choice: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        for name in ("layer_types", "mlp_types", "heads_per_layer",
                     "experts_held"):
            setattr(self, name, tuple(getattr(self, name)))
        n = len(self.layer_types)
        if len(self.mlp_types) != n or len(self.heads_per_layer) != n:
            raise ValueError("layer_types, mlp_types and heads_per_layer "
                             "name the same layers")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {self.layer_types}")
        if set(self.mlp_types) - {DENSE, SPARSE}:
            raise ValueError(f"mlp_types {self.mlp_types}")
        for kind in (FULL, SLIDING):
            if len({h for h, k in zip(self.heads_per_layer,
                                      self.layer_types) if k == kind}) > 1:
                raise ValueError(f"{kind} layers differ in their heads: a "
                                 f"kind's layers are one stack")
        if any(h % self.num_kv_heads for h in self.heads_per_layer):
            raise ValueError("num_kv_heads does not divide a layer's heads")
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts}")
        # dimensions of a head that are rotated, by layer kind
        self.rotated = {FULL: int(self.head_dim * self.full_rotary),
                        SLIDING: int(self.head_dim * self.sliding_rotary)}

    @property
    def num_layers(self):
        return len(self.layer_types)

    def layers_of(self, kind):
        """Indices (in the model) of the attention layers of ``kind``."""
        return [i for i, k in enumerate(self.layer_types) if k == kind]

    def heads_of(self, kind):
        return next((h for h, k in zip(self.heads_per_layer,
                                       self.layer_types) if k == kind), 0)

    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


MOE_WINDOW_CONFIGS = {
    # Laguna-S-2.1 (poolside/Laguna-S-2.1 config.json) at published widths:
    # layers 0..7 of 48 (the dense first layer, then two periods of 1 full
    # : 3 sliding) and one chip's quarter of each layer — 2 of 8 key/value
    # heads (12 of 48 and 18 of 72 query heads), 64 of 256 experts, 25,088
    # of 100,352 ids
    "laguna-s-2.1-8l": MoEWindowConfig(
        vocab_size=25088, max_seq_len=17920, hidden=3072,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING) * 2,
        mlp_types=(DENSE,) + (SPARSE,) * 7,
        heads_per_layer=(12, 18, 18, 18) * 2, num_kv_heads=2, head_dim=128,
        window=512, dense_ffn=12288, expert_ffn=1024, shared_ffn=1024,
        num_experts=256, experts_held=(0, 64), top_k=10),
    "tiny": MoEWindowConfig(dtype="float32"),
}


# ------------------------------------------------------------------ params


def moe_window_init(cfg: MoEWindowConfig, key=None, dtype=None):
    """The parameter pytree: one stack per attention kind (``full``,
    ``sliding``) and per feed-forward kind (``dense``, ``sparse``), a layer
    at its index within its kind.  Matrices normal(0, 0.02), the router's
    normal(0, 1 / sqrt(hidden)): logits of deviation about 1 on a
    normalised input, so the top-k is not a tie."""
    key = key if key is not None else jax.random.key(0)
    dt = dtype or cfg.jdtype()
    D, V = cfg.hidden, cfg.vocab_size
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32))

    def w(*shape, std=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    params = {"wte": w(V, D), "norm_f": jnp.ones((D,), dt),
              "lm_head": w(D, V)}
    for kind in (FULL, SLIDING):
        n, H = len(cfg.layers_of(kind)), cfg.heads_of(kind)
        params[kind] = {
            "ln1": jnp.ones((n, D), dt),
            "q_w": w(n, D, H * hd), "k_w": w(n, D, Hkv * hd),
            "v_w": w(n, D, Hkv * hd), "g_w": w(n, D, H),
            "o_w": w(n, H * hd, D)}
    n = cfg.mlp_types.count(DENSE)
    params[DENSE] = {
        "ln2": jnp.ones((n, D), dt), "gate_w": w(n, D, cfg.dense_ffn),
        "up_w": w(n, D, cfg.dense_ffn), "down_w": w(n, cfg.dense_ffn, D)}
    n, E, F, S = (cfg.mlp_types.count(SPARSE), cfg.experts_held[1],
                  cfg.expert_ffn, cfg.shared_ffn)
    params[SPARSE] = {
        "ln2": jnp.ones((n, D), dt),
        "router_w": w(n, D, cfg.num_experts, std=1 / math.sqrt(D)),
        "shared_gate_w": w(n, D, S), "shared_up_w": w(n, D, S),
        "shared_down_w": w(n, S, D),
        "gate_w": w(n, E, D, F), "up_w": w(n, E, D, F),
        "down_w": w(n, E, F, D)}
    return params


def moe_window_state_spec(cfg: MoEWindowConfig, *, num_pages, page_size,
                          max_batch_size, num_window_pages):
    """The pools, in the order the step takes and returns them: the full
    layers' pages (kind ``"pages"``, the row's whole table) and the window
    layers' (kind ``"window_pages"``, the table whose pages behind the
    window are given back), head-major as the grouped-heads kernel reads
    them."""
    del max_batch_size
    Hkv, hd, dt = cfg.num_kv_heads, cfg.head_dim, cfg.jdtype()
    full = (len(cfg.layers_of(FULL)), num_pages, Hkv, page_size, hd)
    win = (len(cfg.layers_of(SLIDING)), num_window_pages, Hkv, page_size,
           hd)
    return [("k_pages", full, dt, "pages"), ("v_pages", full, dt, "pages"),
            ("window_k_pages", win, dt, "window_pages"),
            ("window_v_pages", win, dt, "window_pages")]


# ------------------------------------------------------------------ rotary


def rotary_table(cfg: MoEWindowConfig, kind):
    """``(inverse frequencies [rot / 2] float32, factor on cos and sin,
    rotated width)`` of a layer kind.  Full layers: YaRN (arXiv:2309.00071)
    — per frequency a blend of the plain one (extrapolation) and the one
    divided by ``yarn_factor`` (interpolation), by a linear ramp between
    the dimensions whose wavelengths fit ``yarn_beta_fast`` and
    ``yarn_beta_slow`` turns into ``yarn_original_max`` positions — and
    ``yarn_attention_factor`` on cos and sin.  Host arithmetic, float64."""
    rot = cfg.rotated[kind]
    if kind == SLIDING:
        inv = cfg.sliding_theta ** (-np.arange(0, rot, 2) / rot)
        return inv.astype(np.float32), 1.0, rot
    base = cfg.full_theta
    freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)

    def dim_of(turns):
        return (rot * math.log(cfg.yarn_original_max / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp
    inv = (1 / (cfg.yarn_factor * freqs) * (1 - extrapolation)
           + 1 / freqs * extrapolation)
    return inv.astype(np.float32), cfg.yarn_attention_factor, rot


def _rope(x, pos, table):
    """The first ``rot`` dimensions of each head rotated (halves against
    each other, ``rotate_half``), the rest passed through; ``x [T, H,
    hd]``."""
    inv, factor, rot = table
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., : rot // 2], xf[..., rot // 2: rot], xf[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------- the step


def moe_window_ragged_step(cfg: MoEWindowConfig, params, batch: RaggedBatch,
                           k_pages, v_pages, window_k_pages, window_v_pages,
                           *, max_q=None, attn_path=None):
    """Unified ragged step over the two groups of page pools; ``batch``
    carries both page tables (``models/ragged.py``).  The attention kernel
    takes the queries in tiles of ``QUERY_TILE`` (16) slots of one row
    each, and reads up to 8 tiles of a row at once.

    Returns ``(logits [B, V] float32, k_pages, v_pages, window_k_pages,
    window_v_pages, stats [4] int32)``: ``stats`` is the (token, expert)
    pairs the held experts computed over all sparse layers, the most any
    one held expert got in one layer, the (layer, held expert)s that got a
    pair at all: whose weights the step read, and the rows of the tiles
    the expert kernels visited (the pairs and their tiles' padding)."""
    from ..kernels.paged_attention import QUERY_TILE, ragged_paged_attention

    tokens, query_lens, context_lens = (batch.tokens, batch.query_lens,
                                        batch.context_lens)
    T = tokens.shape[0]
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    f32, dtype = jnp.float32, cfg.jdtype()
    pair_tile = expert_tile(T, cfg.top_k, cfg.num_experts, dtype)
    view = RaggedView(batch, max_q=max_q, max_seq_len=cfg.max_seq_len,
                      num_pages=k_pages.shape[1], page_size=k_pages.shape[3],
                      num_window_pages=window_k_pages.shape[1])
    tables = {FULL: rotary_table(cfg, FULL),
              SLIDING: rotary_table(cfg, SLIDING)}
    heads_kv = jnp.arange(Hkv)[None, :]

    def attention(x, kind, i, kp, vp):
        p, H = params[kind], cfg.heads_of(kind)
        sliding = kind == SLIDING
        page = view.window_page if sliding else view.page
        with jax.named_scope("window_attn" if sliding else "attn"):
            u = _rms(x, p["ln1"][i], cfg.rms_eps)
            q = jnp.einsum("td,de->te", u, p["q_w"][i]).reshape(T, H, hd)
            k = jnp.einsum("td,de->te", u, p["k_w"][i]).reshape(T, Hkv, hd)
            v = jnp.einsum("td,de->te", u, p["v_w"][i]).reshape(T, Hkv, hd)
            q = _rope(q, view.pos, tables[kind])
            k = _rope(k, view.pos, tables[kind])
            with jax.named_scope("kv_write"):
                # one [hd] row per (token, head): the pool keeps the
                # head-major layout the kernel reads
                at = (i, page[:, None], heads_kv, view.slot_in_page[:, None])
                kp = kp.at[at].set(k.astype(kp.dtype), mode="drop")
                vp = vp.at[at].set(v.astype(vp.dtype), mode="drop")
            # queries in tiles of one row each: 64 decode rows beside a
            # chunk of 1024 are 128 tiles of 16 slots, not 64 rows of 1024
            heads = ragged_paged_attention(
                view.pad_tiles(q, QUERY_TILE), kp, vp,
                batch.window_page_tables if sliding else batch.page_tables,
                query_lens, context_lens, path=attn_path,
                layer=jnp.int32(i), selected=(None, 2 ** 30),
                window=cfg.window if sliding else None,
                q_tiles=view.tiles(QUERY_TILE))
            heads = view.unpad_tiles(heads)                     # [T, H, hd]
            gate = jax.nn.sigmoid(jnp.einsum(
                "td,dh->th", u, p["g_w"][i], preferred_element_type=f32))
            heads = (heads.astype(f32) * gate[..., None]).astype(dtype)
            out = jnp.einsum("te,ed->td", heads.reshape(T, H * hd),
                             p["o_w"][i], preferred_element_type=f32)
        return (x.astype(f32) + out).astype(dtype), kp, vp

    @jax.named_scope("sparse_mlp")
    def sparse_mlp(x, i):
        p = params[SPARSE]
        u = _rms(x, p["ln2"][i], cfg.rms_eps)
        with jax.named_scope("router"):
            logits = jnp.einsum("td,de->te", u.astype(f32),
                                p["router_w"][i].astype(f32),
                                precision=jax.lax.Precision.HIGHEST)
            weights, experts = route_top_k(
                logits, cfg.top_k, norm_topk=cfg.norm_topk,
                scale=cfg.routed_scale)
            if cfg.drop_last_choice:
                weights = weights.at[:, -1].set(0.0)
        with jax.named_scope("shared_expert"):
            shared = _gated_mlp(u, p["shared_gate_w"][i],
                                p["shared_up_w"][i], p["shared_down_w"][i])
        with jax.named_scope("experts"):
            routed, sizes = dropless_experts(
                u, weights, experts, p["gate_w"], p["up_w"], p["down_w"],
                layer=i, experts_held=cfg.experts_held,
                num_experts=cfg.num_experts, valid=view.valid,
                tile=pair_tile, path=attn_path)
        y = x.astype(f32) + shared.astype(f32) + routed
        return y.astype(dtype), sizes

    def dense_mlp(x, i):
        p = params[DENSE]
        with jax.named_scope("mlp"):
            h = _gated_mlp(_rms(x, p["ln2"][i], cfg.rms_eps), p["gate_w"][i],
                           p["up_w"][i], p["down_w"][i])
        return (x.astype(f32) + h.astype(f32)).astype(dtype)

    with jax.named_scope("embed"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(dtype)  # [T, D]
    seen = dict.fromkeys((FULL, SLIDING, DENSE, SPARSE), 0)
    pairs = jnp.zeros((), jnp.int32)
    fullest = jnp.zeros((), jnp.int32)
    active = jnp.zeros((), jnp.int32)
    tile_rows = jnp.zeros((), jnp.int32)
    for kind, mlp in zip(cfg.layer_types, cfg.mlp_types):
        i, j = seen[kind], seen[mlp]
        seen[kind] += 1
        seen[mlp] += 1
        if kind == SLIDING:
            x, window_k_pages, window_v_pages = attention(
                x, kind, i, window_k_pages, window_v_pages)
        else:
            x, k_pages, v_pages = attention(x, kind, i, k_pages, v_pages)
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
    return (logits, k_pages, v_pages, window_k_pages, window_v_pages,
            jnp.stack([pairs, fullest, active, tile_rows]))

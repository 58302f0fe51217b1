"""Gated short-convolution layers beside QK-normed grouped-query attention,
with a sparse-expert feed-forward chosen by sigmoid and a selection bias,
written for serving.

The fifth decoder family of the repo (LFM2-MoE, ``model_type``
``lfm2_moe``).  Every block is pre-norm, ``h = x + Mixer(RMSNorm(x))``, ``y
= h + FFN(RMSNorm(h))``, and the mixer is one of two kinds:

- **A gated short convolution** (``conv``): ``[B | C | x] = W_in u``, ``z
  = B * x``, a causal depthwise convolution of width ``conv_width`` over
  ``z`` with no bias and no activation, then ``W_out (C * conv(z))``.  Its
  only cache is the row's last ``conv_width - 1`` values of ``z``: a
  per-row state (``"slots"``), the only state of most of the layers.  The
  taps over a ragged batch are ``models/ssm.py``'s ``conv_taps``.
- **Grouped-query attention** (``full_attention``) whose queries and keys
  each pass through an RMSNorm per head before rotary positions, over a
  paged key/value cache read through the grouped-heads mode of
  ``ragged_paged_attention``.

The feed-forward of the first ``num_dense_layers`` layers is a plain gated
MLP; the rest are dropless sparse-expert layers (``models/experts.py``):
``p = sigmoid(W_r u)`` over all experts in float32, the ``top_k`` of ``p +
b`` chosen (``b`` the selection bias, which does not weight them), the
unbiased ``p`` of the chosen renormalised, every expert held here.  The
head is tied to the embedding.

``short_conv_ragged_step`` takes the scheduler's ``RaggedBatch``
(``models/ragged.py``) like the other steps.  The layers differ in kind,
so the step is a Python loop over them, as ``moe_window.py``'s is; each
kind's parameters are one stack, a layer at its index within its kind.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..kernels.expert_matmul import visited_rows
from .experts import dropless_experts, expert_tile, route_top_k
from .hybrid import _gated_mlp, _rms, _rope
from .ragged import RaggedBatch, RaggedView
from .ssm import conv_taps

__all__ = ["ShortConvMoEConfig", "short_conv_init", "short_conv_ragged_step",
           "short_conv_state_spec", "SHORT_CONV_CONFIGS", "CONV", "ATTN",
           "DENSE", "SPARSE"]

CONV, ATTN = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# a pool row's lanes: a head narrower than this is stored zero-padded to it
_LANES = 128


@dataclasses.dataclass(unsafe_hash=True)
class ShortConvMoEConfig:
    vocab_size: int = 512
    max_seq_len: int = 256
    hidden: int = 64
    layer_types: tuple = (CONV, CONV, ATTN, CONV)
    num_dense_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    conv_width: int = 3
    dense_ffn: int = 128
    expert_ffn: int = 32
    num_experts: int = 8
    top_k: int = 2
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    # False chooses the experts by p alone (a control for the benchmark)
    expert_bias: bool = True
    # a control for the benchmark: True leaves the convolution's oldest
    # tap out
    conv_two_taps: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(f"layer_types {self.layer_types}")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(f"num_dense_layers {self.num_dense_layers} of "
                             f"{self.num_layers}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads does not divide num_heads")
        if not 0 < self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} of {self.num_experts}")
        if self.conv_width < 2:
            raise ValueError("conv_width < 2 keeps no window")

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def mlp_types(self):
        return ((DENSE,) * self.num_dense_layers
                + (SPARSE,) * (self.num_layers - self.num_dense_layers))

    def count(self, kind):
        return (self.layer_types + self.mlp_types).count(kind)

    @property
    def pool_head_dim(self):
        """A head's width in the page pools: ``head_dim`` rounded up to
        whole rows of 128 lanes.  A pool of 64-wide rows made XLA re-lay
        both pools around every layer's scatter of a token's rows (8
        copies of 402 MB a step and 1.2 GB of temporaries at the cell's
        shapes, AOT compile for v5e); 128-wide rows are written in
        place."""
        return -(-self.head_dim // _LANES) * _LANES

    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


SHORT_CONV_CONFIGS = {
    # LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B config.json) at published widths:
    # layers 0..11 of 24, the first of two pipeline stages (both dense
    # layers, 3 attention and 9 convolution layers), all 32 experts held
    "lfm2-8b-a1b-12l": ShortConvMoEConfig(
        vocab_size=65536, max_seq_len=2048, hidden=2048,
        layer_types=(CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV, CONV,
                     CONV, ATTN, CONV),
        num_dense_layers=2, num_heads=32, num_kv_heads=8, head_dim=64,
        conv_width=3, dense_ffn=7168, expert_ffn=1792, num_experts=32,
        top_k=4),
    "tiny": ShortConvMoEConfig(dtype="float32"),
}


# ------------------------------------------------------------------ params


def short_conv_init(cfg: ShortConvMoEConfig, key=None, dtype=None):
    """The parameter pytree: one stack per mixer kind (``conv``,
    ``full_attention``) and per feed-forward kind (``dense``, ``sparse``),
    a layer at its index within its kind; the head is ``wte``.  Matrices
    normal(0, 0.02), the router's normal(0, 1 / sqrt(hidden)) and the
    selection bias normal(0, 0.05): a top-k that is neither a tie nor
    blind to the bias.  The bias is float32, as the router is."""
    key = key if key is not None else jax.random.key(0)
    dt = dtype or cfg.jdtype()
    D, V, K = cfg.hidden, cfg.vocab_size, cfg.conv_width
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32))

    def w(*shape, std=0.02, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    n = cfg.count(CONV)
    params = {"wte": w(V, D), "norm_f": jnp.ones((D,), dt),
              CONV: {"ln1": jnp.ones((n, D), dt), "in_w": w(n, D, 3 * D),
                     "conv_w": w(n, K, D, std=0.5), "out_w": w(n, D, D)}}
    n = cfg.count(ATTN)
    params[ATTN] = {
        "ln1": jnp.ones((n, D), dt), "q_w": w(n, D, H * hd),
        "k_w": w(n, D, Hkv * hd), "v_w": w(n, D, Hkv * hd),
        "q_norm": jnp.ones((n, hd), dt), "k_norm": jnp.ones((n, hd), dt),
        "o_w": w(n, H * hd, D)}
    n = cfg.count(DENSE)
    params[DENSE] = {
        "ln2": jnp.ones((n, D), dt), "gate_w": w(n, D, cfg.dense_ffn),
        "up_w": w(n, D, cfg.dense_ffn), "down_w": w(n, cfg.dense_ffn, D)}
    n, E, F = cfg.count(SPARSE), cfg.num_experts, cfg.expert_ffn
    params[SPARSE] = {
        "ln2": jnp.ones((n, D), dt),
        "router_w": w(n, D, E, std=1 / math.sqrt(D)),
        "expert_bias": w(n, E, std=0.05, dtype=jnp.float32),
        "gate_w": w(n, E, D, F), "up_w": w(n, E, D, F),
        "down_w": w(n, E, F, D)}
    return params


def short_conv_state_spec(cfg: ShortConvMoEConfig, *, num_pages, page_size,
                          max_batch_size):
    """The pools, in the order the step takes and returns them: the
    attention layers' pages (kind ``"pages"``, head-major as the
    grouped-heads kernel reads them, a head's row ``pool_head_dim`` wide)
    and the convolution layers' windows
    (kind ``"slots"``: a row's last ``conv_width - 1`` inputs ``z``)."""
    dt = cfg.jdtype()
    kv = (cfg.count(ATTN), num_pages, cfg.num_kv_heads, page_size,
          cfg.pool_head_dim)
    return [("k_pages", kv, dt, "pages"), ("v_pages", kv, dt, "pages"),
            ("conv_state", (cfg.count(CONV), max_batch_size,
                            cfg.conv_width - 1, cfg.hidden), dt, "slots")]


# --------------------------------------------------------------- the step


def short_conv_ragged_step(cfg: ShortConvMoEConfig, params,
                           batch: RaggedBatch, k_pages, v_pages, conv_state,
                           *, max_q=None, attn_path=None):
    """Unified ragged step over the attention layers' page pools and the
    convolution layers' windows.  A row whose chunk starts at position 0
    (``view.fresh``) starts its windows from zero, inside the step.  The
    attention kernel takes the queries in tiles of ``QUERY_TILE`` (16)
    slots of one row each.

    Returns ``(logits [B, V] float32, k_pages, v_pages, conv_state, stats
    [4] int32)``, ``stats`` as ``moe_window_ragged_step``'s: the (token,
    expert) pairs over all sparse layers, the most any one expert got in
    one layer, the (layer, expert)s that got a pair at all (whose weights
    the step read), and the rows of the tiles the expert kernels
    visited."""
    from ..kernels.paged_attention import QUERY_TILE, ragged_paged_attention

    tokens, query_lens, context_lens = (batch.tokens, batch.query_lens,
                                        batch.context_lens)
    T = tokens.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E = cfg.num_experts
    # a head's rows in the pools and in the kernel, zero past `hd`: the
    # padding adds nothing to a score or to an output's first `hd`
    widen = lambda a: jnp.pad(a, ((0, 0), (0, 0),
                                  (0, cfg.pool_head_dim - hd)))
    f32, dtype = jnp.float32, cfg.jdtype()
    pair_tile = expert_tile(T, cfg.top_k, E, dtype)
    view = RaggedView(batch, max_q=max_q, max_seq_len=cfg.max_seq_len,
                      num_pages=k_pages.shape[1], page_size=k_pages.shape[3])
    heads_kv = jnp.arange(Hkv)[None, :]
    keep = ~view.fresh

    def short_conv(x, i, conv):
        p = params[CONV]
        with jax.named_scope("short_conv"):
            u = _rms(x, p["ln1"][i], cfg.rms_eps)
            proj = jnp.einsum("td,de->te", u, p["in_w"][i],
                              preferred_element_type=f32)
            gate_b, gate_c, xs = jnp.split(proj, 3, axis=1)
            z = (gate_b * xs).astype(dtype)
            w = p["conv_w"][i]
            if cfg.conv_two_taps:
                w = w.at[0].set(0)
            with jax.named_scope("conv_window"):
                window = jnp.where(keep[:, None, None], conv[i],
                                   jnp.zeros_like(conv[i]))
                s, window = conv_taps(view, z, window, w)
                conv = conv.at[i].set(window)
            y = (gate_c * s).astype(dtype)
            out = jnp.einsum("te,ed->td", y, p["out_w"][i],
                             preferred_element_type=f32)
        return (x.astype(f32) + out).astype(dtype), conv

    def attention(x, i, kp, vp):
        p = params[ATTN]
        with jax.named_scope("attn"):
            u = _rms(x, p["ln1"][i], cfg.rms_eps)
            q = jnp.einsum("td,de->te", u, p["q_w"][i]).reshape(T, H, hd)
            k = jnp.einsum("td,de->te", u, p["k_w"][i]).reshape(T, Hkv, hd)
            v = jnp.einsum("td,de->te", u, p["v_w"][i]).reshape(T, Hkv, hd)
            with jax.named_scope("qk_norm"):
                q = _rms(q, p["q_norm"][i], cfg.rms_eps)
                k = _rms(k, p["k_norm"][i], cfg.rms_eps)
            q = widen(_rope(q, view.pos, cfg.rope_theta))
            k = widen(_rope(k, view.pos, cfg.rope_theta))
            v = widen(v)
            with jax.named_scope("kv_write"):
                # one [hd] row per (token, head): the pool keeps the
                # head-major layout the kernel reads
                at = (i, view.page[:, None], heads_kv,
                      view.slot_in_page[:, None])
                kp = kp.at[at].set(k.astype(kp.dtype), mode="drop")
                vp = vp.at[at].set(v.astype(vp.dtype), mode="drop")
            # queries in tiles of one row each: a decode row is one tile
            heads = ragged_paged_attention(
                view.pad_tiles(q, QUERY_TILE), kp, vp, batch.page_tables,
                query_lens, context_lens, scale=1 / math.sqrt(hd),
                path=attn_path, layer=jnp.int32(i), selected=(None, 2 ** 30),
                q_tiles=view.tiles(QUERY_TILE))
            heads = view.unpad_tiles(heads)[..., :hd].reshape(T, H * hd)
            out = jnp.einsum("te,ed->td", heads, p["o_w"][i],
                             preferred_element_type=f32)
        return (x.astype(f32) + out).astype(dtype), kp, vp

    def dense_mlp(x, j):
        p = params[DENSE]
        with jax.named_scope("mlp"):
            h = _gated_mlp(_rms(x, p["ln2"][j], cfg.rms_eps), p["gate_w"][j],
                           p["up_w"][j], p["down_w"][j])
        return (x.astype(f32) + h.astype(f32)).astype(dtype)

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
                    bias=p["expert_bias"][j] if cfg.expert_bias else None)
            with jax.named_scope("experts"):
                routed, sizes = dropless_experts(
                    u, weights, experts, p["gate_w"], p["up_w"],
                    p["down_w"], layer=j, experts_held=(0, E),
                    num_experts=E, valid=view.valid, tile=pair_tile,
                    path=attn_path)
        return (x.astype(f32) + routed).astype(dtype), sizes

    with jax.named_scope("embed"):
        x = jnp.take(params["wte"], tokens, axis=0).astype(dtype)  # [T, D]
    seen = dict.fromkeys((CONV, ATTN, DENSE, SPARSE), 0)
    pairs = jnp.zeros((), jnp.int32)
    fullest = jnp.zeros((), jnp.int32)
    active = jnp.zeros((), jnp.int32)
    tile_rows = jnp.zeros((), jnp.int32)
    for kind, mlp in zip(cfg.layer_types, cfg.mlp_types):
        i, j = seen[kind], seen[mlp]
        seen[kind] += 1
        seen[mlp] += 1
        if kind == CONV:
            x, conv_state = short_conv(x, i, conv_state)
        else:
            x, k_pages, v_pages = attention(x, i, k_pages, v_pages)
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
        # tied: the embedding's rows are the head's columns
        logits = jnp.einsum("bd,vd->bv", view.last(x), params["wte"],
                            preferred_element_type=f32)
    return (logits, k_pages, v_pages, conv_state,
            jnp.stack([pairs, fullest, active, tile_rows]))

"""Parallel-mixer decoder: a Mamba-2 state-space mixer and grouped-query
attention side by side in every block.

The third decoder family of the repo, written for serving.  Where
``models/hybrid.py`` alternates layers of two kinds, every block here runs
two mixers on one normalised input and adds both to the stream (Falcon-H1,
``model_type`` ``falcon_h1``):

    u = RMSNorm(h)
    h += att(u) + ssm(u)
    h += MLP(RMSNorm(h))

each branch with its own published input and output scalars.  The
attention branch is grouped-query attention with rotary positions over a
paged key/value cache, read dense through the grouped-heads mode of
``ragged_paged_attention``.  The state-space branch (Mamba-2,
arXiv:2405.21060) projects ``u`` to ``[z | x | B | C | dt]``, runs a causal
depthwise convolution of width ``conv_width`` over ``[x | B | C]`` and then
the selective scan of ``kernels/ssd_scan.py``; its output is gated by
``z``, normalised within each group and projected back.

So every layer owns three kinds of cache at once: pages of keys and
values, the convolution's window (the row's last ``conv_width - 1`` inputs,
without which a prompt's second chunk would start from zeros) and the
scan's ``[heads, P, N]`` float32 state.  The last two are per batch row.
All layers are alike, so the parameters are one stack and the step is one
``lax.scan`` over it with the four pools carried in place.

``ssm_ragged_step`` takes the scheduler's ``RaggedBatch``
(``models/ragged.py``), as ``gpt_ragged_step`` and ``hybrid_ragged_step``
do.  RMSNorm, the rotary positions and the gated MLP are ``hybrid.py``'s.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .hybrid import _gated_mlp, _rms, _rope
from .ragged import RaggedBatch, RaggedView

__all__ = ["SSMConfig", "ssm_init", "ssm_ragged_step", "ssm_state_spec",
           "SSM_CONFIGS"]


@dataclasses.dataclass(unsafe_hash=True)
class SSMConfig:
    vocab_size: int = 1024
    max_seq_len: int = 256
    hidden: int = 64
    ffn_hidden: int = 128
    num_layers: int = 3
    # attention branch
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1e11
    # state-space branch
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_state: int = 16
    ssm_groups: int = 2
    conv_width: int = 4
    rms_eps: float = 1e-5
    # the published scalars
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)    # z, x, B, C, dt
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    # controls for the benchmark: False leaves part of the mathematics out
    input_dependent_decay: bool = True   # False: dt from its bias alone
    carry_conv_window: bool = True       # False: zeros before every chunk
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.ssm_multipliers = tuple(self.ssm_multipliers)
        self.mlp_multipliers = tuple(self.mlp_multipliers)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_kv_heads does not divide num_heads")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_groups does not divide ssm_heads")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, "
                             "dt) and mlp_multipliers 2 (gate, down)")

    @property
    def d_ssm(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def bc_width(self):
        return self.ssm_groups * self.ssm_state

    @property
    def conv_channels(self):
        return self.d_ssm + 2 * self.bc_width

    @property
    def in_width(self):
        return 2 * self.d_ssm + 2 * self.bc_width + self.ssm_heads

    def in_proj_scale(self):
        """``[in_width]`` float32: what multiplies each column of the input
        projection's output — ``ssm_in_multiplier`` (applied to the
        input: the same thing) times the scalar of the column's part."""
        parts = [self.d_ssm, self.d_ssm, self.bc_width, self.bc_width,
                 self.ssm_heads]
        return self.ssm_in_multiplier * np.repeat(
            np.array(self.ssm_multipliers, np.float32), parts)

    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


SSM_CONFIGS = {
    # Falcon-H1-34B-Instruct (tiiuae/Falcon-H1-34B-Instruct config.json) at
    # published widths, 6 of its 72 layers: one pipeline stage of twelve
    "falcon-h1-34b-6l": SSMConfig(
        vocab_size=261120, max_seq_len=2048, hidden=5120, ffn_hidden=21504,
        num_layers=6, num_heads=20, num_kv_heads=4, head_dim=128,
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2),
    "tiny": SSMConfig(dtype="float32"),
}


# ------------------------------------------------------------------ params


def ssm_init(cfg: SSMConfig, key=None, dtype=None):
    """The parameter pytree: ``blocks`` holds the layers stacked on axis
    0.  Matrices normal(0, 0.02); what sets the decay as Mamba-2 starts
    it, so that it is neither 0 nor 1: ``A`` uniform in [1, 16], ``dt``'s
    bias the inverse softplus of a log-uniform step in [1e-3, 1e-1]."""
    key = key if key is not None else jax.random.key(0)
    dt = dtype or cfg.jdtype()
    D, F, V, L = cfg.hidden, cfg.ffn_hidden, cfg.vocab_size, cfg.num_layers
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hs = cfg.ssm_heads
    keys = iter(jax.random.split(key, 24))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * 0.02).astype(dt)

    def uniform(lo, hi, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    step = jnp.exp(uniform(math.log(1e-3), math.log(1e-1), L, Hs))
    blocks = {
        "ln1": jnp.ones((L, D), dt), "ln2": jnp.ones((L, D), dt),
        "q_w": w(L, D, H * hd), "k_w": w(L, D, Hkv * hd),
        "v_w": w(L, D, Hkv * hd), "o_w": w(L, H * hd, D),
        "in_w": w(L, D, cfg.in_width),
        "conv_w": uniform(-0.5, 0.5, L, cfg.conv_width,
                          cfg.conv_channels).astype(dt),
        "conv_b": jnp.zeros((L, cfg.conv_channels), dt),
        "A_log": jnp.log(uniform(1.0, 16.0, L, Hs)),
        "D": jnp.ones((L, Hs), jnp.float32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_norm": jnp.ones((L, cfg.d_ssm), dt),
        "out_w": w(L, cfg.d_ssm, D),
        "mlp_gate_w": w(L, D, F), "mlp_up_w": w(L, D, F),
        "mlp_down_w": w(L, F, D),
    }
    return {"wte": w(V, D), "blocks": blocks, "norm_f": jnp.ones((D,), dt),
            "lm_head": w(D, V)}


def ssm_state_spec(cfg: SSMConfig, *, num_pages, page_size, max_batch_size):
    """What the cache manager has to hold for this model, in the order the
    step takes and returns it: ``(name, shape, dtype, kind)`` with kind
    ``"pages"`` (axis 1 is the physical page) or ``"slots"`` (axis 1 is
    the batch row).  The pages are head-major, as the grouped-heads mode
    of the attention kernel reads them."""
    L = cfg.num_layers
    kv = (L, num_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
    return [
        ("k_pages", kv, cfg.jdtype(), "pages"),
        ("v_pages", kv, cfg.jdtype(), "pages"),
        ("conv_state", (L, max_batch_size, cfg.conv_width - 1,
                        cfg.conv_channels), cfg.jdtype(), "slots"),
        ("ssm_state", (L, max_batch_size, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32, "slots"),
    ]


# ------------------------------------------------------------------- pieces


def _conv(cfg, view: RaggedView, xbc, window, w, b):
    """The causal depthwise convolution over the packed tokens ``xbc [T,
    C]``, each row's chunk read with the row's stored ``window [B, K - 1,
    C]`` in front of it; returns ``(SiLU(conv) [T, C], the rows' new
    windows)``.  A row's new window is its last ``K - 1`` valid inputs:
    the chunk's, and for a chunk shorter than that the tail of the old
    window before them; an idle row keeps its own."""
    K1 = cfg.conv_width - 1
    T = xbc.shape[0]
    slots = view.batch.slots
    q = view.batch.query_lens
    f32 = jnp.float32
    out = xbc.astype(f32) * w[K1].astype(f32) + b.astype(f32)
    for back in range(1, K1 + 1):
        # the input `back` slots earlier: the packed neighbour (a row's
        # tokens are contiguous) or, before the chunk's first token, the
        # stored window
        earlier = jnp.pad(xbc, ((back, 0), (0, 0)))[:T]
        stored = window[view.row, jnp.clip(K1 + slots - back, 0, K1 - 1)]
        tap = jnp.where((slots >= back)[:, None], earlier, stored)
        out = out + tap.astype(f32) * w[K1 - back].astype(f32)
    last = jnp.clip(jnp.cumsum(q) - 1, 0, T - 1)                   # [B]
    i = jnp.arange(K1)[None, :]                                    # [1, K1]
    at = q[:, None] - K1 + i          # the chunk slot of new window slot i
    from_chunk = xbc[jnp.clip(last[:, None] - (K1 - 1 - i), 0, T - 1)]
    from_window = jnp.take_along_axis(
        window, jnp.clip(q[:, None] + i, 0, K1 - 1)[..., None], axis=1)
    new = jnp.where((at >= 0)[..., None], from_chunk, from_window)
    return jax.nn.silu(out).astype(xbc.dtype), new.astype(window.dtype)


def _gated_group_norm(cfg, y, z, g):
    """``RMSNorm(y * SiLU(z))`` with the mean square taken within each of
    the ``ssm_groups`` groups of channels (the gate before the norm)."""
    T = y.shape[0]
    v = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    v = v.reshape(T, cfg.ssm_groups, -1)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                          + cfg.rms_eps)
    return (v.reshape(T, -1) * g.astype(jnp.float32)).astype(y.dtype)


# --------------------------------------------------------------- the step


def ssm_ragged_step(cfg: SSMConfig, params, batch: RaggedBatch, k_pages,
                    v_pages, conv_state, ssm_state, *, max_q=None,
                    attn_path=None):
    """Unified ragged step of the parallel-mixer decoder over its four
    state pools; ``batch`` is the scheduler's ``RaggedBatch``
    (``models/ragged.py``).  A row whose chunk starts at position 0 (a
    newly admitted or recomputed request: ``view.fresh``) starts its
    convolution window and its scan state from zero, inside the step.

    Returns ``(logits [B, V] float32, k_pages, v_pages, conv_state,
    ssm_state)``."""
    from ..kernels.paged_attention import ragged_paged_attention
    from ..kernels.ssd_scan import ssd_scan

    tokens, query_lens, context_lens, page_tables = (
        batch.tokens, batch.query_lens, batch.context_lens,
        batch.page_tables)
    T = tokens.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Hs, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                   cfg.ssm_groups)
    f32, dtype = jnp.float32, cfg.jdtype()
    view = RaggedView(batch, max_q=max_q, max_seq_len=cfg.max_seq_len,
                      num_pages=k_pages.shape[1], page_size=k_pages.shape[3])
    in_scale = cfg.in_proj_scale()
    cuts = (cfg.d_ssm, cfg.d_ssm + cfg.conv_channels)      # z | xBC | dt
    xbc_cuts = (cfg.d_ssm, cfg.d_ssm + cfg.bc_width)       # x | B | C
    mlp_gate, mlp_down = cfg.mlp_multipliers
    keep = ~view.fresh if cfg.carry_conv_window \
        else jnp.zeros_like(view.fresh)

    def out_proj(a, w, scale):
        return jnp.einsum("te,ed->td", a, w,
                          preferred_element_type=f32) * scale

    def block(carry, xs):
        x, kp, vp, conv, ssm = carry
        p, layer = xs
        with jax.named_scope("norm"):
            u = _rms(x, p["ln1"], cfg.rms_eps)
        with jax.named_scope("attn"):
            a = u * cfg.attention_in_multiplier \
                if cfg.attention_in_multiplier != 1 else u
            q = jnp.einsum("td,de->te", a, p["q_w"]).reshape(T, H, hd)
            k = (jnp.einsum("td,de->te", a, p["k_w"],
                            preferred_element_type=f32)
                 * cfg.key_multiplier).astype(dtype).reshape(T, Hkv, hd)
            v = jnp.einsum("td,de->te", a, p["v_w"]).reshape(T, Hkv, hd)
            q = _rope(q, view.pos, cfg.rope_theta)
            k = _rope(k, view.pos, cfg.rope_theta)
            with jax.named_scope("kv_write"):
                # one [hd] row per (token, head): the pool keeps the
                # head-major layout the kernel reads
                at = (layer, view.page[:, None], jnp.arange(Hkv)[None, :],
                      view.slot_in_page[:, None])
                kp = kp.at[at].set(k.astype(kp.dtype), mode="drop")
                vp = vp.at[at].set(v.astype(vp.dtype), mode="drop")
            heads = ragged_paged_attention(
                view.pad(q), kp, vp, page_tables, query_lens, context_lens,
                path=attn_path, layer=layer, selected=(None, 2 ** 30),
                total_q=T)
            heads = view.unpad(heads).reshape(T, H * hd).astype(dtype)
            att = out_proj(heads, p["o_w"], cfg.attention_out_multiplier)
        with jax.named_scope("ssm"):
            proj = jnp.einsum("td,de->te", u, p["in_w"],
                              preferred_element_type=f32) * in_scale
            z, xbc, dt = jnp.split(proj, cuts, axis=1)
            with jax.named_scope("conv"):
                window = jnp.where(keep[:, None, None], conv[layer],
                                   jnp.zeros_like(conv[layer]))
                xbc, window = _conv(cfg, view, xbc.astype(dtype), window,
                                    p["conv_w"], p["conv_b"])
                conv = conv.at[layer].set(window)
            xs_, bm, cm = jnp.split(xbc, xbc_cuts, axis=1)
            if not cfg.input_dependent_decay:
                dt = jnp.zeros_like(dt)
            dt = jax.nn.softplus(dt + p["dt_bias"])               # [T, Hs]
            # the kernel is head-major: [B, heads, Q, ...] in and out
            pad = lambda a: view.pad(a).transpose(0, 2, 1, 3)
            with jax.named_scope("state_write"):
                y, ssm = ssd_scan(
                    pad(xs_.reshape(T, Hs, P)),
                    view.pad(dt).transpose(0, 2, 1),
                    pad(bm.reshape(T, G, N)), pad(cm.reshape(T, G, N)),
                    -jnp.exp(p["A_log"]), p["D"], ssm, query_lens,
                    view.fresh, layer=layer, path=attn_path)
            y = view.unpad(y, q_axis=2).reshape(T, Hs * P)
            y = _gated_group_norm(cfg, y, z, p["ssm_norm"])
            out = out_proj(y, p["out_w"], cfg.ssm_out_multiplier)
        with jax.named_scope("residual"):
            x = (x.astype(f32) + att + out).astype(dtype)
        with jax.named_scope("mlp"):
            h = _gated_mlp(_rms(x, p["ln2"], cfg.rms_eps), p["mlp_gate_w"],
                           p["mlp_up_w"], p["mlp_down_w"], mlp_gate)
            x = (x.astype(f32) + h.astype(f32) * mlp_down).astype(dtype)
        return (x, kp, vp, conv, ssm), None

    with jax.named_scope("embed"):
        x = (jnp.take(params["wte"], tokens, axis=0).astype(f32)
             * cfg.embedding_multiplier).astype(dtype)             # [T, D]
    (x, k_pages, v_pages, conv_state, ssm_state), _ = jax.lax.scan(
        block, (x, k_pages, v_pages, conv_state, ssm_state),
        (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    with jax.named_scope("lm_head"):
        x = _rms(x, params["norm_f"], cfg.rms_eps)
        logits = jnp.einsum("bd,dv->bv", view.last(x), params["lm_head"],
                            preferred_element_type=f32) \
            * cfg.lm_head_multiplier
    return logits, k_pages, v_pages, conv_state, ssm_state

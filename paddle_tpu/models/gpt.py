"""GPT — the flagship decoder-transformer family.

Parity targets: the reference's GPT pretrain configs (BASELINE.md — GPT-3
1.3B/6.7B hybrid DP+TP+PP+sharding) and its fused transformer ops
(operators/fused/fused_multi_transformer_op.cu,
incubate/nn/layer/fused_transformer.py).

TPU-first design: the model is *functional-first* — parameters live in a
pytree with blocks STACKED along a leading layer axis so the forward is a
``lax.scan`` over layers (one compiled block body instead of L copies: fast
compile, natural per-block remat, and the stacking axis doubles as the
pipeline-stage axis).  An nn.Layer facade wraps the same functions for the
eager API.  Attention routes through the Pallas flash kernel when available.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .ragged import RaggedBatch, RaggedView

__all__ = ["GPTConfig", "gpt_init", "gpt_forward", "gpt_loss",
           "gpt_param_specs", "gpt_ragged_step", "GPT",
           "GPT_CONFIGS"]


@dataclasses.dataclass(unsafe_hash=True)
class GPTConfig:
    vocab_size: int = 50304          # multiple of 128 for MXU/TP tiling
    max_seq_len: int = 1024
    hidden: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: int = 3072
    dropout: float = 0.0
    dtype: str = "bfloat16"
    use_flash: bool = True
    remat: str = "dots"              # per-block checkpoint policy
    tie_embeddings: bool = True
    # sequence parallelism flavor when the engine's sep axis > 1:
    #   "ulysses" — all_to_all head-scatter (caps sep at local head count)
    #   "ring"    — ring attention, KV blocks rotate on ICI (no head cap;
    #               needs S/sep % 128 == 0 for the pallas tiles)
    seq_parallel: str = "ulysses"
    # MoE (Mixtral-style): >0 replaces every block's dense FFN with a
    # moe_experts-expert MoE of the same per-expert hidden (ffn_hidden)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self):
        return self.hidden // self.num_heads

    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


GPT_CONFIGS = {
    # the reference's benchmark family (BASELINE.json); gpt3-1.3b and
    # gpt2-medium are the configurations of BENCHMARK.json's GPT cells
    "gpt2-small": GPTConfig(hidden=768, num_layers=12, num_heads=12,
                            ffn_hidden=3072),
    "gpt2-medium": GPTConfig(hidden=1024, num_layers=24, num_heads=16,
                             ffn_hidden=4096),
    "gpt2-large": GPTConfig(hidden=1280, num_layers=36, num_heads=20,
                            ffn_hidden=5120),
    "gpt3-1.3b": GPTConfig(hidden=2048, num_layers=24, num_heads=16,
                           ffn_hidden=8192, max_seq_len=2048),
    "gpt3-6.7b": GPTConfig(hidden=4096, num_layers=32, num_heads=32,
                           ffn_hidden=16384, max_seq_len=2048),
    "tiny": GPTConfig(vocab_size=1024, max_seq_len=128, hidden=128,
                      num_layers=4, num_heads=4, ffn_hidden=512),
}


# ------------------------------------------------------------------ params


def gpt_init(cfg: GPTConfig, key=None, dtype=None):
    """Initialize the parameter pytree.  Block params are stacked on axis 0
    (shape [L, ...]) for scan/pipeline use."""
    key = key if key is not None else jax.random.key(0)
    dt = dtype or cfg.jdtype()
    D, F, L, V = cfg.hidden, cfg.ffn_hidden, cfg.num_layers, cfg.vocab_size
    k = iter(jax.random.split(key, 16))

    def init(key_, shape, std=0.02):
        return (jax.random.normal(key_, shape, jnp.float32) * std).astype(dt)

    resid_std = 0.02 / math.sqrt(2 * L)
    params = {
        "wte": init(next(k), (V, D)),
        "wpe": init(next(k), (cfg.max_seq_len, D), 0.01),
        "blocks": {
            "ln1_g": jnp.ones((L, D), dt), "ln1_b": jnp.zeros((L, D), dt),
            "qkv_w": init(next(k), (L, D, 3 * D)),
            "qkv_b": jnp.zeros((L, 3 * D), dt),
            "proj_w": init(next(k), (L, D, D), resid_std),
            "proj_b": jnp.zeros((L, D), dt),
            "ln2_g": jnp.ones((L, D), dt), "ln2_b": jnp.zeros((L, D), dt),
        },
        "lnf_g": jnp.ones((D,), dt), "lnf_b": jnp.zeros((D,), dt),
    }
    E = cfg.moe_experts
    if E:
        params["blocks"].update({
            # gate in fp32: routing decisions are precision-sensitive
            "gate_w": (jax.random.normal(next(k), (L, D, E), jnp.float32)
                       * 0.02),
            "up_w": init(next(k), (L, E, D, F)),
            "up_b": jnp.zeros((L, E, F), dt),
            "down_w": init(next(k), (L, E, F, D), resid_std),
            "down_b": jnp.zeros((L, E, D), dt),
        })
    else:
        params["blocks"].update({
            "up_w": init(next(k), (L, D, F)),
            "up_b": jnp.zeros((L, F), dt),
            "down_w": init(next(k), (L, F, D), resid_std),
            "down_b": jnp.zeros((L, D), dt),
        })
    if not cfg.tie_embeddings:
        params["lm_head"] = init(next(k), (D, V))
    return params


def gpt_param_specs(cfg: GPTConfig, zero_stage=0):
    """PartitionSpecs per param — the TP/ZeRO sharding plan.

    mp: Megatron-style column/row split per block (qkv/up are column-split,
    proj/down row-split → one psum per residual write, inserted by GSPMD).
    Embedding is vocab-sharded over mp.  zero_stage>=3 additionally shards
    the remaining replicated dim over 'sharding' (param ZeRO); stages 1/2
    shard only optimizer state (see engine.make_opt_specs).
    """
    z = "sharding" if zero_stage >= 3 else None
    specs = {
        "wte": P("mp", z),
        "wpe": P(None, None),
        "blocks": {
            "ln1_g": P(None, None), "ln1_b": P(None, None),
            "qkv_w": P(None, z, "mp"), "qkv_b": P(None, "mp"),
            "proj_w": P(None, "mp", z), "proj_b": P(None, None),
            "ln2_g": P(None, None), "ln2_b": P(None, None),
        },
        "lnf_g": P(None), "lnf_b": P(None),
    }
    if cfg.moe_experts:
        specs["blocks"].update({
            "gate_w": P(None, None, None),
            "up_w": P(None, "ep", z, None), "up_b": P(None, "ep", None),
            "down_w": P(None, "ep", z, None), "down_b": P(None, "ep", None),
        })
    else:
        specs["blocks"].update({
            "up_w": P(None, z, "mp"), "up_b": P(None, "mp"),
            "down_w": P(None, "mp", z), "down_b": P(None, None),
        })
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(z, "mp")
    return specs


# ----------------------------------------------------------------- forward


def _layer_norm(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _dropout(x, rate, key):
    """Inverted dropout; identity when rate==0 or key is None (eval)."""
    if rate <= 0.0 or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def gpt_block(cfg: GPTConfig, bp, x, dropout_key=None, return_kv=False):
    """One transformer block: pre-LN attention + MLP (dense or MoE).
    Returns (x, aux) where aux is the MoE load-balance loss (0 for dense).
    bp holds this layer's slice of the stacked block params.  dropout_key
    enables residual dropout (reference: resid_pdrop on the attention
    projection and the FFN output).  return_kv=True additionally returns
    this layer's k/v as [B, S, H, hd] (token-major — the page layout the
    serving KV cache stores) for prefill cache population."""
    B, S, D = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    k_attn = k_ffn = None
    if dropout_key is not None and cfg.dropout > 0.0:
        k_attn, k_ffn = jax.random.split(dropout_key)

    with jax.named_scope("attn"):
        h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"])
        qkv = jnp.einsum("bsd,de->bse", h, bp["qkv_w"]) + bp["qkv_b"]
        # qkv columns are head-major [H, 3, hd] so a TP shard of the
        # columns is a whole group of heads (keeps engine.py mp splits
        # layout-compatible)
        qkv = qkv.reshape(B, S, H, 3, hd)
        k_tm, v_tm = qkv[:, :, :, 1], qkv[:, :, :, 2]  # token-major [B,S,H,hd]
        q = qkv[:, :, :, 0].transpose(0, 2, 1, 3)
        k = k_tm.transpose(0, 2, 1, 3)
        v = v_tm.transpose(0, 2, 1, 3)

        from ..kernels.flash_attention import (flash_attention,
                                               flash_attention_available)

        if cfg.use_flash and flash_attention_available(q, k, v, None,
                                                       causal=True):
            attn_out = flash_attention(q, k, v, causal=True)
        else:
            from ..ops.attention import _naive_attention

            attn_out = _naive_attention(q, k, v, causal=True,
                                        training=False)
        attn_out = attn_out.transpose(0, 2, 1, 3).reshape(B, S, D)
        proj = jnp.einsum("bsd,de->bse", attn_out, bp["proj_w"]) \
            + bp["proj_b"]
        x = x + _dropout(proj, cfg.dropout, k_attn)

    with jax.named_scope("mlp"):
        out, aux = _block_mlp(cfg, bp, x, k_ffn)
    return (out, aux, k_tm, v_tm) if return_kv else (out, aux)


def _block_mlp(cfg: GPTConfig, bp, x, k_ffn):
    """The block's second half: pre-LN FFN (dense or MoE) and its
    residual.  Returns (x, aux)."""
    h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"])
    if cfg.moe_experts:
        from ..distributed.moe import moe_layer

        y, aux = moe_layer(
            {"gate_w": bp["gate_w"], "up_w": bp["up_w"], "up_b": bp["up_b"],
             "down_w": bp["down_w"], "down_b": bp["down_b"]},
            h, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor)
        return x + _dropout(y, cfg.dropout, k_ffn), aux
    h = jnp.einsum("bsd,df->bsf", h, bp["up_w"]) + bp["up_b"]
    h = jax.nn.gelu(h, approximate=True)
    h = jnp.einsum("bsf,fd->bsd", h, bp["down_w"]) + bp["down_b"]
    return x + _dropout(h, cfg.dropout, k_ffn), jnp.zeros((), jnp.float32)


def gpt_forward(cfg: GPTConfig, params, tokens, *, blocks=None,
                return_aux=False, dropout_key=None):
    """tokens [B, S] → logits [B, S, V].  Blocks run under lax.scan with
    per-block remat (cfg.remat policy).  return_aux=True also returns the
    summed MoE load-balance loss.  dropout_key (training only) drives
    embedding + residual dropout; remat replays the same key, so the
    backward recompute sees identical masks (the reference preserves RNG
    state across recompute the same way, recompute.py:331)."""
    B, S = tokens.shape
    x = jnp.take(params["wte"], tokens, axis=0) + params["wpe"][:S]
    x = x.astype(cfg.jdtype())
    if dropout_key is not None and cfg.dropout > 0.0:
        emb_key, layers_key = jax.random.split(jax.random.fold_in(
            dropout_key, 0))
        x = _dropout(x, cfg.dropout, emb_key)
    else:
        layers_key = None

    block_params = blocks if blocks is not None else params["blocks"]
    L = jax.tree_util.tree_leaves(block_params)[0].shape[0]

    def body(carry, xs):
        x, aux_sum = carry
        bp, i = xs
        k = (jax.random.fold_in(layers_key, i)
             if layers_key is not None else None)
        x, aux = _rematted_block(cfg)(bp, x, k)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (block_params, jnp.arange(L)))
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["wte"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return (logits, aux_sum) if return_aux else logits


@functools.lru_cache(maxsize=None)
def _rematted_block(cfg: GPTConfig):
    from ..distributed.recompute import checkpoint_policy

    fn = lambda bp, x, k=None: gpt_block(cfg, bp, x, dropout_key=k)
    if cfg.remat == "nothing":
        return fn
    return jax.checkpoint(fn, policy=checkpoint_policy(cfg.remat),
                          prevent_cse=False)


def gpt_loss(cfg: GPTConfig, params, tokens, labels=None, dropout_key=None):
    """Next-token cross entropy in fp32 (the reference's
    softmax_with_cross_entropy numerics)."""
    if labels is None:
        labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    logits, aux = gpt_forward(cfg, params, tokens, return_aux=True,
                              dropout_key=dropout_key)
    with jax.named_scope("ce_head"):
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        safe = jnp.maximum(labels, 0)
        picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        mask = (labels != -100).astype(jnp.float32)
        ce = -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    if cfg.moe_experts:
        # per-layer mean aux (sum over layers / L keeps the weight's scale
        # independent of depth, matching the engine's normalization)
        ce = ce + cfg.moe_aux_weight * aux / cfg.num_layers
    return ce


# ----------------------------------------------- KV-cache ragged step
#
# The serving engine (paddle_tpu/serving) generates autoregressively with a
# block-paged KV cache instead of full-sequence recompute.  ONE entry
# point with STATIC shapes, so the whole engine compiles exactly once:
#
#   gpt_ragged_step — a packed batch of query tokens where every row is
#                     at an arbitrary point in its life: a mid-prefill
#                     prompt chunk, or a decode step (the query_len == 1
#                     chunk).  Appends each token's K/V to the pages and
#                     attends via the ragged paged-attention kernel.
#
# This is what kills the prefill/decode phase split: a prompt is N
# bounded-size chunk rows interleaved with decode rows, not one
# batch-stalling full-sequence pass.  Pages are stacked
# [L, P, page_size, H, hd] and the layer loop is a lax.scan over
# (blocks, layer index) that CARRIES both pools: a layer scatters its
# tokens' K/V into [layer, page, slot] and the kernel reads [layer, page]
# blocks, so the donated pools stay where they are from entry to return.
# Carried, not xs/ys: as xs/ys XLA slices every layer's pages out,
# restacks them and copies both pools, whole, every step.  The kernel's
# work list depends on the rows' lengths alone: it is built once, outside
# the scan, and handed to every layer's call.


def gpt_ragged_step(cfg: GPTConfig, params, batch: RaggedBatch, k_pages,
                    v_pages, *, max_q=None, attn_path=None, mesh=None):
    """Unified ragged step over the paged KV cache — the serving
    engine's single jitted program for both prompt chunks and decode.

    ``batch`` is the scheduler's ``RaggedBatch``; its packing contract is
    written in ``models/ragged.py``.  ``max_q`` (static) bounds any
    single row's chunk — the padded query width handed to the attention
    kernel.  ``attn_path`` (static) is handed to
    ``ragged_paged_attention`` as its ``path``: ``None`` lets
    ``kernels.dispatch`` pick from the platform.  ``mesh`` (static) is
    the serving mesh when params and pages are sharded over its "mp"
    axis: attention then runs under a shard_map over the head axis —
    heads are independent, and GSPMD cannot partition a Mosaic kernel
    by itself ("wrap the call in a shard_map").

    Compute is flat [T, D] (a decode row costs one token, not a padded
    chunk); only the attention kernel sees a per-row padded [B, max_q]
    view.  Returns (logits [B, V] at each row's last packed token — the
    next-token distribution for a decode row or a prompt-completing
    chunk; rows with query_len 0 return garbage the engine ignores —
    k_pages, v_pages)."""
    tokens, query_lens, context_lens, page_tables = (
        batch.tokens, batch.query_lens, batch.context_lens,
        batch.page_tables)
    T = tokens.shape[0]
    H, hd, D = cfg.num_heads, cfg.head_dim, cfg.hidden
    page_size = k_pages.shape[2]
    view = RaggedView(batch, max_q=max_q, max_seq_len=cfg.max_seq_len,
                      num_pages=k_pages.shape[1], page_size=page_size)

    with jax.named_scope("embed"):
        x = jnp.take(params["wte"], tokens, axis=0) + \
            jnp.take(params["wpe"], view.pos, axis=0)
        x = x.astype(cfg.jdtype())                                 # [T, D]

    from ..kernels.paged_attention import (ragged_paged_attention,
                                           ragged_work_items)

    with jax.named_scope("work_list"):
        items = ragged_work_items(query_lens, context_lens, page_size,
                                  page_tables.shape[1])

    def attend(q, kp, vp, tables, q_lens, ctx_lens, layer, items):
        return ragged_paged_attention(q, kp, vp, tables, q_lens, ctx_lens,
                                      path=attn_path, layer=layer,
                                      items=items)

    if mesh is not None:
        # heads that do not divide stay whole on every shard, as
        # mesh.resolve_spec leaves the page pool
        mp = "mp" if H % mesh.shape["mp"] == 0 else None
        heads = jax.sharding.PartitionSpec(None, None, mp, None)
        pool = jax.sharding.PartitionSpec(None, None, None, mp, None)
        rep = jax.sharding.PartitionSpec()
        attend = jax.shard_map(
            attend, mesh=mesh,
            in_specs=(heads, pool, pool, rep, rep, rep, rep, rep),
            out_specs=heads, check_vma=False)

    def body(carry, xs):
        x, kp, vp = carry
        bp, layer = xs
        with jax.named_scope("attn"):
            h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"])
            qkv = jnp.einsum("td,de->te", h, bp["qkv_w"]) + bp["qkv_b"]
            qkv = qkv.reshape(T, H, 3, hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [T, H, hd]
            with jax.named_scope("kv_write"):
                # a masked token's page is out of bounds, which
                # mode="drop" discards
                kp = kp.at[layer, view.page, view.slot_in_page].set(
                    k.astype(kp.dtype), mode="drop")
                vp = vp.at[layer, view.page, view.slot_in_page].set(
                    v.astype(vp.dtype), mode="drop")
            # the kernel wants one padded row of queries per request
            attn = attend(view.pad(q), kp, vp, page_tables, query_lens,
                          context_lens, layer, items)
            attn = view.unpad(attn).reshape(T, D).astype(x.dtype)
            x = x + jnp.einsum("td,de->te", attn, bp["proj_w"]) \
                + bp["proj_b"]

        with jax.named_scope("mlp"):
            h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"])
            if cfg.moe_experts:
                from ..distributed.moe import moe_layer

                y, _ = moe_layer(
                    {"gate_w": bp["gate_w"], "up_w": bp["up_w"],
                     "up_b": bp["up_b"], "down_w": bp["down_w"],
                     "down_b": bp["down_b"]},
                    h[None], top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor)
                return (x + y[0], kp, vp), None
            h = jnp.einsum("td,df->tf", h, bp["up_w"]) + bp["up_b"]
            h = jax.nn.gelu(h, approximate=True)
            h = jnp.einsum("tf,fd->td", h, bp["down_w"]) + bp["down_b"]
            return (x + h, kp, vp), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, k_pages, v_pages),
        (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    with jax.named_scope("lm_head"):
        x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
        x_last = view.last(x)                                      # [B, D]
        if cfg.tie_embeddings:
            logits = jnp.einsum("bd,vd->bv", x_last, params["wte"])
        else:
            logits = jnp.einsum("bd,dv->bv", x_last, params["lm_head"])
    return logits, k_pages, v_pages


def gpt_num_params(cfg: GPTConfig):
    D, F, L, V = cfg.hidden, cfg.ffn_hidden, cfg.num_layers, cfg.vocab_size
    attn_part = 4 * D + D * 3 * D + 3 * D + D * D + D
    if cfg.moe_experts:
        E = cfg.moe_experts
        ffn_part = D * E + E * (D * F + F + F * D + D)
    else:
        ffn_part = D * F + F + F * D + D
    n = V * D + cfg.max_seq_len * D + L * (attn_part + ffn_part) + 2 * D
    if not cfg.tie_embeddings:
        n += D * V
    return n


def gpt_flops_per_token(cfg: GPTConfig, seq_len):
    """Training FLOPs/token ≈ 6*N + attention term (per Chinchilla appendix)."""
    n = gpt_num_params(cfg)
    attn = 6 * cfg.num_layers * cfg.hidden * seq_len  # fwd+bwd qk/av matmuls
    return 6 * n + 2 * attn


# ------------------------------------------------------------ Layer facade


from ..core.tensor import Parameter, Tensor  # noqa: E402
from ..nn.layer.layers import Layer  # noqa: E402


class GPT(Layer):
    """Eager facade over the functional model (single-chip / small-scale)."""

    def __init__(self, config: GPTConfig = None, **kwargs):
        super().__init__()
        if config is None:
            config = GPTConfig(**kwargs)
        self.config = config
        from ..core.random import split_key

        raw = gpt_init(config, key=split_key())
        flat, self._treedef = jax.tree_util.tree_flatten(raw)
        self._paths = [
            "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(raw)[0]
        ]
        for name, arr in zip(self._paths, flat):
            self.register_parameter(name.replace("/", "_"), Parameter(arr))

    def _params_tree(self):
        flat = [self._parameters[n.replace("/", "_")].data for n in self._paths]
        return jax.tree_util.tree_unflatten(self._treedef, flat)

    def forward(self, tokens, labels=None):
        from ..core import dispatch

        tokens_arr = tokens.data if isinstance(tokens, Tensor) else tokens
        bundle = {n.replace("/", "_"): self._parameters[n.replace("/", "_")]
                  for n in self._paths}

        def pure(bundle_arrs, tok):
            flat = [bundle_arrs[n.replace("/", "_")] for n in self._paths]
            params = jax.tree_util.tree_unflatten(self._treedef, flat)
            if labels is None:
                return gpt_forward(self.config, params, tok)
            lab = labels.data if isinstance(labels, Tensor) else labels
            return gpt_loss(self.config, params, tok, lab)

        return dispatch._eager_run("gpt_forward", pure, True,
                                   (bundle, tokens_arr), {})

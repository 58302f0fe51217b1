"""Attention ops.

Parity: the reference's fused attention stack
(paddle/fluid/operators/fused/fused_attention_op.cu,
fused_multi_transformer_op.cu) — rebuilt TPU-first: the hot path is a Pallas
flash-attention kernel (paddle_tpu/kernels/flash_attention.py); the reference
semantics (naive softmax(QK^T)V) remain as the XLA fallback that also serves
CPU tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.dispatch import register_op


def _naive_attention(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None,
                     training=True, key=None):
    # q,k,v: [batch, heads, seq, head_dim]
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    from .linalg import mxu_precision

    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32,
        precision=mxu_precision(q, k)
    ) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(causal_mask, logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and training:
        if key is None:
            from ..core.random import split_key

            key = split_key()
        keep = 1.0 - dropout_p
        drop_mask = jax.random.bernoulli(key, p=keep, shape=probs.shape)
        probs = jnp.where(drop_mask, probs / keep, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                      precision=mxu_precision(probs, v))


@register_op("scaled_dot_product_attention")
def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, scale=None, training=True,
                                 use_flash=True):
    """q/k/v: [batch, heads, seq, head_dim].

    Dispatches to the Pallas flash-attention kernel on TPU when shapes allow,
    else the XLA softmax path (which XLA still fuses well).  Attention
    dropout forces the naive path (the flash kernel is dropout-free, like the
    reference's fused_attention fast path).
    """
    if use_flash and (dropout_p == 0.0 or not training):
        from ..kernels.flash_attention import (flash_attention,
                                               flash_attention_available)

        if flash_attention_available(q, k, v, attn_mask, causal=is_causal):
            return flash_attention(q, k, v, causal=is_causal, scale=scale)
    return _naive_attention(q, k, v, mask=attn_mask, dropout_p=dropout_p,
                            causal=is_causal, scale=scale, training=training)

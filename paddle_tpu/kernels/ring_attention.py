"""Ring attention — sequence-parallel flash attention over a mesh axis.

SURVEY.md §5.7/§7.6: the reference has NO sequence/context parallelism;
this is the required new capability.  Design (Ring Attention with Blockwise
Transformers, public technique): each "sep" rank holds a sequence shard of
Q/K/V ([B, H, S/sep, hd]); K/V blocks rotate around the ring via
``ppermute`` while each rank folds the visiting block into its local
online-softmax state.  Per-pair math runs the Pallas flash kernels
(kernels/flash_attention.py); partial results merge by logsumexp.  Unlike
Ulysses (all_to_all head-scatter, engine._attention), the head count does
NOT bound the parallelism degree — only S/sep must stay tile-aligned.

Causality across shards is block-triangular: a visiting KV block j against
local Q block i needs full attention when j < i, causal-within when j == i,
and nothing when j > i (skipped via lax.cond; the predicate varies only
over 'sep' and the branches contain no collectives, so SPMD stays safe).

Backward (flash-2 style, second ring pass): dQ accumulates locally per
visiting block; dK/dV contributions ride the ring alongside the K/V blocks
and arrive home after a full rotation.  p_ij is recomputed from the saved
FINAL logsumexp, so per-pair backward reuses the flash bwd kernels as-is.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.vma import lifter as _vma_lift  # branch outputs must share vma
from . import dispatch
from .flash_attention import _fit_blocks, _flash_bwd, _flash_fwd

__all__ = ["ring_attention"]


def _causal_mask(S):
    i = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    return i >= j


def _pair_fwd_ref(q, k, v, scale, causal):
    """jnp reference of one pair's flash forward → (out, lse)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        s = jnp.where(_causal_mask(q.shape[2]), s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    lse = m + jnp.log(l)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / l[..., None],
                     v.astype(jnp.float32))
    return out, lse


def _pair_bwd_ref(q, k, v, do, lse, delta, scale, causal):
    """jnp reference of the per-pair backward with global lse/delta."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        s = jnp.where(_causal_mask(q.shape[2]), s, -1e30)
    p = jnp.exp(s - lse[..., None])
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq, dk, dv


def _pair_fwd(q, k, v, scale, causal, block_q, block_kv, mosaic):
    """One (Q-shard, KV-block) flash forward → (out, lse)."""
    if mosaic:
        return _flash_fwd(q, k, v, scale, causal, block_q, block_kv, False)
    return _pair_fwd_ref(q, k, v, scale, causal)


def _pair_bwd(q, k, v, do, lse, delta, scale, causal, block_q, block_kv,
              mosaic):
    """Per-pair backward with the GLOBAL lse/delta: returns (dq, dk, dv)
    in fp32.  The flash kernels' p = exp(s - lse) is exactly the
    ring-global softmax weight when lse is the final merged value."""
    if mosaic:
        return _flash_bwd(q, k, v, do, lse, delta, scale, causal, block_q,
                          block_kv, False, jnp.float32)
    return _pair_bwd_ref(q, k, v, do, lse, delta, scale, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring(q, k, v, axis_name, scale, block_q, block_kv, mosaic):
    out, _ = _ring_fwd_impl(q, k, v, axis_name, scale, block_q, block_kv,
                            mosaic)
    return out


def _ring_fwd_impl(q, k, v, axis_name, scale, block_q, block_kv, mosaic):
    sep = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    fwd_perm = [(i, (i + 1) % sep) for i in range(sep)]
    neg = jnp.float32(-1e30)
    lift = _vma_lift(q, k, v)

    def step(carry, r):
        k_cur, v_cur, acc, lse_acc = carry
        j = (my - r) % sep

        def full_pair(args):
            kk, vv = args
            o, l = _pair_fwd(q, kk, vv, scale, False, block_q, block_kv,
                             mosaic)
            return lift(o.astype(jnp.float32)), lift(l)

        def causal_pair(args):
            kk, vv = args
            o, l = _pair_fwd(q, kk, vv, scale, True, block_q, block_kv,
                             mosaic)
            return lift(o.astype(jnp.float32)), lift(l)

        def skip_pair(args):
            return (lift(jnp.zeros(q.shape, jnp.float32)),
                    lift(jnp.full(q.shape[:3], neg, jnp.float32)))

        case = jnp.where(j < my, 0, jnp.where(j == my, 1, 2))
        o, l = jax.lax.switch(case, [full_pair, causal_pair, skip_pair],
                              (k_cur, v_cur))
        # logsumexp merge of the running state with this block's partial
        lse_new = jnp.logaddexp(lse_acc, l)
        w_acc = jnp.exp(lse_acc - lse_new)[..., None]
        w_new = jnp.exp(l - lse_new)[..., None]
        acc = acc * w_acc + o * w_new
        k_nxt = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, fwd_perm)
        return (k_nxt, v_nxt, acc, lse_new), None

    acc0 = lift(jnp.zeros(q.shape, jnp.float32))
    lse0 = lift(jnp.full(q.shape[:3], neg, jnp.float32))
    (k_back, v_back, acc, lse), _ = jax.lax.scan(
        step, (k, v, acc0, lse0), jnp.arange(sep))
    # fully-masked rows (none exist under causal ring, but guard anyway)
    out = acc.astype(q.dtype)
    return out, lse


def _ring_fwd_rule(q, k, v, axis_name, scale, block_q, block_kv, mosaic):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, scale, block_q, block_kv,
                              mosaic)
    return out, (q, k, v, out, lse)


def _ring_bwd_rule(axis_name, scale, block_q, block_kv, mosaic, res, g):
    q, k, v, out, lse = res
    sep = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    fwd_perm = [(i, (i + 1) % sep) for i in range(sep)]
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)  # [B,H,s]
    lift = _vma_lift(q, k, v, g)

    def step(carry, r):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
        j = (my - r) % sep

        def full_pair(args):
            kk, vv = args
            r_ = _pair_bwd(q, kk, vv, do, lse, delta, scale, False,
                           block_q, block_kv, mosaic)
            return tuple(lift(t) for t in r_)

        def causal_pair(args):
            kk, vv = args
            r_ = _pair_bwd(q, kk, vv, do, lse, delta, scale, True,
                           block_q, block_kv, mosaic)
            return tuple(lift(t) for t in r_)

        def skip_pair(args):
            kk, vv = args
            return (lift(jnp.zeros(q.shape, jnp.float32)),
                    lift(jnp.zeros(kk.shape, jnp.float32)),
                    lift(jnp.zeros(vv.shape, jnp.float32)))

        case = jnp.where(j < my, 0, jnp.where(j == my, 1, 2))
        dq_i, dk_i, dv_i = jax.lax.switch(
            case, [full_pair, causal_pair, skip_pair], (k_cur, v_cur))
        dq_acc = dq_acc + dq_i
        dk_cur = dk_cur + dk_i
        dv_cur = dv_cur + dv_i
        # rotate KV and their accumulating grads together: after sep hops
        # each block (and its dk/dv) is home with every rank's contribution
        k_nxt = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, fwd_perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, fwd_perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, fwd_perm)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_acc), None

    zeros_kv = lift(jnp.zeros(k.shape, jnp.float32))
    (k_b, v_b, dk, dv, dq), _ = jax.lax.scan(
        step,
        (k, v, zeros_kv, zeros_kv, lift(jnp.zeros(q.shape, jnp.float32))),
        jnp.arange(sep))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(q, k, v, axis_name, causal=True, scale=None,
                   block_q=512, block_kv=1024, path=None):
    """Sequence-parallel causal attention over mesh axis ``axis_name``.

    q/k/v: [B, H, S_local, hd] — the LOCAL sequence shard (global S =
    S_local * axis_size, contiguous blocks in rank order).  Must run
    inside shard_map with ``axis_name`` mapped.  S_local must be a
    multiple of 128 (TPU tile).  Only causal=True is supported (the
    non-causal case is just flash over an all_gather'd sequence).
    ``path`` is ``dispatch.MOSAIC`` (the flash kernels per pair; the
    default on a TPU) or ``dispatch.REFERENCE`` (a jnp pair, elsewhere).
    There is no interpreted path: the Pallas HLO interpreter's
    dynamic_slice fails shard_map's check_vma on sep-varying operands.
    """
    path = dispatch.resolve_path(
        path, off_tpu=dispatch.REFERENCE,
        allowed=(dispatch.MOSAIC, dispatch.REFERENCE))
    if not causal:
        raise NotImplementedError(
            "ring_attention is causal-only; for non-causal, all_gather the "
            "sequence and use flash_attention")
    S = q.shape[2]
    if S % 128 != 0:
        raise ValueError(f"ring_attention needs S_local % 128 == 0, got {S}")
    bq, bkv = _fit_blocks(S, block_q, block_kv)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _ring(q, k, v, axis_name, scale, bq, bkv, path == dispatch.MOSAIC)

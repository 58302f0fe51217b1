"""Flash attention — Pallas TPU kernel with custom VJP.

The reference's fused_attention_op.cu / fused_multi_transformer_op.cu keep
softmax(QK^T)V in registers/SMEM; the TPU equivalent streams K/V blocks
through VMEM with the online-softmax recurrence so the [S,S] score matrix
never hits HBM.  Forward saves per-row logsumexp; backward recomputes block
scores (flash-2 style) with two kernels (dKdV sweep, dQ sweep).

Grid note: TPU pallas grids execute sequentially on a core with the LAST
axis innermost — the kv-block axis is last so VMEM scratch carries the
online-softmax state across kv steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

__all__ = ["flash_attention", "flash_attention_available"]

_NEG_INF = -1e30


def flash_attention_available(q, k, v, mask, causal=False):
    """Whether the kernel covers these operands (shape and mask only —
    the platform never makes it unavailable)."""
    if mask is not None:
        return False
    if q.ndim != 4 or q.shape != k.shape or q.shape != v.shape:
        return False
    B, H, S, D = q.shape
    if D > 256:
        return False
    if S % 128 != 0 and not causal:
        # non-128-multiple S is only supported via the causal pad path
        return False
    return True


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_kv,
                num_kv):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    should_run = True
    if causal:
        should_run = kj * block_kv <= qi * block_q + block_q - 1

    @pl.when(should_run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                 # [bq, D]
        k = k_ref[0].astype(jnp.float32)                 # [bkv, D]
        v = v_ref[0].astype(jnp.float32)                 # [bkv, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:]                                 # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == num_kv - 1)
    def _final():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the ``like`` arrays' vma so
    the pallas_call type-checks under shard_map(check_vma=True): the kernel
    is elementwise in the device dimension, so outputs vary over every mesh
    axis any input does (pallas does not validate this itself — an
    under-declared vma would silently drop AD's psums downstream)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _flash_fwd(q, k, v, scale, causal, block_q, block_kv, interpret):
    B, H, S, D = q.shape
    bh = B * H
    qf = q.reshape(bh, S, D)
    kf = k.reshape(bh, S, D)
    vf = v.reshape(bh, S, D)
    num_q = S // block_q
    num_kv = S // block_kv

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=num_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, S, D), q.dtype, qf, kf, vf),
            _sds((bh, S, 1), jnp.float32, qf, kf, vf),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(B, H, S, D), lse[..., 0].reshape(B, H, S)


# ----------------------------------------------------------------- backward


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc,
                     *, scale, causal, block_q, block_kv, num_q):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    should_run = True
    if causal:
        should_run = kj * block_kv <= qi * block_q + block_q - 1

    @pl.when(should_run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                    # [bq, 1]
        delta = delta_ref[0]                                # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                                # [bq, bkv]
        # dv += p^T dO
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _final():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, causal, block_q, block_kv, num_kv):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    should_run = True
    if causal:
        should_run = kj * block_kv <= qi * block_q + block_q - 1

    @pl.when(should_run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                    # [bq, 1]
        delta = delta_ref[0]                                # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == num_kv - 1)
    def _final():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, do, lse, delta, scale, causal, block_q, block_kv,
               interpret, out_dtype):
    """The two backward sweeps for q/do [B, H, Sq, D] against k/v
    [B, H, Skv, D] with per-row ``lse``/``delta`` [B, H, Sq] → (dq, dk,
    dv) in ``out_dtype``.  ``lse``/``delta`` are operands so ring
    attention can pass the ring-global values: p = exp(s - lse) is then
    the global softmax weight of this (Q-shard, KV-block) pair."""
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    bh = B * H
    qf, dof = q.reshape(bh, Sq, D), do.reshape(bh, Sq, D)
    kf, vf = k.reshape(bh, Skv, D), v.reshape(bh, Skv, D)
    lsef = lse.reshape(bh, Sq, 1)
    deltaf = delta.reshape(bh, Sq, 1)
    num_q = Sq // block_q
    num_kv = Skv // block_kv

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_q=num_q),
        grid=(bh, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds((bh, Skv, D), out_dtype, qf, kf, vf, dof),
            _sds((bh, Skv, D), out_dtype, qf, kf, vf, dof),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(qf, kf, vf, dof, lsef, deltaf)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, num_kv=num_kv),
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((bh, Sq, D), out_dtype, qf, kf, vf, dof),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Skv, D),
            dv.reshape(B, H, Skv, D))


# -------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_kv, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_kv, interpret)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_kv, interpret):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_kv,
                          interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_kv, interpret, res, g):
    q, k, v, out, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return _flash_bwd(q, k, v, g, lse, delta, scale, causal, block_q,
                      block_kv, interpret, q.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _fit_blocks(S, block_q, block_kv):
    """Largest 128-multiple divisors of S (itself a 128-multiple) under
    the requested block sizes and the 1024 cap."""
    def fit(b):
        b = min(b, S, 1024)
        b -= b % 128       # align to the TPU tile (terminates the search)
        while b > 128 and S % b:
            b -= 128
        return max(b, 128)

    return fit(block_q), fit(block_kv)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=512, block_kv=1024, path=None):
    """q/k/v: [B, H, S, D] → [B, H, S, D].

    ``path`` is ``dispatch.MOSAIC`` or ``dispatch.INTERPRET``; ``None``
    takes Mosaic on a TPU and the interpreter elsewhere.  The default
    blocks (512, 1024) were picked on a v5e before PR 1; their speed
    against XLA's attention is not measured on the current code.
    """
    # no REFERENCE here: callers pick ops.attention._naive_attention
    path = dispatch.resolve_path(
        path, off_tpu=dispatch.INTERPRET,
        allowed=(dispatch.MOSAIC, dispatch.INTERPRET))
    S = q.shape[2]
    if S % 128 != 0:
        # TPU tiling needs S in 128-multiples.  Causal: zero-pad the tail
        # (row i only attends j<=i, so pad rows can't leak into real rows)
        # and slice back.  Non-causal padding would corrupt the softmax
        # (padded keys score exp(0)=1) — reject with a clear error.
        if not causal:
            raise ValueError(
                f"flash_attention requires seq_len % 128 == 0 for "
                f"non-causal attention, got S={S}; pad the sequence or "
                f"gate on flash_attention_available()")
        pad = (-S) % 128
        zpad = [(0, 0), (0, 0), (0, pad), (0, 0)]
        out = flash_attention(jnp.pad(q, zpad), jnp.pad(k, zpad),
                              jnp.pad(v, zpad), causal=causal, scale=scale,
                              block_q=block_q, block_kv=block_kv, path=path)
        return out[:, :, :S]

    block_q, block_kv = _fit_blocks(S, block_q, block_kv)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, scale, causal, block_q, block_kv,
                  path == dispatch.INTERPRET)

"""Flash-attention kernel conformance: forward + backward vs naive XLA path
(the kernel body under the Pallas interpreter, chosen explicitly; that
the same code compiles for the TPU is tests/test_tpu_aot_compile.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import dispatch
from paddle_tpu.ops.attention import _naive_attention

flash_attention = functools.partial(kernels.flash_attention,
                                    path=dispatch.INTERPRET)


def _rand_qkv(B=1, H=2, S=256, D=64, seed=0):
    k = jax.random.key(seed)
    kq, kk, kv = jax.random.split(k, 3)
    q = jax.random.normal(kq, (B, H, S, D), jnp.float32)
    k_ = jax.random.normal(kk, (B, H, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, D), jnp.float32)
    return q, k_, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = _naive_attention(q, k, v, causal=causal, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_naive(causal):
    q, k, v = _rand_qkv(S=256)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def f_naive(q, k, v):
        return jnp.sum(_naive_attention(q, k, v, causal=causal,
                                        training=False) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_bf16_forward():
    q, k, v = _rand_qkv(S=128)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = flash_attention(q, k, v, causal=True)
    ref = _naive_attention(q, k, v, causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)


def test_multiblock_seq():
    q, k, v = _rand_qkv(S=512)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = _naive_attention(q, k, v, causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_ragged_seq_causal_pads():
    # S not a 128-multiple: causal path zero-pads and slices back
    q, k, v = _rand_qkv(S=200)
    out = flash_attention(q, k, v, causal=True)
    ref = _naive_attention(q, k, v, causal=True, training=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_ragged_seq_noncausal_raises():
    q, k, v = _rand_qkv(S=200)
    with pytest.raises(ValueError, match="128"):
        flash_attention(q, k, v, causal=False)

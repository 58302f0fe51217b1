"""Ring attention tests (SURVEY §5.7: the new-capability requirement).

All on the virtual CPU mesh; pallas kernels run in interpret mode there.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.kernels.ring_attention import ring_attention
from paddle_tpu.ops.attention import _naive_attention


def _qkv(B, H, S, D, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(B, H, S, D).astype(np.float32) * 0.5
    return mk(), mk(), mk()


def _ring_run(q, k, v, sep, grad=False):
    """shard_map ring over 'sep' with the sequence split in rank order."""
    mesh = Mesh(np.array(jax.devices()[:sep]), ("sep",))

    def local(q, k, v):
        return ring_attention(q, k, v, "sep", causal=True)

    spec = P(None, None, "sep", None)
    mapped = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                           out_specs=spec, check_vma=True)
    if not grad:
        return jax.jit(mapped)(q, k, v)

    def loss(q, k, v):
        return (mapped(q, k, v).astype(jnp.float32) ** 2).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _ref_run(q, k, v, grad=False):
    ref = lambda q, k, v: _naive_attention(jnp.asarray(q), jnp.asarray(k),
                                           jnp.asarray(v), causal=True,
                                           training=False)
    if not grad:
        return ref(q, k, v)

    def loss(q, k, v):
        return (ref(q, k, v).astype(jnp.float32) ** 2).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))


class TestRingParity:
    def test_fwd_matches_naive_sep4(self):
        q, k, v = _qkv(2, 2, 512, 64)
        out = _ring_run(q, k, v, sep=4)
        ref = _ref_run(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)

    def test_fwd_matches_sep2(self):
        q, k, v = _qkv(1, 2, 256, 64, seed=3)
        out = _ring_run(q, k, v, sep=2)
        ref = _ref_run(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)

    def test_grads_match_naive(self):
        q, k, v = _qkv(1, 2, 256, 64, seed=5)
        dq, dk, dv = _ring_run(q, k, v, sep=2, grad=True)
        rq, rk, rv = _ref_run(q, k, v, grad=True)
        for a, b, name in ((dq, rq, "dq"), (dk, rk, "dk"), (dv, rv, "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-2, rtol=1e-2, err_msg=name)

    def test_seq4096_parity(self):
        """VERDICT r2 #6 'done' criterion: seq 4096, sep=4, interpret mode."""
        q, k, v = _qkv(1, 1, 4096, 64, seed=7)
        out = _ring_run(q, k, v, sep=4)
        ref = _ref_run(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)

    @pytest.mark.parametrize("causal", [True, False])
    def test_pair_backward_kernels_match_ref(self, causal):
        """The ring's per-pair backward is flash's two sweeps fed the
        ring-global lse/delta, an fp32 cotangent and fp32 outputs.  Under
        shard_map only Mosaic can run them (no interpreted ring path), so
        they are checked here, interpreted, against the jnp pair."""
        from paddle_tpu.kernels.flash_attention import _flash_bwd, _flash_fwd
        from paddle_tpu.kernels.ring_attention import _pair_bwd_ref

        q, k, v = (jnp.asarray(t) for t in _qkv(1, 2, 256, 64, seed=11))
        do = jnp.asarray(_qkv(1, 2, 256, 64, seed=12)[0])
        scale = 1.0 / np.sqrt(q.shape[-1])
        out, lse = _flash_fwd(q, k, v, scale, causal, 128, 128, True)
        delta = jnp.sum(do * out, axis=-1)
        got = _flash_bwd(q, k, v, do, lse, delta, scale, causal, 128, 128,
                         True, jnp.float32)
        want = _pair_bwd_ref(q, k, v, do, lse, delta, scale, causal)
        for a, b, name in zip(got, want, ("dq", "dk", "dv")):
            assert a.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4, err_msg=name)

    def test_s_local_tile_check(self):
        q, k, v = _qkv(1, 1, 256, 64)
        mesh = Mesh(np.array(jax.devices()[:2]), ("sep",))
        with pytest.raises(ValueError, match="128"):
            jax.shard_map(
                lambda q, k, v: ring_attention(q[:, :, :100], k[:, :, :100],
                                               v[:, :, :100], "sep"),
                mesh=mesh, in_specs=(P(None, None, "sep", None),) * 3,
                out_specs=P(None, None, "sep", None), check_vma=True,
            )(q, k, v)


@pytest.mark.slow
class TestEngineRing:
    """sep=4 ring beats the Ulysses head cap: num_heads=2 < sep=4."""

    def test_ring_lifts_head_cap_and_matches(self):
        from paddle_tpu.distributed.engine import EngineConfig, HybridEngine
        from paddle_tpu.models.gpt import GPTConfig

        cfg = dict(vocab_size=256, max_seq_len=512, hidden=128,
                   num_layers=2, num_heads=2, ffn_hidden=256,
                   dtype="float32", use_flash=False, remat="nothing")
        rng = np.random.RandomState(0)
        tokens = rng.randint(0, 256, (2, 512)).astype(np.int32)
        labels = np.concatenate([tokens[:, 1:], np.full((2, 1), -100)],
                                axis=1).astype(np.int32)

        base = HybridEngine(GPTConfig(**cfg), devices=jax.devices()[:1])
        bp, bo = base.init(seed=0)
        base_losses = []
        for _ in range(2):
            bp, bo, l = base.step(bp, bo, tokens, labels, lr=1e-3)
            base_losses.append(float(l))

        # Ulysses would assert here: heads(2) % sep(4) != 0
        ring = HybridEngine(GPTConfig(**cfg, seq_parallel="ring"), sep=4,
                            devices=jax.devices()[:4])
        rp, ro = ring.init(seed=0)
        ring_losses = []
        for _ in range(2):
            rp, ro, l = ring.step(rp, ro, tokens, labels, lr=1e-3)
            ring_losses.append(float(l))
        np.testing.assert_allclose(ring_losses, base_losses, atol=5e-4,
                                   rtol=1e-4)

    def test_ulysses_head_cap_still_asserts(self):
        from paddle_tpu.distributed.engine import HybridEngine
        from paddle_tpu.models.gpt import GPTConfig

        cfg = GPTConfig(vocab_size=256, max_seq_len=512, hidden=128,
                        num_layers=2, num_heads=2, ffn_hidden=256,
                        dtype="float32")
        with pytest.raises(AssertionError, match="ring"):
            HybridEngine(cfg, sep=4, devices=jax.devices()[:4])

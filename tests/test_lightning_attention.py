"""``lightning_attention``: the Pallas kernel (under the interpreter) and
the chunked algebra inside it against the recurrence written as a scan,
on ragged rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.lightning_attention import (lightning_attention,
                                                    lightning_slopes)

B, H, Q, HD, L = 5, 3, 16, 8, 2


def inputs(seed, query_lens):
    ks = jax.random.split(jax.random.key(seed), 4)
    live = (jnp.arange(Q)[None, :] < jnp.asarray(query_lens)[:, None])
    live = live[:, None, :, None]
    draw = lambda k: jnp.where(live, jax.random.normal(k, (B, H, Q, HD)), 0)
    state = jax.random.normal(ks[3], (L, B, H, HD, HD))
    return draw(ks[0]), draw(ks[1]), draw(ks[2]), state


def by_hand(q, k, v, s0, slopes, n):
    """One row and head, position by position, in numpy."""
    lam = np.exp(-slopes)
    S, out = np.array(s0, np.float64), []
    for t in range(n):
        S = lam * S + np.outer(k[t], v[t])
        out.append(q[t] @ S)
    return np.array(out), S


def test_slopes_are_the_published_geometric_series():
    s = np.asarray(lightning_slopes(32))
    assert np.allclose(s[0], 2 ** -0.25) and np.allclose(s[-1], 2 ** -8)
    assert np.allclose(s[1:] / s[:-1], 2 ** -0.25)


@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_ragged_rows_against_the_recurrence(path):
    """A full chunk, a decode row, an idle row, a part chunk and a chunk in
    a reused slot (``fresh``), in one call, on layer 1 of a stacked state."""
    query_lens = jnp.array([16, 1, 0, 7, 12])
    fresh = jnp.array([0, 0, 0, 0, 1])
    q, k, v, state = inputs(0, query_lens)
    slopes = lightning_slopes(H)
    o, new = jax.jit(lambda *a: lightning_attention(
        *a, layer=jnp.int32(1), path=path, block=8))(
        q, k, v, state, slopes, query_lens, fresh)
    assert o.shape == q.shape and new.shape == state.shape
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    # the idle row's state is as it was, bit for bit
    np.testing.assert_array_equal(np.asarray(new[1, 2]),
                                  np.asarray(state[1, 2]))
    for b in range(B):
        n = int(query_lens[b])
        for h in range(H):
            s0 = np.zeros((HD, HD)) if int(fresh[b]) else state[1, b, h]
            want_o, want_s = by_hand(
                np.asarray(q[b, h]), np.asarray(k[b, h]),
                np.asarray(v[b, h]), s0, float(slopes[h]), n)
            if n:
                np.testing.assert_allclose(np.asarray(o[b, h, :n]), want_o,
                                           rtol=2e-4, atol=2e-4)
                np.testing.assert_allclose(np.asarray(new[1, b, h]), want_s,
                                           rtol=2e-4, atol=2e-4)
            assert float(jnp.abs(o[b, h, n:]).max(initial=0.0)) == 0.0


def test_kernel_equals_reference_on_a_one_layer_state_and_in_bfloat16():
    query_lens = jnp.array([9, 16, 3, 0, 1])
    fresh = jnp.array([1, 0, 1, 1, 0])
    q, k, v, state = inputs(1, query_lens)
    slopes = lightning_slopes(H)
    for cast in (jnp.float32, jnp.bfloat16):
        args = (q.astype(cast), k.astype(cast), v.astype(cast), state[0],
                slopes, query_lens, fresh)
        o1, s1 = lightning_attention(*args, path=dispatch.REFERENCE)
        o2, s2 = lightning_attention(*args, path=dispatch.INTERPRET,
                                     block=4)
        tol = 1e-4 if cast == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(o1, np.float32),
                                   np.asarray(o2, np.float32), atol=tol)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
        assert o2.dtype == cast and s2.dtype == jnp.float32


def test_chunks_fed_one_after_another_equal_one_long_chunk():
    """Prefill in two chunks then two decode tokens through the carried
    state equals the whole sequence in one call."""
    query_lens = jnp.full((B,), 16)
    q, k, v, state = inputs(2, query_lens)
    slopes = lightning_slopes(H)
    zero = jnp.zeros_like(state[0])
    whole, s_whole = lightning_attention(
        q, k, v, zero, slopes, query_lens, jnp.ones(B, jnp.int32),
        path=dispatch.INTERPRET, block=8)
    S, parts = zero, []
    for lo, hi in ((0, 8), (8, 14), (14, 15), (15, 16)):
        pad = lambda a: jnp.pad(a[:, :, lo:hi],
                                ((0, 0), (0, 0), (0, 8 - (hi - lo)), (0, 0)))
        o, S = lightning_attention(
            pad(q), pad(k), pad(v), S, slopes, jnp.full((B,), hi - lo),
            jnp.full((B,), int(lo == 0)), path=dispatch.INTERPRET, block=8)
        parts.append(o[:, :, : hi - lo])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, axis=2)),
                               np.asarray(whole), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(s_whole), atol=1e-4)


def test_a_stacked_state_needs_its_layer():
    q, k, v, state = inputs(3, jnp.ones(B, jnp.int32))
    with pytest.raises(ValueError, match="layer"):
        lightning_attention(q, k, v, state, lightning_slopes(H),
                            jnp.ones(B), jnp.zeros(B))

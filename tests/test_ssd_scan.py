"""The selective-scan kernel (``kernels/ssd_scan.py``) under the Pallas
interpreter against its ``lax.scan`` oracle, over ragged rows, in float32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.ssd_scan import ssd_scan

B, H, G, P, N, L = 6, 4, 2, 8, 16, 3


def _inputs(query_lens, Q, seed=0, stacked=True):
    rng = np.random.default_rng(seed)
    live = np.arange(Q)[None, :] < np.asarray(query_lens)[:, None]
    draw = lambda *s: rng.standard_normal(s).astype(np.float32)
    x = draw(B, H, Q, P) * live[:, None, :, None]
    Bm = draw(B, G, Q, N) * live[:, None, :, None]
    Cm = draw(B, G, Q, N) * live[:, None, :, None]
    # junk in dt's padded slots: the kernel takes them as 0
    dt = np.log1p(np.exp(draw(B, H, Q))) * 0.3
    A = -rng.uniform(1.0, 16.0, H).astype(np.float32)
    D = 1.0 + 0.1 * draw(H)
    state = draw(L, B, H, P, N) if stacked else draw(B, H, P, N)
    return tuple(jnp.asarray(a) for a in (x, dt, Bm, Cm, A, D, state))


def _both(query_lens, fresh, Q, block, layer=1, seed=0):
    args = _inputs(query_lens, Q, seed)
    ql = jnp.asarray(query_lens, jnp.int32)
    fr = jnp.asarray(fresh, bool)
    layer = jnp.int32(layer)
    ref = ssd_scan(*args, ql, fr, layer=layer, path=dispatch.REFERENCE)
    got = ssd_scan(*args, ql, fr, layer=layer, path=dispatch.INTERPRET,
                   block=block)
    return args, ref, got


CASES = {
    # chunks of 1, 2, 3, an idle row, a sub-chunk, a row past the narrow
    # body's width
    "one_sub_chunk": ([1, 2, 3, 0, 16, 30], 32, 32),
    # several sub-chunks, the last one partial, beside decode rows
    "several_sub_chunks": ([1, 40, 64, 0, 17, 33], 64, 16),
    # the narrow body is the whole sub-chunk
    "narrow_is_block": ([1, 8, 5, 0, 2, 3], 8, 8),
    "all_decode": ([1, 1, 1, 1, 1, 1], 32, 32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_agrees_with_the_recurrence(case):
    query_lens, Q, block = CASES[case]
    fresh = [False, True, False, False, True, False]
    args, (y_ref, s_ref), (y, s) = _both(query_lens, fresh, Q, block)
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s, s_ref, atol=1e-5, rtol=1e-5)
    live = np.arange(Q)[None, :] < np.asarray(query_lens)[:, None]
    # padded slots hold zeros
    assert not np.asarray(y)[~np.broadcast_to(
        live[:, None, :, None], y.shape)].any()
    # the other layers' state is untouched, and so is an idle row's
    state = np.asarray(args[6])
    np.testing.assert_array_equal(np.asarray(s)[[0, 2]], state[[0, 2]])
    idle = [b for b, n in enumerate(query_lens) if n == 0]
    np.testing.assert_array_equal(np.asarray(s)[1, idle], state[1, idle])


def test_fresh_rows_start_from_zero_and_the_decay_depends_on_the_token():
    query_lens = [3, 3, 1, 1, 5, 5]
    args, (y_ref, s_ref), (y, s) = _both(
        query_lens, [True, False, True, False, True, False], 8, 8)
    x, dt, Bm, Cm, A, D, state = (np.asarray(a, np.float64) for a in args)
    # row 2, fresh, one token: S = dt x (x) B, y = S C + D x
    S = dt[2, :, 0, None, None] * x[2, :, 0, :, None] \
        * np.repeat(Bm[2, :, 0], H // G, axis=0)[:, None, :]
    np.testing.assert_allclose(np.asarray(s)[1, 2], S, atol=1e-5)
    want = np.einsum("hpn,hn->hp", S, np.repeat(Cm[2, :, 0], H // G, axis=0)) \
        + D[:, None] * x[2, :, 0]
    np.testing.assert_allclose(np.asarray(y)[2, :, 0], want, atol=1e-5)
    # row 3, carried, one token: the stored state decayed by exp(dt A)
    S3 = np.exp(dt[3, :, 0] * A)[:, None, None] * state[1, 3] \
        + dt[3, :, 0, None, None] * x[3, :, 0, :, None] \
        * np.repeat(Bm[3, :, 0], H // G, axis=0)[:, None, :]
    np.testing.assert_allclose(np.asarray(s)[1, 3], S3, atol=1e-5, rtol=1e-5)


def test_a_chunk_in_two_calls_equals_the_chunk_in_one():
    """The state carried between calls is the recurrence's: 20 tokens at
    once, and as 13 then 7."""
    rows = [20] * B
    x, dt, Bm, Cm, A, D, state = _inputs(rows, 32, seed=3)
    ql = lambda n: jnp.full((B,), n, jnp.int32)
    no = jnp.zeros((B,), bool)
    run = lambda sl, n, st: ssd_scan(
        _padq(x[:, :, sl]), _padq(dt[:, :, sl]), _padq(Bm[:, :, sl]),
        _padq(Cm[:, :, sl]), A, D, st, ql(n), no, layer=jnp.int32(0),
        path=dispatch.INTERPRET, block=16)
    y_all, s_all = run(slice(0, 20), 20, state)
    y_a, s_a = run(slice(0, 13), 13, state)
    y_b, s_b = run(slice(13, 20), 7, s_a)
    np.testing.assert_allclose(s_b, s_all, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.concatenate([y_a[:, :, :13], y_b[:, :, :7]], axis=2),
        y_all[:, :, :20], atol=2e-5, rtol=1e-5)


def _padq(a, Q=32):
    pad = [(0, 0)] * a.ndim
    pad[2] = (0, Q - a.shape[2])
    return jnp.pad(a, pad)


def test_one_layer_state_and_bad_arguments():
    x, dt, Bm, Cm, A, D, state = _inputs([2] * B, 8, stacked=False)
    ql, no = jnp.full((B,), 2, jnp.int32), jnp.zeros((B,), bool)
    y_ref, s_ref = ssd_scan(x, dt, Bm, Cm, A, D, state, ql, no,
                            path=dispatch.REFERENCE)
    y, s = ssd_scan(x, dt, Bm, Cm, A, D, state, ql, no,
                    path=dispatch.INTERPRET)
    assert s.shape == state.shape
    np.testing.assert_allclose(y, y_ref, atol=1e-5)
    np.testing.assert_allclose(s, s_ref, atol=1e-5)
    with pytest.raises(ValueError, match="layer"):
        ssd_scan(x, dt, Bm, Cm, A, D, state, ql, no, layer=jnp.int32(0))
    with pytest.raises(ValueError, match="multiple"):
        ssd_scan(_padq(x, 24), _padq(dt, 24), _padq(Bm, 24), _padq(Cm, 24),
                 A, D, state, ql, no, path=dispatch.INTERPRET, block=16)
    with pytest.raises(ValueError, match="groups"):
        ssd_scan(x, dt, Bm[:, :1].repeat(3, 1), Cm, A, D, state, ql, no)

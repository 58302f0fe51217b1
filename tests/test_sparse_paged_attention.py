"""``ragged_paged_attention`` with grouped heads and selected pages: the
Pallas kernel (under the interpreter) against the gather-and-mask oracle,
and both against a plain per-token softmax over the listed blocks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.paged_attention import (_work_items,
                                                ragged_paged_attention)

B, Q, HKV, G, HD, PS, P, W, K, L = 4, 32, 2, 2, 8, 4, 64, 16, 3, 2
H = HKV * G
DENSE_LEN = 40
QUERY_LENS = np.array([5, 1, 0, 32])
CONTEXT_LENS = np.array([9, 50, 0, 60])    # dense, sparse decode, idle,
#                                            a chunk that crosses dense_len


def inputs(seed=0):
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, Q, H, HD))
    kp = jax.random.normal(ks[1], (L, P, HKV, PS, HD))
    vp = jax.random.normal(ks[2], (L, P, HKV, PS, HD))
    tables = jnp.asarray(rng.permutation(P)[: B * W].reshape(B, W),
                         jnp.int32)
    pos = (CONTEXT_LENS - QUERY_LENS)[:, None] + np.arange(Q)[None]
    sel = np.full((B, HKV, Q, K), -1, np.int32)
    for b in range(B):
        for g in range(HKV):
            for t in range(Q):
                own = max(int(pos[b, t]), 0) // PS
                sel[b, g, t] = rng.choice(own + 1, K, replace=own + 1 < K)
                sel[b, g, t, 0] = own
    return q, kp, vp, tables, jnp.asarray(sel), pos


def attend(path, q, kp, vp, tables, selected, layer=1):
    return jax.jit(lambda *a: ragged_paged_attention(
        *a, path=path, layer=jnp.int32(layer), selected=selected,
        total_q=40))(q, kp, vp, tables, jnp.asarray(QUERY_LENS),
                     jnp.asarray(CONTEXT_LENS))


def by_hand(q, kp, vp, tables, sel, pos, layer=1):
    """Token by token: softmax over the positions of the listed blocks (or
    of the whole context, within ``dense_len``), causal."""
    out = np.zeros((B, Q, H, HD))
    kp, vp, tables = np.asarray(kp), np.asarray(vp), np.asarray(tables)
    for b in range(B):
        for t in range(int(QUERY_LENS[b])):
            p = int(pos[b, t])
            for h in range(H):
                g = h // G
                blocks = (range(p // PS + 1) if p + 1 <= DENSE_LEN
                          or sel is None else sorted(set(
                              int(x) for x in sel[b, g, t] if x >= 0)))
                at = [w * PS + i for w in blocks for i in range(PS)
                      if w * PS + i <= p]
                keys = np.stack([kp[layer, tables[b, a // PS], g, a % PS]
                                 for a in at])
                vals = np.stack([vp[layer, tables[b, a // PS], g, a % PS]
                                 for a in at])
                s = keys @ np.asarray(q[b, t, h]) / np.sqrt(HD)
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ vals
    return out


@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_selected_pages_with_a_mask_per_query_token(path):
    q, kp, vp, tables, sel, pos = inputs()
    got = attend(path, q, kp, vp, tables, (sel, DENSE_LEN))
    assert got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got), by_hand(q, kp, vp, tables, np.asarray(sel), pos),
        atol=2e-5)
    # padded query slots and the idle row are zeros
    assert float(jnp.abs(got[2]).max()) == 0.0
    assert float(jnp.abs(got[0, 5:]).max()) == 0.0


@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_grouped_heads_without_a_list_attend_over_everything(path):
    q, kp, vp, tables, _, pos = inputs(1)
    got = attend(path, q, kp, vp, tables, (None, 0))
    np.testing.assert_allclose(
        np.asarray(got), by_hand(q, kp, vp, tables, None, pos), atol=2e-5)


def test_every_page_listed_is_the_dense_result():
    q, kp, vp, tables, _, _ = inputs(2)
    every = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32),
                             (B, HKV, Q, W))
    for path in (dispatch.REFERENCE, dispatch.INTERPRET):
        listed = attend(path, q, kp, vp, tables, (every, 0))
        dense = attend(path, q, kp, vp, tables, (None, 0))
        np.testing.assert_allclose(np.asarray(listed), np.asarray(dense),
                                   atol=2e-6)


def test_grouped_equals_the_equal_heads_kernel_on_repeated_heads():
    """The same keys under both layouts: a head-major pool of 2 heads
    shared by 2 query heads each, and the page-major pool of 4 equal
    heads that the dense family's kernel reads."""
    q, kp, vp, tables, _, _ = inputs(3)
    grouped = attend(dispatch.INTERPRET, q, kp, vp, tables, (None, 0))
    spread = lambda pool: jnp.repeat(pool, G, axis=2).transpose(
        0, 1, 3, 2, 4)                       # [L, P, PS, H, HD]
    equal = jax.jit(lambda *a: ragged_paged_attention(
        *a, path=dispatch.INTERPRET, layer=jnp.int32(1)))(
        q, spread(kp), spread(vp), tables, jnp.asarray(QUERY_LENS),
        jnp.asarray(CONTEXT_LENS))
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(equal),
                               atol=2e-5)


def test_bfloat16_kernel_stays_near_the_float32_oracle():
    q, kp, vp, tables, sel, _ = inputs(4)
    bf = lambda a: a.astype(jnp.bfloat16)
    got = attend(dispatch.INTERPRET, bf(q), bf(kp), bf(vp), tables,
                 (sel, DENSE_LEN))
    want = attend(dispatch.REFERENCE, bf(q), bf(kp), bf(vp), tables,
                  (sel, DENSE_LEN))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.05)


def test_work_items_list_each_segments_pages_in_order():
    visit = jnp.array([[0, 1, 0, 1], [0, 0, 0, 0], [1, 1, 1, 0]], bool)
    seg, w, n = _work_items(visit, 8)
    assert int(n) == 5
    assert list(np.asarray(seg[:5])) == [0, 0, 2, 2, 2]
    assert list(np.asarray(w[:5])) == [1, 3, 0, 1, 2]

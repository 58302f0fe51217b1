"""``ragged_paged_attention`` with grouped heads and selected pages: the
Pallas kernel (under the interpreter) against the gather-and-mask oracle,
and both against a plain per-token softmax over the listed blocks.

A work item of the kernel is up to ``pages`` listed pages of one (row,
group, query tile), ``pages`` following from the page size
(``_LISTED_KEY_TILE`` key positions an item, never more than the table
holds).  At the test's pages of 4 and table of 16 that is the whole table:
every segment is one partly filled item.  The kernel cases also run with
the constant set to 8 (2 pages an item: segments of several items, the
last partly filled) and to 4 (one page an item: the algorithm as it was
before items held several pages)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.paged_attention import (_paged_work_items,
                                                _pages_per_item, _work_items,
                                                listed_work_items,
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


# (path, key positions an item): the oracle, and the kernel at 16, 2 and 1
# pages an item
CASES = [(dispatch.REFERENCE, 512), (dispatch.INTERPRET, 512),
         (dispatch.INTERPRET, 8), (dispatch.INTERPRET, 4)]
KERNEL_CASES = [key_tile for path, key_tile in CASES
                if path == dispatch.INTERPRET]


def attend(path, q, kp, vp, tables, selected, layer=1):
    # a new jit each call: the trace reads the module's constant anew
    return jax.jit(lambda *a: ragged_paged_attention(
        *a, path=path, layer=jnp.int32(layer), selected=selected,
        total_q=40))(q, kp, vp, tables, jnp.asarray(QUERY_LENS),
                     jnp.asarray(CONTEXT_LENS))


def items_of(selected):
    """The kernel's work list for the module's lengths, as numpy:
    ``(segment, pages [n, pages an item], count)`` of the items in use."""
    seg, lp, count, n = listed_work_items(
        jnp.asarray(QUERY_LENS), jnp.asarray(CONTEXT_LENS), PS, W, Q, HKV,
        selected, total_q=40)
    n = int(n[0])
    return (np.asarray(seg)[:n], np.asarray(lp).reshape(len(seg), -1)[:n],
            np.asarray(count)[:n])


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


@pytest.mark.parametrize("path,key_tile", CASES)
def test_selected_pages_with_a_mask_per_query_token(path, key_tile,
                                                    monkeypatch):
    """A dense row, a sparse decode row, an idle row and a chunk across
    the ``dense_len`` edge in one call."""
    monkeypatch.setattr(pa, "_LISTED_KEY_TILE", key_tile)
    q, kp, vp, tables, sel, pos = inputs()
    got = attend(path, q, kp, vp, tables, (sel, DENSE_LEN))
    assert got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got), by_hand(q, kp, vp, tables, np.asarray(sel), pos),
        atol=2e-5)
    # padded query slots and the idle row are zeros
    assert float(jnp.abs(got[2]).max()) == 0.0
    assert float(jnp.abs(got[0, 5:]).max()) == 0.0


@pytest.mark.parametrize("path,key_tile", CASES)
def test_grouped_heads_without_a_list_attend_over_everything(path, key_tile,
                                                             monkeypatch):
    monkeypatch.setattr(pa, "_LISTED_KEY_TILE", key_tile)
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


@pytest.mark.parametrize("key_tile", KERNEL_CASES)
def test_two_tokens_of_one_tile_with_disjoint_lists_share_an_item(
        key_tile, monkeypatch):
    """Tokens 16 and 20 of the chunk (positions 44 and 48, past
    ``dense_len``) list pages {0, 2, 11} and {1, 3, 12}: at 16 and at 2
    pages an item, pages of both lists sit in one item ({0, 1}), and each
    token reads its own and none of the other's."""
    monkeypatch.setattr(pa, "_LISTED_KEY_TILE", key_tile)
    q, kp, vp, tables, sel, pos = inputs(5)
    sel = np.array(sel)
    assert pos[3, 16] == 44 and pos[3, 20] == 48
    sel[3, :, 16], sel[3, :, 20] = [0, 2, 11], [1, 3, 12]
    pages = _pages_per_item(PS, W, key_tile)
    seg, lp, count = items_of((jnp.asarray(sel), DENSE_LEN))
    if pages > 1:
        first = lp[seg == 3 * HKV][0]           # row 3, group 0
        assert {0, 1} <= set(first[:2].tolist())
    got = attend(dispatch.INTERPRET, q, kp, vp, tables,
                 (jnp.asarray(sel), DENSE_LEN))
    np.testing.assert_allclose(
        np.asarray(got), by_hand(q, kp, vp, tables, sel, pos), atol=2e-5)


@pytest.mark.parametrize("key_tile", KERNEL_CASES)
def test_bfloat16_kernel_stays_near_the_float32_oracle(key_tile,
                                                       monkeypatch):
    monkeypatch.setattr(pa, "_LISTED_KEY_TILE", key_tile)
    q, kp, vp, tables, sel, _ = inputs(4)
    bf = lambda a: a.astype(jnp.bfloat16)
    got = attend(dispatch.INTERPRET, bf(q), bf(kp), bf(vp), tables,
                 (sel, DENSE_LEN))
    want = attend(dispatch.REFERENCE, bf(q), bf(kp), bf(vp), tables,
                  (sel, DENSE_LEN))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.05)


def test_pages_an_item_follow_from_the_page_size():
    """Whole pages covering 512 key positions: 8 of the long-context
    cell's 64, one of the chat cell's 512, and never more than the table
    holds."""
    assert _pages_per_item(64, 544, pa._LISTED_KEY_TILE) == 8
    assert _pages_per_item(512, 4, pa._LISTED_KEY_TILE) == 1
    assert _pages_per_item(1024, 4, pa._LISTED_KEY_TILE) == 1
    assert _pages_per_item(PS, W, pa._LISTED_KEY_TILE) == W


def test_work_items_list_each_segments_pages_in_order():
    visit = jnp.array([[0, 1, 0, 1], [0, 0, 0, 0], [1, 1, 1, 0]], bool)
    seg, w, n = _work_items(visit, 8)
    assert int(n) == 5
    assert list(np.asarray(seg[:5])) == [0, 0, 2, 2, 2]
    assert list(np.asarray(w[:5])) == [1, 3, 0, 1, 2]
    # the same list as items of one entry
    seg1, w1, count, n1 = _paged_work_items(visit, 8, 1)
    assert int(n1) == 5 and list(np.asarray(count)) == [1] * 5 + [0] * 3
    assert (np.asarray(seg1[:5]) == np.asarray(seg[:5])).all()
    assert (np.asarray(w1[:5]) == np.asarray(w[:5])).all()
    # two entries an item: the last item of a segment partly filled
    seg, w, count, n = _paged_work_items(visit, 4, 2)
    assert int(n) == 3
    assert list(np.asarray(seg[:3])) == [0, 2, 2]
    assert list(np.asarray(count)) == [2, 2, 1, 0]
    w = np.asarray(w).reshape(4, 2)
    assert w[0].tolist() == [1, 3] and w[1].tolist() == [0, 1]
    assert w[2, 0] == 2


@pytest.mark.parametrize("pages", [1, 2, 3, 5, 16])
def test_every_visited_page_is_in_exactly_one_item(pages):
    """Segments with 0 to 16 visited pages: every (segment, page) once, a
    segment's pages ascending across its items, ``ceil(visited / pages)``
    items a segment and only its last one partly filled."""
    rng = np.random.default_rng(pages)
    visit = rng.random((12, 16)) < rng.random((12, 1))
    visit[0], visit[1], visit[2, :] = False, True, np.arange(16) == 7
    per_seg = -(-visit.sum(1) // pages)
    n_max = int(per_seg.sum()) + 3
    seg, w, count, n = map(np.asarray, _paged_work_items(
        jnp.asarray(visit), n_max, pages))
    n, w = int(n), w.reshape(n_max, pages)
    assert n == per_seg.sum() and not count[n:].any()
    assert np.bincount(seg[:n], minlength=12).tolist() == per_seg.tolist()
    listed = [(s, p) for s, row, c in zip(seg[:n], w, count)
              for p in row[:c]]
    assert listed == [tuple(x) for x in np.argwhere(visit)]
    for s in range(12):
        assert (count[:n][seg[:n] == s][:-1] == pages).all()


@pytest.mark.parametrize("key_tile", KERNEL_CASES)
def test_the_cells_rows_fill_items_as_their_lengths_and_lists_say(
        key_tile, monkeypatch):
    """The module's rows: the dense row reads 3 pages a group, the decode
    row its list, the idle row nothing, the chunk's one tile the union of
    its dense tokens' reach and its sparse tokens' lists."""
    monkeypatch.setattr(pa, "_LISTED_KEY_TILE", key_tile)
    _, _, _, _, sel, pos = inputs()
    pages = _pages_per_item(PS, W, key_tile)
    seg, lp, count = items_of((sel, DENSE_LEN))
    sel = np.asarray(sel)
    for b, g in np.ndindex(B, HKV):
        want = set()
        for t in range(int(QUERY_LENS[b])):
            p = int(pos[b, t])
            want |= set(range(p // PS + 1)) if p + 1 <= DENSE_LEN \
                else set(sel[b, g, t].tolist()) - {-1}
        mine = seg == b * HKV + g
        got = [p for row, c in zip(lp[mine], count[mine]) for p in row[:c]]
        assert got == sorted(want)
        assert mine.sum() == -(-len(want) // pages)
    if pages == 2:
        # full items and partly filled last ones: what the kernel's cases
        # above run
        assert {1, 2} <= set(count.tolist())
    if pages == W:
        assert 0 < count.min() and count.max() < W

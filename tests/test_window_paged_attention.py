"""The lower edge of ``ragged_paged_attention``'s grouped-heads mode
(``window=``): the Pallas kernel (under the interpreter) against the
gather-and-mask oracle and both against a plain per-token softmax over the
window, with groups that are not a power of two, and with the pages behind
the window gone (their entries in the table stale)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.paged_attention import ragged_paged_attention

B, Q, HKV, HD, PS, P, W, L = 5, 32, 2, 8, 4, 96, 16, 2
WINDOW = 10
QUERY_LENS = np.array([5, 1, 0, 32, 1])
CONTEXT_LENS = np.array([9, 50, 0, 60, 10])   # inside the window, decode
#                                 far past it, idle, a chunk across it, and
#                                 a decode row whose window is its context


def inputs(groups, seed=0):
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, Q, HKV * groups, HD))
    kp = jax.random.normal(ks[1], (L, P, HKV, PS, HD))
    vp = jax.random.normal(ks[2], (L, P, HKV, PS, HD))
    tables = jnp.asarray(rng.permutation(P)[: B * W].reshape(B, W),
                         jnp.int32)
    pos = (CONTEXT_LENS - QUERY_LENS)[:, None] + np.arange(Q)[None]
    return q, kp, vp, tables, pos


def attend(path, q, kp, vp, tables, window, layer=1):
    return jax.jit(lambda *a: ragged_paged_attention(
        *a, path=path, layer=jnp.int32(layer), selected=(None, 2 ** 30),
        total_q=40, window=window))(
            q, kp, vp, tables, jnp.asarray(QUERY_LENS),
            jnp.asarray(CONTEXT_LENS))


def by_hand(q, kp, vp, tables, pos, groups, window, layer=1):
    """Token by token: softmax over the last ``window`` positions."""
    out = np.zeros(q.shape)
    kp, vp, tables = np.asarray(kp), np.asarray(vp), np.asarray(tables)
    for b in range(B):
        for t in range(int(QUERY_LENS[b])):
            p = int(pos[b, t])
            at = [a for a in range(p + 1) if a > p - window]
            for h in range(HKV * groups):
                g = h // groups
                keys = np.stack([kp[layer, tables[b, a // PS], g, a % PS]
                                 for a in at])
                vals = np.stack([vp[layer, tables[b, a // PS], g, a % PS]
                                 for a in at])
                s = keys @ np.asarray(q[b, t, h]) / np.sqrt(HD)
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ vals
    return out


# (path, key positions an item of the padded-rows kernel): at 512 an item is
# the whole table of 16 pages, at 8 two pages; either way the window's
# lower edge (10 positions back) falls inside an item
@pytest.mark.parametrize("groups", [2, 3])
@pytest.mark.parametrize("path,key_tile", [(dispatch.REFERENCE, 512),
                                           (dispatch.INTERPRET, 512),
                                           (dispatch.INTERPRET, 8)])
def test_a_token_reads_the_last_window_positions_and_no_more(
        path, key_tile, groups, monkeypatch):
    monkeypatch.setattr(pa, "_LISTED_KEY_TILE", key_tile)
    q, kp, vp, tables, pos = inputs(groups)
    got = attend(path, q, kp, vp, tables, WINDOW)
    assert got.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(got), by_hand(q, kp, vp, tables, pos, groups, WINDOW),
        atol=2e-5)
    # one position fewer is another result: the edge is where it is said
    short = attend(path, q, kp, vp, tables, WINDOW - 1)
    assert float(jnp.abs(short - got).max()) > 1e-3
    # padded query slots and the idle row are zeros
    assert float(jnp.abs(got[2]).max()) == 0.0
    assert float(jnp.abs(got[0, 5:]).max()) == 0.0


def test_a_window_wider_than_every_context_is_dense_attention():
    q, kp, vp, tables, _ = inputs(3, seed=1)
    for path in (dispatch.REFERENCE, dispatch.INTERPRET):
        np.testing.assert_allclose(
            np.asarray(attend(path, q, kp, vp, tables, 10 ** 6)),
            np.asarray(attend(path, q, kp, vp, tables, None)), atol=2e-6)


def test_pages_behind_the_window_are_never_fetched():
    """The cache manager gives those pages back: their entries in the table
    are stale.  Point every one of them at a page of NaN: the kernel lists
    only the pages a window reaches, so none comes near a product."""
    q, kp, vp, tables, pos = inputs(3, seed=2)
    want = attend(dispatch.INTERPRET, q, kp, vp, tables, WINDOW)
    poison = min(set(range(P)) - set(np.asarray(tables).ravel().tolist()))
    kp = kp.at[:, poison].set(jnp.nan)
    vp = vp.at[:, poison].set(jnp.nan)
    first = np.maximum(CONTEXT_LENS - QUERY_LENS - WINDOW + 1, 0) // PS
    stale = np.arange(W)[None, :] < first[:, None]
    assert stale.sum() > 10
    tables = jnp.where(jnp.asarray(stale), poison, tables)
    got = attend(dispatch.INTERPRET, q, kp, vp, tables, WINDOW)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_a_window_comes_with_the_grouped_mode():
    q, kp, vp, tables, _ = inputs(2)
    with pytest.raises(ValueError, match="window"):
        ragged_paged_attention(
            q, kp[0].transpose(0, 2, 1, 3), vp[0].transpose(0, 2, 1, 3),
            tables, jnp.asarray(QUERY_LENS), jnp.asarray(CONTEXT_LENS),
            window=WINDOW)


# ---------------------------------------------- queries packed in tiles

TILE = 8


def tiled(q, query_lens=QUERY_LENS, tile=TILE, unused=2):
    """``q [B, Q, ...]`` packed in tiles of ``tile`` slots of one row each,
    as ``RaggedView.pad_tiles`` lays them out: a row's tiles in order, rows
    in order, and ``unused`` tiles at the end (filled with 7s)."""
    rows, index = [], []
    for b, n in enumerate(query_lens):
        for t in range(-(-int(n) // tile)):
            rows.append(b)
            index.append(t)
    nb = len(query_lens)
    tiles = jnp.stack([q[b, t * tile:(t + 1) * tile]
                       for b, t in zip(rows, index)]
                      + [jnp.full_like(q[0, :tile], 7.0)] * unused)
    return tiles, jnp.asarray(rows + [nb] * unused, jnp.int32), \
        jnp.asarray(index + [0] * unused, jnp.int32)


def attend_tiles(path, tiles, rows, index, kp, vp, tables, query_lens,
                 context_lens, window):
    return np.asarray(jax.jit(lambda *a: ragged_paged_attention(
        *a, path=path, layer=jnp.int32(1), selected=(None, 2 ** 30),
        window=window, q_tiles=(rows, index)))(
            tiles, kp, vp, tables, jnp.asarray(query_lens),
            jnp.asarray(context_lens)))


# (tile, run): the chunk of 32 in four tiles of 8 read as one run, or in
# eight tiles of 4 read as four runs of 2 (the chunk of 5 a run of 2)
@pytest.mark.parametrize("tile,run", [(8, 8), (4, 2)])
@pytest.mark.parametrize("window", [WINDOW, None])
@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_queries_packed_in_tiles_give_the_padded_rows_result(
        path, window, tile, run, monkeypatch):
    """A chunk of 32 in tiles of ``tile``, a chunk of 5, decode rows in one
    tile each, nothing for the idle row: tile by tile what the padded
    ``[B, Q]`` layout gives, whatever the runs the kernel reads them in."""
    monkeypatch.setattr(pa, "_RUN_TILES", run)
    q, kp, vp, tables, pos = inputs(3, seed=4)
    want = np.asarray(attend(dispatch.REFERENCE, q, kp, vp, tables, window))
    tiles, rows, index = tiled(q, tile=tile)
    assert tiles.shape[0] == 32 // tile + -(-5 // tile) + 1 + 0 + 1 + 2
    got = attend_tiles(path, tiles, rows, index, kp, vp, tables,
                       QUERY_LENS, CONTEXT_LENS, window)
    assert got.shape == tiles.shape
    for n, (b, t) in enumerate(zip(rows.tolist()[:-2], index.tolist())):
        np.testing.assert_allclose(got[n], want[b, t * tile:(t + 1) * tile],
                                   atol=2e-5)
    assert not got[-2:].any()                        # the unused tiles


# a chunk of 130 (a run of 8 tiles of 16, then a run of one tile of 2
# tokens), a decode row, an idle row, a chunk of 3, a chunk of 20 (a run of
# 2 tiles) and a decode row, in 14 tiles and 2 unused ones: the chunk of
# 20's run is read in the last 8 tiles, whose first three are the chunk of
# 130's last tile, the decode row's and the chunk of 3's, and whose sixth
# is the last decode row's
RUN_QUERY_LENS = np.array([130, 1, 0, 3, 20, 1])
RUN_CONTEXT_LENS = np.array([150, 40, 0, 3, 90, 151])
RUN_W, RUN_Q = 40, 144


def run_inputs(seed):
    nb = len(RUN_QUERY_LENS)
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (nb, RUN_Q, HKV * 3, HD))
    kp = jax.random.normal(ks[1], (L, nb * RUN_W, HKV, PS, HD))
    vp = jax.random.normal(ks[2], (L, nb * RUN_W, HKV, PS, HD))
    tables = jnp.asarray(rng.permutation(nb * RUN_W).reshape(nb, RUN_W),
                         jnp.int32)
    return q, kp, vp, tables


@pytest.mark.parametrize("window", [WINDOW, None])
@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_runs_of_tiles_of_16_give_the_padded_rows_result(path, window):
    q, kp, vp, tables = run_inputs(5)
    want = np.asarray(jax.jit(lambda *a: ragged_paged_attention(
        *a, path=dispatch.REFERENCE, layer=jnp.int32(1),
        selected=(None, 2 ** 30), window=window))(
            q, kp, vp, tables, jnp.asarray(RUN_QUERY_LENS),
            jnp.asarray(RUN_CONTEXT_LENS)))
    tile = pa.QUERY_TILE
    tiles, rows, index = tiled(q, RUN_QUERY_LENS, tile)
    assert tiles.shape[0] == 16
    assert pa.query_tile_runs(130) == (1, 1)
    assert [pa.query_tile_runs(n) for n in (1, 3, 20)] == [(1, 0), (1, 0),
                                                           (0, 1)]
    got = attend_tiles(path, tiles, rows, index, kp, vp, tables,
                       RUN_QUERY_LENS, RUN_CONTEXT_LENS, window)
    for n, (b, t) in enumerate(zip(rows.tolist()[:-2], index.tolist())):
        live = min(tile, int(RUN_QUERY_LENS[b]) - t * tile)
        np.testing.assert_allclose(got[n, :live],
                                   want[b, t * tile:t * tile + live],
                                   atol=2e-5)
        assert not got[n, live:].any()               # padded query slots
    assert not got[-2:].any()


def test_a_run_writes_its_own_tiles_and_no_others():
    """Every tile of a row comes back as the row alone gives it, whatever
    the runs around it read: the same call with every other row idle."""
    q, kp, vp, tables = run_inputs(6)
    tiles, rows, index = tiled(q, RUN_QUERY_LENS, pa.QUERY_TILE)
    both = attend_tiles(dispatch.INTERPRET, tiles, rows, index, kp, vp,
                        tables, RUN_QUERY_LENS, RUN_CONTEXT_LENS, WINDOW)
    for b in np.flatnonzero(RUN_QUERY_LENS):
        mine = np.asarray(rows) == b
        alone = np.where(np.arange(len(RUN_QUERY_LENS)) == b,
                         RUN_QUERY_LENS, 0)
        got = attend_tiles(dispatch.INTERPRET, tiles,
                           jnp.where(mine, rows, len(alone)), index, kp, vp,
                           tables, alone, RUN_CONTEXT_LENS, WINDOW)
        np.testing.assert_array_equal(got[mine], both[mine])
        assert not got[~mine].any()


def test_tiles_come_with_the_grouped_mode_and_no_lists():
    q, kp, vp, tables, _ = inputs(2)
    tiles, rows, index = tiled(q)
    lists = jnp.zeros((B, HKV, Q, 1), jnp.int32)
    for selected in (None, (lists, 0)):
        with pytest.raises(ValueError, match="q_tiles"):
            ragged_paged_attention(
                tiles, kp, vp, tables, jnp.asarray(QUERY_LENS),
                jnp.asarray(CONTEXT_LENS), layer=jnp.int32(0),
                selected=selected, q_tiles=(rows, index))

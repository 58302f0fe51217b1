"""The grouped matrix product of the dropless expert layer
(``kernels/expert_matmul.py``): the Pallas kernel under the interpreter
against the ``jnp`` path and against a plain loop over the groups, at
routings that leave experts empty, fill one expert with everything and need
several tiles an expert; and the expert layer around it
(``models/experts.py``): every choice held, none held, the shares of four
holders adding up to the whole layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.expert_matmul import (_items, buffer_rows,
                                              expert_layout, expert_matmul)
from paddle_tpu.models.experts import (dropless_experts, expert_tile,
                                       route_top_k)

E, K, N_OUT, TILE, LAYERS = 5, 16, 256, 8, 2


def case(sizes, seed=0):
    """A buffer in the layout of ``sizes``, its padding rows filled with
    junk that must not show."""
    sizes = np.asarray(sizes, np.int32)
    ks = jax.random.split(jax.random.key(seed), 4)
    n = buffer_rows(int(sizes.sum()), E, TILE)
    x = jax.random.normal(ks[0], (n, K))
    w = jax.random.normal(ks[1], (LAYERS, E, K, N_OUT)) / 4
    up = jax.random.normal(ks[2], (LAYERS, E, K, N_OUT)) / 4
    return x, jnp.asarray(sizes), w, up


def by_hand(x, sizes, w, up, layer):
    """Group by group; rows outside every group stay ``nan`` (the kernel
    writes zeros in a visited tile's padding and nothing past the last
    group: see ``rows_written``)."""
    starts, _ = expert_layout(sizes, TILE)
    out = np.full((x.shape[0], N_OUT), np.nan, np.float32)
    x = np.asarray(x, np.float64)
    for e, (s, n) in enumerate(zip(np.asarray(starts), np.asarray(sizes))):
        rows = x[s:s + n]
        y = rows @ np.asarray(w[layer, e], np.float64)
        if up is not None:
            y = y / (1 + np.exp(-y)) * (rows @ np.asarray(up[layer, e],
                                                          np.float64))
        out[s:s + n] = y
    return out


SIZES = [[3, 0, 9, 1, 8],        # an empty expert, a tile and a bit
         [0, 0, 21, 0, 0],       # everything on one expert: three tiles
         [0, 0, 0, 0, 0],        # no pair at all
         [8, 8, 8, 8, 8]]        # whole tiles


@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("sizes", SIZES)
@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_each_group_times_its_own_experts_matrix(path, sizes, swiglu):
    x, sizes, w, up = case(sizes)
    up = up if swiglu else None
    got = np.asarray(jax.jit(lambda *a: expert_matmul(
        *a, tile=TILE, layer=jnp.int32(1), path=path))(x, sizes, w, up))
    want = by_hand(x, sizes, w, up, 1)
    real = ~np.isnan(want[:, 0])
    np.testing.assert_allclose(got[real], want[real], atol=2e-4)
    # a visited tile's padding rows are zeros, whatever the buffer held
    starts, tiles = (np.asarray(a) for a in expert_layout(sizes, TILE))
    for s, t, n in zip(starts, tiles, np.asarray(sizes)):
        assert not got[s + n:s + t * TILE].any()


def test_one_layers_matrices_without_a_layer():
    x, sizes, w, up = case(SIZES[0], seed=1)
    for path in (dispatch.REFERENCE, dispatch.INTERPRET):
        a = expert_matmul(x, sizes, w[0], up[0], tile=TILE, path=path)
        b = expert_matmul(x, sizes, w, up, tile=TILE, layer=0, path=path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="layer"):
        expert_matmul(x, sizes, w, tile=TILE)
    with pytest.raises(ValueError, match="whole tiles"):
        expert_matmul(x[:-1], sizes, w[0], tile=TILE)


@pytest.mark.parametrize("sizes", SIZES)
def test_an_experts_weights_are_fetched_once_and_only_if_it_has_a_pair(
        sizes):
    """The work list orders items expert, column block, row tile: the
    weight block's index changes once per (expert with a pair, block), so
    Pallas fetches every such block once and no other."""
    sizes = jnp.asarray(sizes, jnp.int32)
    blocks, n_tiles = 3, buffer_rows(int(sizes.sum()), E, TILE) // TILE
    e, blk, tile, n, _ = (np.asarray(a) for a in _items(
        sizes, TILE, blocks, n_tiles))
    n = int(n[0])
    _, tiles = (np.asarray(a) for a in expert_layout(sizes, TILE))
    assert n == blocks * tiles.sum()
    keys = list(zip(e[:n], blk[:n]))
    fetches = 1 + sum(a != b for a, b in zip(keys, keys[1:])) if n else 0
    assert fetches == blocks * (np.asarray(sizes) > 0).sum()
    # and every (tile, block) of the output is written exactly once
    assert len({(t, b) for t, b in zip(tile[:n], blk[:n])}) == n


def test_the_buffer_holds_any_routing():
    for tokens, k, held, tile in ((1088, 10, 64, 64), (7, 4, 4, 8)):
        n = buffer_rows(tokens * min(k, held), held, tile)
        # the worst case: every group one row past a whole tile
        worst = tokens * min(k, held) + held * (tile - 1)
        assert n >= -(-worst // tile) * tile - held * tile and n % tile == 0
    assert expert_tile(1088, 10, 256, jnp.bfloat16) == 64
    assert expert_tile(19, 4, 16, jnp.float32) == 8


# ------------------------------------------------------- the expert layer

T, D, F, EXPERTS, TOP_K = 19, 16, 8, 16, 4


def layer_inputs(seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, EXPERTS))
    gate = jax.random.normal(ks[2], (EXPERTS, D, F)) / 4
    up = jax.random.normal(ks[3], (EXPERTS, D, F)) / 4
    down = jax.random.normal(ks[4], (EXPERTS, F, D)) / 4
    return u, router, gate, up, down


def whole_layer(u, weights, experts, gate, up, down):
    """Every token through each of its chosen experts, one by one."""
    out = np.zeros((T, D))
    u = np.asarray(u, np.float64)
    for t in range(T):
        for w, e in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            a = u[t] @ np.asarray(gate[e], np.float64)
            h = a / (1 + np.exp(-a)) * (u[t] @ np.asarray(up[e], np.float64))
            out[t] += w * (h @ np.asarray(down[e], np.float64))
    return out


@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_four_holders_shares_add_up_to_the_whole_layer(path):
    u, router, gate, up, down = layer_inputs()
    weights, experts = route_top_k(u @ router, TOP_K, scale=2.5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-6)
    total, pairs = np.zeros((T, D)), 0
    for first in range(0, EXPERTS, 4):
        held = slice(first, first + 4)
        part, sizes = dropless_experts(
            u, weights, experts, gate[held], up[held], down[held],
            experts_held=(first, 4), num_experts=EXPERTS, path=path)
        total += np.asarray(part)
        pairs += int(sizes.sum())
    assert pairs == T * TOP_K                      # no pair lost or doubled
    np.testing.assert_allclose(
        total, whole_layer(u, weights, experts, gate, up, down), atol=5e-4)


@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_every_choice_held_and_none_held(path):
    u, router, gate, up, down = layer_inputs(1)
    first, held = 4, slice(4, 8)
    run = lambda logits, valid=None: dropless_experts(
        u, *route_top_k(logits, TOP_K), gate[held], up[held], down[held],
        experts_held=(first, 4), num_experts=EXPERTS, valid=valid, path=path)
    logits = u @ router
    favour = jnp.zeros((EXPERTS,)).at[held].set(100.0)
    # all four choices of every token are the four held experts
    part, sizes = run(logits + favour)
    assert sizes.tolist() == [T] * 4
    weights, experts = route_top_k(logits + favour, TOP_K)
    np.testing.assert_allclose(
        np.asarray(part),
        whole_layer(u, weights, experts, gate, up, down), atol=5e-4)
    # no choice of any token is held: the part is zero, not garbage
    part, sizes = run(logits - favour)
    assert sizes.tolist() == [0] * 4 and not np.asarray(part).any()
    # a padding slot is routed nowhere
    valid = jnp.arange(T) < 5
    part, sizes = run(logits + favour, valid)
    assert sizes.tolist() == [5] * 4 and not np.asarray(part[5:]).any()


def test_a_tokens_result_does_not_depend_on_its_batch_mates():
    """Dropless: the same token beside other tokens, all of which crowd
    its experts, gets the same result (a capacity would drop it)."""
    u, router, gate, up, down = layer_inputs(2)
    run = lambda u: dropless_experts(
        u, *route_top_k(u @ router, TOP_K), gate[:8], up[:8], down[:8],
        experts_held=(0, 8), num_experts=EXPERTS,
        path=dispatch.INTERPRET)[0]
    crowd = jnp.broadcast_to(u[0], u.shape)      # everyone picks token 0's
    np.testing.assert_allclose(np.asarray(run(crowd)[0]),
                               np.asarray(run(u)[0]), atol=1e-6)

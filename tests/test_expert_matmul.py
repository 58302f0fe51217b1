"""The two grouped products of the dropless expert layer
(``kernels/expert_matmul.py``), which move their own rows: the Pallas
kernels under the interpreter and the ``jnp`` paths against plain loops —
the fetch-by-id call against the product over ``take(u, rows)``, the
weighted add against gathering every pair's result back, masking and
summing over a token's choices — at routings that leave experts empty, fill
one expert with everything (several tiles adding into the same tokens),
put two choices of a token in one tile, hold every choice, hold none, and
carry padding tokens; and the expert layer around them
(``models/experts.py``): every choice held, none held, the shares of four
holders adding up to the whole layer."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.expert_matmul import (_items, buffer_rows,
                                              expert_layout, expert_matmul,
                                              expert_matmul_add,
                                              visited_rows)
from paddle_tpu.models.experts import (dropless_experts, expert_tile,
                                       route_top_k)

E, K, N_OUT, TILE, LAYERS = 5, 16, 256, 8, 2
TOKENS = 6
ELSEWHERE = E                    # a choice this holder does not hold
JUNK = 10 ** 6                   # the token id of a padding row: never read
PATHS = [dispatch.REFERENCE, dispatch.INTERPRET]


def routing_of(sizes):
    """``experts [TOKENS, k]`` that give the held experts ``sizes`` pairs:
    the pairs dealt to the tokens in turn, so a token has several choices,
    on one expert where a group is larger than the tokens."""
    flat = np.repeat(np.arange(E), sizes)
    k = max(1, -(-len(flat) // TOKENS))
    flat = np.concatenate([flat, np.full(TOKENS * k - len(flat), ELSEWHERE)])
    return flat.reshape(k, TOKENS).T.copy()


SIZES = [[3, 0, 9, 1, 8],        # an empty expert, a tile and a bit
         [0, 0, 21, 0, 0],       # everything on one expert: three tiles
         [0, 0, 0, 0, 0],        # no pair at all
         [8, 8, 8, 8, 8]]        # whole tiles
ALL_VALID = np.ones(TOKENS, bool)
# name -> (experts [TOKENS, k], valid [TOKENS])
ROUTINGS = {
    **{f"sizes{i}": (routing_of(s), ALL_VALID) for i, s in enumerate(SIZES)},
    "every_choice_held": (
        (np.arange(TOKENS)[:, None] + np.arange(4)[None, :]) % E, ALL_VALID),
    "two_choices_of_a_token_in_one_tile": (
        np.array([[1, 1, 4], [1, ELSEWHERE, 1], [3, 3, 3]] * 2), ALL_VALID),
    "padding_tokens_touch_nothing": (
        (np.arange(TOKENS)[:, None] + np.arange(3)[None, :]) % E,
        np.arange(TOKENS) % 3 != 1),
}


def case(name, seed=0):
    """A routing laid out by hand: ``sizes [E]``, and per buffer row its
    token id and routing weight (junk in the padding rows, which must not
    show and must not be read), per (token, choice) its buffer row (-1:
    not held), the routing weights, and both calls' matrices."""
    experts, valid = ROUTINGS[name]
    T, k = experts.shape
    rng = np.random.default_rng(seed)
    weights = rng.uniform(0.1, 1.0, (T, k)).astype(np.float32)
    held = (experts < E) & valid[:, None]
    sizes = np.bincount(experts[held], minlength=E).astype(np.int32)
    tiles = -(-sizes // TILE)
    starts = (np.cumsum(tiles) - tiles) * TILE
    n = buffer_rows(T * min(k, E), E, TILE)
    rows = np.full(n, JUNK, np.int32)
    scale = np.full(n, np.nan, np.float32)
    dest = np.full((T, k), -1)
    fill = np.zeros(E, int)
    for t in range(T):
        for j in range(k):
            if held[t, j]:
                e = experts[t, j]
                dest[t, j] = r = starts[e] + fill[e]
                fill[e] += 1
                rows[r], scale[r] = t, weights[t, j]
    ks = jax.random.split(jax.random.key(seed), 5)
    return dict(
        sizes=jnp.asarray(sizes), rows=jnp.asarray(rows),
        scale=jnp.asarray(scale), dest=dest, weights=weights, starts=starts,
        u=jax.random.normal(ks[0], (T, K)),
        w=jax.random.normal(ks[1], (LAYERS, E, K, N_OUT)) / 4,
        up=jax.random.normal(ks[2], (LAYERS, E, K, N_OUT)) / 4,
        h=jax.random.normal(ks[3], (n, N_OUT)),
        down=jax.random.normal(ks[4], (LAYERS, E, N_OUT, K)) / 4)


def by_hand(x, c, w, up, layer):
    """Group by group; rows outside every group stay ``nan`` (a call
    writes zeros in a visited tile's padding and nothing past the last
    group)."""
    out = np.full((x.shape[0], w.shape[-1]), np.nan, np.float32)
    x = np.asarray(x, np.float64)
    for e, (s, n) in enumerate(zip(c["starts"], np.asarray(c["sizes"]))):
        rows = x[s:s + n]
        y = rows @ np.asarray(w[layer, e], np.float64)
        if up is not None:
            y = y / (1 + np.exp(-y)) * (rows @ np.asarray(up[layer, e],
                                                          np.float64))
        out[s:s + n] = y
    return out


@pytest.mark.parametrize("swiglu", [False, True])
@pytest.mark.parametrize("name", ROUTINGS)
@pytest.mark.parametrize("path", PATHS)
def test_rows_fetched_by_id_times_their_groups_matrix(path, name, swiglu):
    """``expert_matmul(u, rows, ...)`` is the product over ``take(u,
    rows)``: nobody builds that buffer, and the padding rows' ids are not
    read."""
    c = case(name)
    up = c["up"] if swiglu else None
    got = np.asarray(jax.jit(lambda *a: expert_matmul(
        *a, tile=TILE, layer=jnp.int32(1), path=path))(
        c["u"], c["rows"], c["sizes"], c["w"], up))
    x = np.asarray(c["u"])[np.minimum(np.asarray(c["rows"]), TOKENS - 1)]
    want = by_hand(x, c, c["w"], up, 1)
    real = ~np.isnan(want[:, 0])
    assert real.sum() == int(c["sizes"].sum())
    np.testing.assert_allclose(got[real], want[real], atol=2e-4)
    # a visited tile's padding rows are zeros, whatever the buffer held
    _, tiles = (np.asarray(a) for a in expert_layout(c["sizes"], TILE))
    for s, t, n in zip(c["starts"], tiles, np.asarray(c["sizes"])):
        assert not got[s + n:s + t * TILE].any()


@pytest.mark.parametrize("name", ROUTINGS)
@pytest.mark.parametrize("path", PATHS)
def test_weighted_results_are_added_into_their_tokens_rows(path, name):
    """``expert_matmul_add`` against the formulation it replaced: every
    pair's result gathered back from the buffer, the choices held
    elsewhere masked, the weighted sum over a token's choices.  A token
    without a pair, and every token when nobody has one, gets zeros; junk
    in the padding rows (``nan`` weights, ids out of range) does not show."""
    c = case(name, seed=1)
    got = np.asarray(jax.jit(lambda *a: expert_matmul_add(
        *a, tokens=TOKENS, tile=TILE, layer=jnp.int32(1), path=path))(
        c["h"], c["rows"], c["scale"], c["sizes"], c["down"]))
    out = by_hand(c["h"], c, c["down"], None, 1)
    held = c["dest"] >= 0
    gathered = np.where(held[..., None], out[np.maximum(c["dest"], 0)], 0.0)
    want = (gathered * c["weights"][..., None]).sum(axis=1)
    assert got.shape == (TOKENS, K) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert not got[~held.any(axis=1)].any()


@pytest.mark.parametrize("call", ["fetch", "add"])
def test_two_column_blocks_give_one_blocks_result(call, monkeypatch):
    """The list walks a column block at a time; with the matrices in two
    blocks every column is what it is in one (the weighted add zeroes and
    fills each block of its accumulator in turn)."""
    # (the package's attribute of that name is the function)
    module = importlib.import_module("paddle_tpu.kernels.expert_matmul")
    c = case("sizes0", seed=2)
    wide = jax.random.normal(jax.random.key(9), (E, N_OUT, 256)) / 4

    def run():
        if call == "fetch":
            return jax.jit(lambda: expert_matmul(
                c["u"], c["rows"], c["sizes"], c["w"][0], c["up"][0],
                tile=TILE, path=dispatch.INTERPRET))()
        return jax.jit(lambda: expert_matmul_add(
            c["h"], c["rows"], c["scale"], c["sizes"], wide, tokens=TOKENS,
            tile=TILE, path=dispatch.INTERPRET))()

    # the accumulator's own limit narrows the block as the tokens grow
    assert module._column_block(1024, 3072, 2, acc_rows=64) == 3072
    assert module._column_block(1024, 3072, 2, acc_rows=1088) == 1536
    one = np.asarray(run())
    depth = K if call == "fetch" else N_OUT
    monkeypatch.setattr(module, "_BLOCK_BYTES", depth * 128 * 4)
    assert module._column_block(depth, 256, 4) == 128
    two = np.asarray(run())
    real = ~np.isnan(by_hand(c["h"], c, c["down"], None, 0)[:, 0])
    rows = real if call == "fetch" else slice(None)
    np.testing.assert_allclose(one[rows], two[rows], atol=1e-4)


def test_one_layers_matrices_without_a_layer():
    c = case("sizes0", seed=1)
    u, rows, scale, sizes, h = (c[n] for n in ("u", "rows", "scale", "sizes",
                                               "h"))
    for path in PATHS:
        a = expert_matmul(u, rows, sizes, c["w"][0], c["up"][0], tile=TILE,
                          path=path)
        b = expert_matmul(u, rows, sizes, c["w"], c["up"], tile=TILE,
                          layer=0, path=path)
        real = np.asarray(rows) != JUNK
        np.testing.assert_array_equal(np.asarray(a)[real],
                                      np.asarray(b)[real])
        a = expert_matmul_add(h, rows, scale, sizes, c["down"][1],
                              tokens=TOKENS, tile=TILE, path=path)
        b = expert_matmul_add(h, rows, scale, sizes, c["down"],
                              tokens=TOKENS, tile=TILE, layer=1, path=path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="layer"):
        expert_matmul(u, rows, sizes, c["w"], tile=TILE)
    with pytest.raises(ValueError, match="layer"):
        expert_matmul_add(h, rows, scale, sizes, c["down"], tokens=TOKENS,
                          tile=TILE)
    with pytest.raises(ValueError, match="whole tiles"):
        expert_matmul(u, rows[:-1], sizes, c["w"][0], tile=TILE)
    with pytest.raises(ValueError, match="pieces"):
        expert_matmul(u[:, :-1], rows, sizes, c["w"][0, :, :-1], tile=TILE,
                      path=dispatch.INTERPRET)


@pytest.mark.parametrize("sizes", SIZES)
def test_an_experts_weights_are_fetched_once_and_only_if_it_has_a_pair(
        sizes):
    """The work list orders items column block, then the visited tiles in
    buffer order, which is expert order: the weight block's index changes
    once per (block, expert with a pair), so Pallas fetches every such
    block once and no other; a token's results arrive in ascending expert
    order; with no pair at all one item a block still writes the output."""
    sizes = jnp.asarray(sizes, jnp.int32)
    blocks, n_tiles = 3, buffer_rows(int(sizes.sum()), E, TILE) // TILE
    e, blk, tile, n, live = (np.asarray(a) for a in _items(
        sizes, TILE, blocks, n_tiles))
    n = int(n[0])
    starts, tiles = (np.asarray(a) for a in expert_layout(sizes, TILE))
    visited = int(tiles.sum())
    assert n == blocks * max(visited, 1)
    assert int(visited_rows(sizes, TILE)) == visited * TILE
    keys = list(zip(blk[:n], e[:n]))
    fetches = 1 + sum(a != b for a, b in zip(keys, keys[1:]))
    assert fetches == blocks * max(int((np.asarray(sizes) > 0).sum()), 1)
    # every (tile, block) of the output is written exactly once, the tiles
    # of a block in buffer order and so in expert order
    assert len({(t, b) for t, b in zip(tile[:n], blk[:n])}) == n
    per = max(visited, 1)
    assert tile[:n].tolist() == list(range(per)) * blocks
    assert blk[:n].tolist() == sorted(blk[:n].tolist())
    assert all(np.diff(e[b * per:(b + 1) * per]).min(initial=0) >= 0
               for b in range(blocks))
    # the rows of each buffer tile that are pairs
    want = np.zeros(n_tiles, int)
    for s, t, size in zip(starts, tiles, np.asarray(sizes)):
        for j in range(t):
            want[s // TILE + j] = min(TILE, size - j * TILE)
    assert live.tolist() == want.tolist()
    assert live[:visited].sum() == int(sizes.sum()) and (
        visited or not live.any())


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

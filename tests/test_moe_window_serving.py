"""The sparse-expert decoder with sliding-window layers beside full ones,
served through the one ``Engine``, against the plain reference in
``benchmark/reference_moe_window.py``, at a tiny size on seeded weights
(both layer kinds, unequal head counts, a dense first layer, 16 experts of
which this holder has 4); the cache manager with two groups of page pools,
the window group's pages given back as the window passes; the shares of a
four-chip deployment adding up to the uncut model; and the counters.

Tolerance of the parity tests: float32 weights and the kernels' ``jnp``
paths on the CPU, so the program and the reference differ only in the
order of float32 sums: logits (deviation about 1) agree within 2e-5 at
every decoded position.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_moe_window as rm  # noqa: E402
from benchmark import run as bench  # noqa: E402
from paddle_tpu.kernels import dispatch  # noqa: E402
from paddle_tpu.models.moe_window import (  # noqa: E402
    DENSE, FULL, MOE_WINDOW_CONFIGS, SLIDING, SPARSE, MoEWindowConfig,
    moe_window_init, moe_window_ragged_step, moe_window_state_spec)
from paddle_tpu.models.ragged import (RaggedBatch,  # noqa: E402
                                      WindowRaggedBatch)
from paddle_tpu.serving import Engine, SamplingParams  # noqa: E402
from paddle_tpu.serving.kv_cache import (PagedKVCache,  # noqa: E402
                                         window_pages_per_row)
from paddle_tpu.serving.model import MoEWindowServed, as_served  # noqa: E402

TOL = 2e-5
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "tiny-moe-window.json")))
WINDOW = CONFIG["sliding_window"]
RUNNER = bench.load_module("runners", "serve_moe_window")


def program_config(config=CONFIG, fault=None):
    """The program's config as the benchmark's runner builds it from the
    configuration file, in float32."""
    return RUNNER.moe_window_config(dict(config, dtype="float32"), fault)


@pytest.fixture(scope="module")
def tiny():
    params = rm.weights(CONFIG, 7, jnp.float32)
    return program_config(), params, rm.Model(CONFIG, "float32")


def serve(cfg, params, prompts, new_tokens, each_step=None, **engine):
    """Drive the engine to the end; per request the logits row each of its
    tokens was sampled from (read from ``Engine.step_logits`` at the moment
    the per-row hook is handed the row's id), and the engine."""
    eng = Engine(cfg, params, **engine)
    seen, sound = {}, eng._sample_token

    def spy(token, req):
        row = eng.step_logits[eng._slots.index(req)]
        seen.setdefault(req.id, []).append(np.asarray(row, np.float32))
        return sound(token, req)

    eng._sample_token = spy
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, new_tokens)]
    while eng.has_work():
        eng.step()
        eng.cache.check_integrity()
        if each_step:
            each_step(eng)
    return reqs, seen, eng


def worst_gap(model, params, reqs, seen):
    worst = 0.0
    for r in reqs:
        ref = np.asarray(model.forward_logits(
            params, np.asarray(r.tokens, np.int32), len(r.prompt)))
        # a preempted request was served twice: its last pass is the one
        got = np.stack(seen[r.id][-len(r.output):])
        worst = max(worst, float(np.abs(got - ref[:len(got)]).max()))
    return worst


def test_chunked_prefill_and_decode_through_two_page_tables(tiny):
    """Prompts in chunks of 16 (the window's width) beside decode rows,
    through the full layers' table and the window layers', against no cache
    at all; contexts run to several windows, and a fourth request takes
    over the row and the pages of a finished one."""
    cfg, params, model = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (70, 9, 33, 120)]
    new = (12, 30, 8, 20)
    reqs, seen, eng = serve(cfg, params, prompts, new, page_size=4,
                            num_pages=128, max_batch_size=3, chunk_len=16)
    assert [len(r.output) for r in reqs] == list(new)
    assert worst_gap(model, params, reqs, seen) < TOL
    m = eng.metrics
    assert m.requests_preempted.value == 0 and not eng.prefix_cache
    # per layer: a full layer reads every position, a window layer 16
    assert 0 < m.attention_selected.value < m.attention_context.value
    one = sum(int(np.minimum(np.arange(1, len(r.tokens)), WINDOW).sum())
              for r in reqs)
    assert m.attention_selected.value == one
    assert m.window_pages_released.value > 0
    assert m.pages_in_use_window.value == m.pages_in_use_full.value == 0


def test_a_context_many_windows_long_holds_a_bounded_number_of_pages(tiny):
    """240 positions are 15 windows; the window pools never hold more than
    ``window_pages_per_row`` pages a row, the full pools hold them all, and
    every page released is free again at the end."""
    cfg, params, model = tiny
    page, chunk = 4, 16
    per_row = window_pages_per_row(WINDOW, page, chunk)
    assert per_row == 9
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist() for n in (200, 150)]
    held = []

    def watch(eng):
        cache = eng.cache
        for sid in cache.seq_ids():
            assert cache.window_pages_held(sid) <= per_row
        held.append((cache.num_used_window_pages, cache.num_used_pages))

    reqs, seen, eng = serve(cfg, params, prompts, (40, 60), watch,
                            page_size=page, num_pages=128, max_batch_size=2,
                            chunk_len=chunk)
    assert eng.cache.num_window_pages == 2 * per_row
    assert max(w for w, _ in held) <= 2 * per_row
    assert max(f for _, f in held) >= 100        # of (240 + 210) / 4
    # decoding far past the window: 16 positions and the page they began
    # in, a row
    assert held[-3][0] <= WINDOW // page + 1
    assert worst_gap(model, params, reqs, seen) < TOL
    cache = eng.cache
    assert cache.num_used_window_pages == 0 == cache.num_used_pages
    assert cache.window_pages_released >= (240 + 210 - 2 * WINDOW) // page - 2


def test_a_row_alone_and_among_63_batch_mates_has_the_same_logits(tiny):
    """Dropless: no capacity, so what the other 63 rows route to the held
    experts cannot push a token out.  One request served alone, then the
    same request in a batch of 64 rows."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 512, 21).tolist()
    knobs = dict(page_size=4, num_pages=1024, max_batch_size=64,
                 chunk_len=16)
    (alone,), seen_alone, _ = serve(cfg, params, [prompt], (6,), **knobs)
    mates = [rng.integers(0, 512, int(n)).tolist()
             for n in rng.integers(3, 40, 63)]
    reqs, seen, eng = serve(cfg, params, [prompt] + mates, [6] * 64,
                            **knobs)
    assert eng.metrics.requests_preempted.value == 0
    assert reqs[0].tokens == alone.tokens
    np.testing.assert_allclose(np.stack(seen[reqs[0].id]),
                               np.stack(seen_alone[alone.id]), atol=2e-6)


def test_a_preempted_request_gets_its_window_pages_again(tiny):
    """A full pool too small for all three: the youngest is preempted (both
    its tables go), recomputed later, and still equals the reference."""
    cfg, params, model = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).tolist() for n in (40, 44, 36)]
    reqs, seen, eng = serve(cfg, params, prompts, (30, 30, 30),
                            page_size=4, num_pages=44, max_batch_size=3,
                            chunk_len=16)
    assert eng.metrics.requests_preempted.value > 0
    assert all(len(r.output) == 30 for r in reqs)
    assert worst_gap(model, params, reqs, seen) < TOL


def _zeroed(params, group, name):
    return dict(params, **{group: dict(params[group], **{
        name: jnp.zeros_like(params[group][name])})})


@pytest.mark.parametrize("gone", [
    (FULL, "o_w"), (SLIDING, "o_w"), (DENSE, "down_w"),
    (SPARSE, "shared_down_w"), (SPARSE, "down_w"), (SLIDING, "g_w"),
    (SLIDING, "q_w"), (FULL, "q_w"), (SPARSE, "router_w"), "window_511",
    "drop_pair"])
def test_no_branch_and_no_mechanism_is_idle(tiny, gone):
    """At the deviations the configuration file assumes, each branch (both
    attention kinds, the dense layer, the shared expert, the routed
    experts), the head gate, the scores of both attention kinds, the
    router's choice, the window's edge and a token's last choice each move
    the logits by far more than the tolerance: the parity above would see
    any of them gone."""
    cfg, params, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, 60).tolist()]
    served = params
    if isinstance(gone, str):
        cfg = program_config(fault=gone)
    else:
        served = _zeroed(params, *gone)
    reqs, seen, _ = serve(cfg, served, prompts, (8,), page_size=4,
                          num_pages=64, max_batch_size=2, chunk_len=16)
    deviation = float(np.std(np.stack(seen[reqs[0].id])))
    assert 0.5 < deviation < 2
    assert worst_gap(model, params, reqs, seen) > max(1000 * TOL,
                                                      0.02 * deviation)


def test_the_router_is_neither_a_tie_nor_one_expert(tiny):
    """The assumed deviations give a top-k that means something: over 400
    tokens every held expert is chosen, none by more than three times its
    share, and the pairs the program counted are the reference's."""
    cfg, params, model = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, 100).tolist() for _ in range(4)]
    reqs, _, eng = serve(cfg, params, prompts, (1,) * 4, page_size=4,
                         num_pages=128, max_batch_size=4, chunk_len=16)
    s = model.s
    counts = np.zeros(s.held, int)
    for r in reqs:
        row = np.zeros(256, np.int32)
        row[:100] = r.prompt
        h = model.embed(params["wte"], row)
        seen = dict.fromkeys((FULL, SLIDING, DENSE, SPARSE), 0)
        for kind, mlp in zip(s.kinds, s.mlps):
            i, j = seen[kind], seen[mlp]
            seen[kind] += 1
            seen[mlp] += 1
            h = model.attention[kind](rm._at(params[kind], i), h)
            if mlp == DENSE:
                h = model.dense(rm._at(params[DENSE], j), h)
                continue
            p = rm._at(params[SPARSE], j)
            u, shared, w, idx = model.route(p, h)
            local = np.asarray(idx)[:100] - s.first
            counts += np.bincount(local[(local >= 0) & (local < s.held)],
                                  minlength=s.held)
            rows, w = model.held_rows(w, idx, 100)
            h = h + shared + model.routed(p, u, rows, w)
    m = eng.metrics
    assert m.expert_pairs.value == counts.sum()
    share = 4 * 100 * 3 * s.top_k / s.E          # pairs an expert expects
    assert counts.min() > share / 3 and counts.max() < 3 * share
    assert 1 <= m.expert_rows_max.value < 4
    assert 0 < m.expert_weight_reads.value <= 3 * s.held * (
        m.steps_ahead.value + m.steps_not_ahead.value)


def test_tile_rows_are_each_layers_groups_rounded_up_to_whole_tiles(
        tiny, monkeypatch):
    """``serving_expert_tile_rows_total`` is, over the sparse layers of
    every step, each held expert's pairs rounded up to whole tiles of the
    step's tile: what the expert kernels visit.  Never under the pairs."""
    import paddle_tpu.models.moe_window as mw

    cfg, params, _ = tiny
    seen, sound = [], mw.dropless_experts

    def spy(*args, tile, **kw):
        y, sizes = sound(*args, tile=tile, **kw)
        jax.debug.callback(
            lambda s: seen.append((tile, np.asarray(s))), sizes)
        return y, sizes

    monkeypatch.setattr(mw, "dropless_experts", spy)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, n).tolist() for n in (40, 7)]
    _, _, eng = serve(cfg, params, prompts, (3, 5), page_size=4,
                      num_pages=64, max_batch_size=2, chunk_len=16)
    jax.effects_barrier()
    m = eng.metrics
    tile = mw.expert_tile(17, cfg.top_k, cfg.num_experts, jnp.float32)
    assert seen and {t for t, _ in seen} == {tile}
    assert m.expert_pairs.value == sum(int(s.sum()) for _, s in seen) > 0
    assert m.expert_tile_rows.value == sum(
        int((-(-s // tile)).sum()) * tile for _, s in seen)
    assert m.expert_tile_rows.value >= m.expert_pairs.value
    assert MoEWindowServed.step_stats[-1] == "expert_tile_rows"


def test_the_pallas_kernels_under_the_interpreter_give_the_jnp_paths_step():
    """One step of the model with both kernels (the window's lower edge,
    the grouped expert product) under the Pallas interpreter against the
    same step on their ``jnp`` paths: a chunk, a chunk of two, decode rows
    past the window and an idle row."""
    cfg = program_config()
    params = rm.weights(CONFIG, 3, jnp.float32)
    B, T, page, pages = 5, 24, 8, 16
    spec = moe_window_state_spec(cfg, num_pages=pages, page_size=page,
                                 max_batch_size=B, num_window_pages=pages)
    rng = np.random.default_rng(0)
    state = [jnp.asarray(rng.standard_normal(s) * 0.1, d)
             for _, s, d, _ in spec]
    q = [13, 2, 1, 0, 1]
    ctx = [13, 10, 23, 5, 24]
    tokens, rows, slots = (np.zeros(T, np.int32), np.full(T, B, np.int32),
                           np.zeros(T, np.int32))
    off = 0
    for b, n in enumerate(q):
        tokens[off:off + n] = rng.integers(0, 512, n)
        rows[off:off + n], slots[off:off + n] = b, np.arange(n)
        off += n
    tables = np.arange(B * 3, dtype=np.int32).reshape(B, 3)
    batch = WindowRaggedBatch(*(jnp.asarray(a) for a in (
        tokens, rows, slots, np.asarray(q, np.int32),
        np.asarray(ctx, np.int32), tables, tables[::-1].copy())))
    run = lambda path, tile=None: moe_window_ragged_step(
        cfg, params, batch, *state, max_q=16, attn_path=path,
        query_tile=tile)
    ref, got = run(dispatch.REFERENCE), run(dispatch.INTERPRET)
    # the chunk of 13 in two tiles of 8 query slots: the same step
    for path in (dispatch.REFERENCE, dispatch.INTERPRET):
        np.testing.assert_allclose(np.asarray(run(path, 8)[0])[[0, 1, 2, 4]],
                                   np.asarray(ref[0])[[0, 1, 2, 4]],
                                   atol=2e-5)
    live = np.asarray(q) > 0
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(ref[0])[live], atol=2e-5)
    for a, b, (name, *_) in zip(got[1:5], ref[1:5], spec):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)
    assert got[5].tolist() == ref[5].tolist() and int(got[5][0]) > 0


def test_prefix_reuse_and_mesh_are_refused(tiny):
    cfg, params, _ = tiny
    assert isinstance(as_served(cfg), MoEWindowServed)
    with pytest.raises(ValueError, match="window pages were given back"):
        Engine(cfg, params, page_size=4, num_pages=32, prefix_cache=True)
    eng = Engine(cfg, params, page_size=4, num_pages=32)
    assert eng.prefix_cache is False and eng.window == WINDOW
    with pytest.raises(ValueError, match="admitted cold"):
        eng.cache.allocate_prefixed(0, [1, 2, 3], 4)
    with pytest.raises(NotImplementedError, match="sparse experts"):
        Engine(cfg, params, page_size=4, num_pages=32, mesh=object())
    with pytest.raises(ValueError, match="num_window_pages"):
        Engine(cfg, params, page_size=4, num_pages=32, chunk_len=16,
               num_window_pages=3)


def test_config_refuses_what_the_step_cannot_run():
    with pytest.raises(ValueError, match="name the same layers"):
        MoEWindowConfig(heads_per_layer=(4, 6))
    with pytest.raises(ValueError, match="differ in their heads"):
        MoEWindowConfig(heads_per_layer=(4, 6, 6, 8))
    with pytest.raises(ValueError, match="num_kv_heads"):
        MoEWindowConfig(heads_per_layer=(4, 5, 5, 5))
    with pytest.raises(ValueError, match="experts_held"):
        MoEWindowConfig(experts_held=(14, 4))
    big = MOE_WINDOW_CONFIGS["laguna-s-2.1-8l"]
    assert big == RUNNER.moe_window_config(bench.load_json(
        bench.HERE, "configs", "laguna-s-2.1-8l.json"))
    shapes = jax.eval_shape(lambda: moe_window_init(big))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 4_683_660_288 == rm.Sizes(bench.load_json(
        bench.HERE, "configs", "laguna-s-2.1-8l.json")).n_params()   # 9.37 GB


# ------------------------------------------------------------ cache manager


def window_cache(rows=3, pages=16, window_pages=8):
    cfg = MOE_WINDOW_CONFIGS["tiny"]
    return PagedKVCache(
        num_pages=pages, page_size=4, max_seq_len=cfg.max_seq_len,
        state=moe_window_state_spec(cfg, num_pages=pages, page_size=4,
                                    max_batch_size=rows,
                                    num_window_pages=window_pages))


def test_two_groups_of_pools_with_a_free_list_each():
    cache = window_cache()
    assert cache.num_window_pages == 8 and cache.num_pages == 16
    assert cache.allocate("a", 10)              # 3 pages of each group
    assert cache.num_used_pages == 3 == cache.num_used_window_pages
    assert cache.extend("a", 20)                # 5 of each
    # positions 0..11 are behind the window: three window pages go back,
    # their entries stay in the table, the full table is untouched
    before = cache.window_page_table("a")
    assert cache.release_window("a", 12) == 3
    assert cache.release_window("a", 12) == 0 == cache.release_window("a", 5)
    assert cache.window_page_table("a") == before
    assert cache.window_pages_held("a") == 2 and cache.num_used_pages == 5
    assert cache.num_used_window_pages == 2
    cache.check_integrity()
    # the window group is the scarcer one here: 6 free, 8 wanted
    assert not cache.allocate("b", 32)
    assert cache.num_used_pages == 5 and "b" not in cache.seq_ids()
    assert cache.allocate("b", 24)
    assert cache.num_used_window_pages == 8
    # an extension that only the window group cannot cover takes nothing
    assert not cache.extend("a", 24)
    assert cache.num_used_pages == 5 + 6
    cache.check_integrity()
    assert cache.release_window("b", 16) == 4 and cache.extend("a", 24)
    cache.free("a")
    cache.free("b")
    cache.check_integrity()
    assert cache.num_used_pages == 0 == cache.num_used_window_pages
    assert cache.window_pages_released == 7
    cache.allocate("c", 5)
    cache.reset()
    cache.check_integrity()
    assert cache.num_used_window_pages == 0


def test_a_model_without_window_layers_has_no_second_table():
    from paddle_tpu.models.gpt import GPT_CONFIGS

    eng = Engine(GPT_CONFIGS["tiny"], page_size=4, num_pages=32,
                 max_batch_size=2)
    assert eng.window is None and eng.cache.num_window_pages == 0
    eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=2))
    eng._try_admit()
    plan = eng._ensure_capacity()
    batch, _ = eng._pack(plan)
    assert type(batch) is RaggedBatch and len(batch) == 6


# ------------------------------------- the share and the model it is cut from


def whole_config():
    """The tiny model uncut: all 16 experts held."""
    return dict(CONFIG, num_experts=16, experts_held=[0, 16])


def test_four_chips_shares_add_up_to_the_uncut_reference():
    """A layer shared by four chips: experts ``[0, 4)``, ``[4, 8)``, ... of
    a sparse layer, two of eight key/value heads (with their query heads)
    of an attention layer, and a quarter of the vocabulary's rows.  The
    routed parts of the four shares, with the shared expert and the residual
    counted once, are the uncut layer; the four shares' heads, each through
    its rows of ``W_o``, are the uncut attention; the four slices' logits
    side by side are the uncut head's."""
    whole = whole_config()
    params = rm.weights(whole, 11, jnp.float32)
    model = rm.Model(whole, "float32")
    s = model.s
    rng = np.random.default_rng(6)
    n = 48
    h = jnp.asarray(rng.standard_normal((n, s.D)), jnp.float32)

    # the expert layer
    p = rm._at(params[SPARSE], 0)
    u, shared, w, idx = model.route(p, h)
    rows, wr = model.held_rows(w, idx, n)
    uncut = h + shared + model.routed(p, u, rows, wr)
    total = h + shared
    for first in range(0, 16, 4):
        share = dict(whole, num_experts=4, experts_held=[first, 4])
        part = rm.Model(share, "float32")
        cut = dict(p, **{k: p[k][first:first + 4]
                         for k in ("gate_w", "up_w", "down_w")})
        # the router's weights whole on every chip: the same choices
        u_c, shared_c, w_c, idx_c = part.route(cut, h)
        np.testing.assert_array_equal(np.asarray(idx_c), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(shared_c),
                                      np.asarray(shared))
        rows_c, wr_c = part.held_rows(w_c, idx_c, n)
        total = total + part.routed(cut, u_c, rows_c, wr_c)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5)

    # an attention layer, by key/value head: a chip's heads are a slice of
    # the columns of W_q, W_k, W_v, W_g and of the rows of W_o
    wide = dict(whole, num_key_value_heads=8,
                num_attention_heads_per_layer=[16, 24, 24, 24])
    wparams = rm.weights(wide, 12, jnp.float32)
    wmodel = rm.Model(wide, "float32")
    for kind in (FULL, SLIDING):
        pa = rm._at(wparams[kind], 0)
        H = wmodel.s.heads_of(kind)
        G, hd = H // 8, wmodel.s.hd
        uncut = wmodel.attention[kind](pa, h) - h
        share = dict(wide, num_key_value_heads=2,
                     num_attention_heads_per_layer=[4, 6, 6, 6])
        part = rm.Model(share, "float32")
        total = jnp.zeros_like(h)
        for c in range(4):
            kv = slice(c * 2 * hd, (c + 1) * 2 * hd)
            qh = slice(c * 2 * G, (c + 1) * 2 * G)
            qc = slice(c * 2 * G * hd, (c + 1) * 2 * G * hd)
            cut = {"ln1": pa["ln1"], "q_w": pa["q_w"][:, qc],
                   "k_w": pa["k_w"][:, kv], "v_w": pa["v_w"][:, kv],
                   "g_w": pa["g_w"][:, qh], "o_w": pa["o_w"][qc]}
            total = total + part.attention[kind](cut, h) - h
        np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                                   atol=2e-5)

    # the head, by rows of the vocabulary
    hp = jnp.pad(h, ((0, 256), (0, 0)))
    uncut = model.head(params["norm_f"], params["lm_head"], hp, np.int32(0))
    quarter = s.Vp // 4
    side = [model.head(params["norm_f"],
                       params["lm_head"][:, c * quarter:(c + 1) * quarter],
                       hp, np.int32(0)) for c in range(4)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(side, axis=1)),
                               np.asarray(uncut), atol=1e-5)


def test_the_program_on_a_share_is_the_reference_on_that_share(tiny):
    """The program holds experts [4, 8) of 16 (``experts_held`` with a
    first that is not 0): its routed part is the reference's on the same
    share, which the test above ties to the uncut layer."""
    cfg, params, model = tiny
    assert cfg.experts_held == (4, 4) and cfg.num_experts == 16
    assert model.s.first == 4 and model.s.E == 16
    assert params[SPARSE]["router_w"].shape[-1] == 16
    assert params[SPARSE]["gate_w"].shape[1] == 4

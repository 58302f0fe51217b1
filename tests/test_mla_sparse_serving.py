"""Latent attention in every layer — full layers reading the positions a
learned indexer chooses token by token, sliding layers their window — with
sigmoid-routed experts and a shared one, served through the one ``Engine``,
against the plain reference in ``benchmark/reference_mla_sparse.py``, at a
tiny size on seeded weights (two full layers and three sliding ones, the
first layer dense, 4 of 16 experts held, an index top-k of 24 that prunes
at the prompts' lengths); the absorbed latent kernel in interpret mode
against its ``jnp`` oracle; the absorbed product against the expanded
one; the indexer's choice against the reference's exact top-k; the window
pages given back; the program in bfloat16 within rounding of the float32
reference, where each planted fault is not; and the expert layer's share
of the uncut layer.

Tolerance of the parity tests: float32 weights and the kernels' ``jnp``
paths on the CPU, so the program and the reference differ in the order of
float32 sums and in the absorption (``q W_UK^T . c_kv`` for ``q . c_kv
W_UK``): logits (deviation about 1.4) agree within 1e-4 at every decoded
position.
"""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_mla_sparse as rm  # noqa: E402
from benchmark import run as bench  # noqa: E402
from paddle_tpu.kernels import dispatch  # noqa: E402
from paddle_tpu.kernels import latent_attention as la  # noqa: E402
from paddle_tpu.models import mla_sparse as ms  # noqa: E402
from paddle_tpu.models.experts import (dropless_experts,  # noqa: E402
                                       route_top_k)
from paddle_tpu.models.ragged import RaggedView, WindowRaggedBatch  # noqa
from paddle_tpu.serving import Engine, SamplingParams  # noqa: E402
from paddle_tpu.serving.model import MLASparseServed, as_served  # noqa

TOL = 1e-4
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "tiny-mla-sparse.json")))
RUNNER = bench.load_module("runners", "serve_mla_sparse")


def program_config(config=CONFIG):
    """The program's config as the benchmark's runner builds it from the
    configuration file, in float32."""
    return RUNNER.mla_sparse_config(dict(config, dtype="float32"))


def plant(monkeypatch, fault):
    """The program with one of the reference's faults written in: a full
    layer's list of positions replaced by every position of the context
    (``no_selection``) or by the newest ``index_topk`` (``newest``), or
    the latents' rescale left out (``no_rescale``)."""
    if fault == "no_rescale":
        monkeypatch.setattr(ms.MLASparseConfig, "scales",
                            lambda self, kind: (1.0, 1.0))
        return
    sound = ms._selected

    def selected(scores, valid, pos, k, page_size):
        _, _, pages = sound(scores, valid, pos, k, page_size)
        k = scores.shape[1] if fault == "no_selection" else k
        newest = jnp.maximum(pos[:, None] - jnp.arange(k)[None, :], 0)
        counts = jnp.where(valid, jnp.minimum(pos + 1, k), 0)
        return newest.astype(jnp.int32), counts.astype(jnp.int32), pages

    monkeypatch.setattr(ms, "_selected", selected)


@pytest.fixture(scope="module")
def tiny():
    params = rm.weights(CONFIG, 7, jnp.float32)
    return program_config(), params, rm.Model(CONFIG, "float32")


def serve(cfg, params, prompts, new_tokens, **engine):
    """Drive the engine to the end; per request the logits row each of its
    tokens was sampled from, and the engine."""
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
    return reqs, seen, eng


def worst_gap(model, params, reqs, seen):
    worst = 0.0
    for r in reqs:
        ref = np.asarray(model.forward_logits(
            params, np.asarray(r.tokens, np.int32), len(r.prompt)))
        got = np.stack(seen[r.id][-len(r.output):])
        worst = max(worst, float(np.abs(got - ref[:len(got)]).max()))
    return worst


@pytest.mark.parametrize("chunk_len", [16, 5])
def test_prefill_and_decode_through_the_engine_are_the_reference(
        tiny, chunk_len):
    """Prompts in chunks beside decode rows, against no cache at all, at
    contexts past the index top-k (24) and the window (9): the full layers
    read the indexer's choice, the sliding ones their window; a fourth
    request takes over the row and the pages of a finished one."""
    cfg, params, model = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (70, 9, 33, 120)]
    new = (12, 30, 8, 20)
    reqs, seen, eng = serve(cfg, params, prompts, new, page_size=4,
                            num_pages=128, max_batch_size=3,
                            chunk_len=chunk_len)
    assert [len(r.output) for r in reqs] == list(new)
    assert worst_gap(model, params, reqs, seen) < TOL
    m = eng.metrics
    assert not eng.prefix_cache
    assert m.expert_pairs.value > 0 and m.expert_weight_reads.value > 0
    # every full-layer token read min(24, context) rows, a sliding one
    # min(9, context): the counters the device kept are that arithmetic
    assert m.latent_rows_selected.value + m.latent_rows_window.value \
        == m.attention_selected.value
    assert 0 < m.latent_row_pages.value * 4 \
        and m.latent_rows_selected.value <= m.latent_row_pages.value * 4
    assert m.attention_selected.value < m.attention_context.value


def test_window_pages_are_given_back_and_a_row_holds_few(tiny):
    """The sliding layers' latent pools: a 120-token prompt and its decode
    hold no more window pages than the window and a chunk reach, the pages
    behind the window go back to the pool, and the result is the
    reference's."""
    cfg, params, model = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, 120).tolist()]
    eng = Engine(cfg, params, page_size=4, num_pages=64, max_batch_size=1,
                 chunk_len=8)
    seen, sound = {}, eng._sample_token

    def spy(token, req):
        seen.setdefault(req.id, []).append(
            np.asarray(eng.step_logits[0], np.float32))
        return sound(token, req)

    eng._sample_token = spy
    req = eng.add_request(prompts[0], SamplingParams(max_new_tokens=10))
    held, full = [], []
    while eng.has_work():
        eng.step()
        held.append(eng.cache.num_used_window_pages)
        full.append(eng.cache.num_used_pages)
    per_row = -(-(cfg.window + 8) // 4) + 1
    assert max(held) <= per_row < max(full) == -(-130 // 4)
    assert eng.metrics.window_pages_released.value >= (130 - 9 - 8) // 4
    assert worst_gap(model, params, [req], seen) < TOL


@pytest.mark.parametrize("listed", [True, False])
def test_the_kernel_in_interpret_mode_is_its_reference(monkeypatch, listed):
    """Rows fetched one by one by position through the page table, blocks
    of 16 rows (so a query spans several, the last partly), a query that
    reads nothing, a page pool of float32 slabs: the Mosaic body under the
    interpreter against the ``jnp`` oracle."""
    monkeypatch.setattr(la, "_ROWS", 16)
    rng = np.random.default_rng(0)
    B, P, page, L = 3, 40, 8, 2
    latent, rope, H = (128, 64, 4) if listed else (256, 64, 2)
    S = la.latent_slab(latent + rope)
    pool = jnp.asarray(rng.standard_normal((L, P, page, S, 128)),
                       jnp.float32)
    tables = jnp.asarray(rng.permutation(P)[: B * 12].reshape(B, 12),
                         jnp.int32)
    N = 13
    rows = jnp.asarray(rng.integers(0, B, N), jnp.int32)
    ctx = rng.integers(1, 96, N)
    Wq = 128 * -(-(latent + rope) // 128)
    q = jnp.asarray(rng.standard_normal((N, H, Wq)), jnp.float32)
    if listed:
        R = 40
        counts = np.minimum(R, ctx)
        counts[3] = 0
        pos = np.zeros((N, R), np.int32)
        for n in range(N):
            pos[n, : counts[n]] = rng.permutation(ctx[n])[: counts[n]]
        kw = dict(listed=jnp.asarray(pos))
    else:
        counts = np.minimum(33, ctx)
        counts[5] = 0
        kw = dict(first=jnp.asarray(ctx - counts, jnp.int32), window=33)
    args = (q, pool, tables, rows, jnp.asarray(counts, jnp.int32))
    want = la.latent_attention(*args, layer=1, scale=0.1, latent=latent,
                               path=dispatch.REFERENCE, **kw)
    got = jax.jit(lambda *a: la.latent_attention(
        *a, layer=1, scale=0.1, latent=latent, path=dispatch.INTERPRET,
        **kw))(*args)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.any(np.asarray(got)[3 if listed else 5])


# the staged call's cases: how the queries of each tile and their lists
# are drawn (pages of 8, blocks of 32 positions, lists of up to 40)
STAGED_CASES = ("every_live_count", "short_lists", "boundaries",
                "empty_blocks", "different_ends", "score_order")


def _staged_inputs(case, monkeypatch):
    """A listed call's arguments and tiles of 16 slots for one of
    ``STAGED_CASES``: rows picked out of blocks of 32 positions (4 pages of
    8) in runs of 16, so a list spans several blocks and a block's run
    several picks; two padding queries read nothing."""
    monkeypatch.setattr(la, "_ROWS", 16)
    monkeypatch.setattr(la, "_STAGE", 32)
    rng = np.random.default_rng(STAGED_CASES.index(case))
    B, page, max_pages, L, R, G = 4, 8, 20, 2, 40, 16
    P = B * max_pages + 6
    latent, rope, H = 128, 64, 2
    S = la.latent_slab(latent + rope)
    pool = jnp.asarray(rng.standard_normal((L, P, page, S, 128)),
                       jnp.float32)
    tables = rng.permutation(P)[: B * max_pages].reshape(B, max_pages)
    # (batch row, positions of the tile's queries) a tile
    if case == "every_live_count":
        tiles = [(k % B, rng.integers(k, 150) - np.arange(k)[::-1])
                 for k in range(1, G + 1)]
    elif case == "short_lists":
        tiles = [(0, np.arange(16)), (1, np.arange(5, 9)), (2, [3])]
    else:
        tiles = [(r, np.arange(140, 140 + G - 2 * r)) for r in range(B)]
    rows, pos = [], []
    tokens = np.full((len(tiles) + 2, G), -1, np.int32)
    for t, (r, ps) in enumerate(tiles):
        tokens[t, : len(ps)] = np.arange(len(pos), len(pos) + len(ps))
        rows += [r] * len(ps)
        pos += list(ps)
    N = len(pos) + 2
    rows, pos = np.asarray(rows + [0, 0]), np.asarray(pos + [0, 0])
    counts = np.minimum(R, pos + 1)
    counts[-2:] = 0
    listed = np.zeros((N, R), np.int32)
    edges = [p for p in range(0, 160, page)
             for p in (p - 1, p) if p >= 0]        # pages' and blocks' ends
    for n in range(N - 2):
        ctx = np.arange(pos[n] + 1)
        if case == "boundaries":
            ctx = np.asarray([p for p in edges if p <= pos[n]])
        elif case == "empty_blocks":
            ctx = ctx[(ctx < 32) | (ctx >= 96)]
        elif case == "different_ends":
            ctx = ctx[: 41 + 9 * (n % 12)]
        counts[n] = min(counts[n], len(ctx))
        pick = rng.permutation(ctx)[: counts[n]]
        listed[n, : counts[n]] = -np.sort(-pick) if case == "score_order" \
            else pick
    tile_rows = np.full(len(tokens), B, np.int32)
    tile_rows[: len(tiles)] = [r for r, _ in tiles]
    q = jnp.asarray(rng.standard_normal((N, H, 256)), jnp.float32)
    args = (q, pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(rows, jnp.int32), jnp.asarray(counts, jnp.int32))
    kw = dict(layer=1, scale=0.1, latent=latent, listed=jnp.asarray(listed))
    return args, kw, (jnp.asarray(tile_rows), jnp.asarray(tokens))


@pytest.mark.parametrize("case", STAGED_CASES)
def test_the_staged_call_is_the_reference_and_the_per_row_call(
        monkeypatch, case):
    """Tiles of one row's queries read through the staged call (their
    rows picked out of blocks of pages in VMEM, lists sorted by position)
    under the interpreter: the ``jnp`` oracle, and the per-row call on the
    same inputs; a query that reads nothing reads zeros."""
    args, kw, tiles = _staged_inputs(case, monkeypatch)
    want = la.latent_attention(*args, path=dispatch.REFERENCE, **kw)
    per_row = jax.jit(lambda *a: la.latent_attention(
        *a, path=dispatch.INTERPRET, **kw))(*args)
    got = jax.jit(lambda *a: la.latent_attention(
        *a, path=dispatch.INTERPRET, tiles=tiles, **kw))(*args)
    assert np.asarray(la.staged_queries(args[4], tiles)).any()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got, per_row, atol=2e-5, rtol=2e-5)
    assert not np.any(np.asarray(got)[-2:])


def test_the_two_calls_split_the_queries_by_tile(monkeypatch):
    """A tile of one live query goes to the per-row call, a tile of two or
    more to the staged one; each call alone leaves the other's queries at
    zero, and the two add up to one per-row call over all of them."""
    args, kw, tiles = _staged_inputs("every_live_count", monkeypatch)
    q, pool, tables, rows, counts = args
    staged = np.asarray(la.staged_queries(counts, tiles))
    tokens = np.asarray(tiles[1])
    for t, slots in enumerate(tokens):
        live = slots[slots >= 0]
        assert (staged[live] == (len(live) >= 2)).all(), t
    assert staged.sum() == sum(range(2, 17)) and not staged[-2:].any()
    per_row = lambda c: jax.jit(lambda *a: la.latent_attention(
        *a, path=dispatch.INTERPRET, **kw))(q, pool, tables, rows, c)
    alone = per_row(jnp.where(staged, 0, counts))
    whole = per_row(counts)
    picked = jax.jit(lambda c, lst, out: la._staged(
        q, pool, tables, c, lst, tiles, jnp.ones(1, jnp.int32), out,
        scale=kw["scale"], latent=kw["latent"], interpret=True))(
        jnp.where(staged, counts, 0), kw["listed"],
        jnp.zeros(whole.shape, jnp.float32))
    assert not np.any(np.asarray(alone)[staged])
    assert not np.any(np.asarray(picked)[~staged])
    np.testing.assert_allclose(alone + picked, whole, atol=2e-5, rtol=2e-5)


def _one_layer(cfg, params, n):
    """One prompt of ``n`` tokens through the program's step as one chunk,
    every layer a full layer: ``(step outputs, view inputs)``."""
    cfg = dataclasses.replace(cfg, layer_types=(ms.FULL,),
                              num_dense_layers=1)
    B, page = 1, 4
    spec = ms.mla_sparse_state_spec(cfg, num_pages=64, page_size=page,
                                    max_batch_size=B, num_window_pages=8)
    state = [jnp.zeros(s, d) for _, s, d, _ in spec]
    p = dict(params, **{ms.FULL: jax.tree_util.tree_map(
        lambda a: a[:1], params[ms.FULL]), ms.DENSE: params[ms.DENSE]})
    tables = np.arange(64, dtype=np.int32).reshape(B, 64)
    batch = WindowRaggedBatch(*(jnp.asarray(a) for a in (
        np.arange(n, dtype=np.int32) % 512, np.zeros(n, np.int32),
        np.arange(n, dtype=np.int32), np.asarray([n], np.int32),
        np.asarray([n], np.int32), tables, tables[:, :8])))
    return cfg, p, batch, state


def test_the_indexer_chooses_the_reference_exact_top_k(tiny):
    """Every token's listed positions are the reference's ``lax.top_k`` of
    the dense scores, as sets (the order is the kernel's business); a token
    with fewer positions than the top-k reads all of them."""
    cfg, params, model = tiny
    n = 90
    cfg1, p, batch, state = _one_layer(cfg, params, n)
    x = jnp.take(params["wte"], batch.tokens, axis=0)
    # the reference takes whole blocks of queries: padded, as its forward
    theirs, count = model.indexer(rm._at(params[rm.FULL], 0),
                                  jnp.pad(x, ((0, 128 - n), (0, 0))))
    view = RaggedView(batch, max_q=n, max_seq_len=cfg.max_seq_len,
                      num_pages=64, page_size=4, num_window_pages=8)
    # the program's scores from its own index keys, written by one step
    _, _, index_pages, _, _ = ms.mla_sparse_ragged_step(
        cfg1, p, batch, *state, max_q=n)
    fp = p[ms.FULL]
    u = ms._rms(x, fp["ln1"][0], cfg.rms_eps)
    s_q, _ = cfg.scales(ms.FULL)
    cq = ms._rms(u @ fp["q_a"][0], fp["q_norm"][0], cfg.rms_eps) * s_q
    qi = (cq @ fp["iq"][0]).reshape(n, cfg.index_heads, cfg.index_dim)
    qi = ms._rope_first(qi, view.pos, cfg.full.theta, cfg.full.rope_dim)
    wi = (u @ fp["iw"][0]) / math.sqrt(cfg.index_heads * cfg.index_dim)
    scores = ms.index_scores(cfg1, view, batch.page_tables, index_pages, 0,
                             qi, wi)
    listed, counts, pages = ms._selected(scores, view.valid, view.pos,
                                         cfg.index_topk, 4)
    listed, counts = np.asarray(listed), np.asarray(counts)
    theirs, count = np.asarray(theirs)[:n], np.asarray(count)[:n]
    assert np.array_equal(counts, count)
    for t in range(n):
        assert set(listed[t, : counts[t]]) == set(theirs[t, : count[t]]), t
    assert counts.max() == cfg.index_topk and counts[0] == 1
    # pages touched: every valid token's chosen rows lie in them
    assert counts.sum() <= 4 * int(pages)


def test_the_absorbed_product_is_the_expanded_one(tiny):
    """``[q W_UK^T | q_rope] . [c_kv | k_rope]`` then ``W_UV`` (the
    program's kernel, over every position) equals per-head keys ``c_kv
    W_UK`` and values ``c_kv W_UV`` (the reference's expanded form, with
    every position chosen): one full layer whose top-k covers the
    context."""
    cfg, params, model = tiny
    n = 40
    wide = dataclasses.replace(cfg, index_topk=64)
    cfg1, p, batch, state = _one_layer(wide, params, n)
    logits, *_ = ms.mla_sparse_ragged_step(cfg1, p, batch, *state, max_q=n)
    x = jnp.take(params["wte"], batch.tokens, axis=0)
    fp = rm._at(params[rm.FULL], 0)
    picked = (jnp.tile(jnp.arange(n, dtype=jnp.int32), (n, 1)),
              jnp.arange(1, n + 1))
    h = model.attention[rm.FULL](fp, x, picked)
    h = model.dense(rm._at(params[rm.DENSE], 0), h)
    ref = model.head(params["norm_f"], params["lm_head"],
                     jnp.pad(h, ((0, 256), (0, 0))), n - 1)[0]
    np.testing.assert_allclose(logits[0], ref, atol=TOL)


def test_the_faults_change_what_a_full_layer_reads(tiny, monkeypatch):
    """The benchmark's planted faults, written into the program, each move
    the logits of a long prompt."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 512, 100).tolist()
    out = {}
    for fault in (None,) + RUNNER.FAULTS:
        with monkeypatch.context() as m:
            if fault:
                plant(m, fault)
            _, seen, _ = serve(cfg, params, [prompt], [2], page_size=4,
                               num_pages=64, max_batch_size=1, chunk_len=16)
        out[fault] = seen[0][0]
    for fault in RUNNER.FAULTS:
        assert np.abs(out[fault] - out[None]).max() > 100 * TOL, fault


@pytest.mark.parametrize("fault", rm.FAULTS)
def test_the_reference_with_a_fault_is_the_program_with_it(
        tiny, fault, monkeypatch):
    """The benchmark calibrates its limits on the chip with the faults
    written into the reference: each is the same function as the program
    with that fault planted, prefill and decode, past the top-k and the
    window."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).tolist() for n in (60, 31)]
    plant(monkeypatch, fault)
    reqs, seen, _ = serve(cfg, params, prompts, (6, 10), page_size=4,
                          num_pages=128, max_batch_size=2, chunk_len=16)
    model = rm.Model(CONFIG, "float32", fault=fault)
    assert worst_gap(model, params, reqs, seen) < TOL


def test_in_bfloat16_the_rows_are_the_reference_within_rounding(
        monkeypatch):
    """The served dtype: the program in bfloat16 against the float32
    reference keeps the median decoded row within 3% of its deviation
    (rounding, and the rare top-k choice it flips), while each planted
    fault moves the median row by over 10%: the comparison the benchmark
    makes sees a wrong selection through bfloat16's rounding."""
    cfg = RUNNER.mla_sparse_config(dict(CONFIG, dtype="bfloat16"))
    params = rm.weights(CONFIG, 7, jnp.bfloat16)
    model = rm.Model(CONFIG, "float32")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 512, n).tolist() for n in (120, 90, 60)]

    def median_row_err(fault=None):
        with monkeypatch.context() as m:
            if fault:
                plant(m, fault)
            reqs, seen, _ = serve(cfg, params, prompts, (8, 8, 8),
                                  page_size=4, num_pages=128,
                                  max_batch_size=3, chunk_len=16)
        errs = []
        for r in reqs:
            ref = np.asarray(model.forward_logits(
                params, np.asarray(r.tokens, np.int32), len(r.prompt)))
            got = np.stack(seen[r.id][-len(r.output):])
            ref = ref[:len(got)]
            errs += list(np.sqrt(np.mean((got - ref) ** 2, axis=1))
                         / ref.std(axis=1))
        return float(np.median(errs))

    assert median_row_err() < 0.03
    for fault in RUNNER.FAULTS:
        assert median_row_err(fault) > 0.1, fault


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 holders of 32 of 256 experts each (the cell's deployment, at a
    64-wide hidden size): their parts of the routed sum, with the shared
    expert counted once, add up to the layer with every expert held."""
    rng = np.random.default_rng(0)
    T, D, F, E, k = 24, 64, 32, 256, 8
    u = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((D, E)) / 8, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(E) * 0.05, jnp.float32)
    mats = [jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
            for s in ((E, D, F), (E, D, F), (E, F, D))]
    shared = [jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
              for s in ((D, F), (D, F), (F, D))]
    weights, experts = route_top_k(u @ wr, k, score="sigmoid", bias=bias)

    def part(first, count):
        return dropless_experts(
            u, weights, experts, *(m[first:first + count] for m in mats),
            experts_held=(first, count), num_experts=E,
            path=dispatch.REFERENCE)[0]

    swiglu = lambda g, up, down: (jax.nn.silu(u @ g) * (u @ up)) @ down
    whole = swiglu(*shared) + part(0, E)
    summed = swiglu(*shared) + sum(part(32 * c, 32) for c in range(8))
    np.testing.assert_allclose(summed, whole, atol=1e-5)
    # and the whole is the plain sum over each token's chosen experts
    plain = swiglu(*shared)
    for t in range(T):
        for j in range(k):
            e = int(experts[t, j])
            h = jax.nn.silu(u[t] @ mats[0][e]) * (u[t] @ mats[1][e])
            plain = plain.at[t].add(weights[t, j] * (h @ mats[2][e]))
    np.testing.assert_allclose(whole, plain, atol=1e-5)


def test_the_staged_rows_are_a_chunk_steps_and_never_a_decode_steps(tiny):
    """``serving_latent_rows_total{kind="staged"}``: a step that reads a
    prompt's chunk (tiles of two or more of its tokens) counts its full
    layers' rows there, a step of decode rows alone counts none, and the
    staged rows never pass the selected ones."""
    cfg, params, _ = tiny
    eng = Engine(cfg, params, page_size=4, num_pages=64, max_batch_size=2,
                 chunk_len=16)
    m = eng.metrics
    rng = np.random.default_rng(2)
    req = eng.add_request(rng.integers(0, 512, 40).tolist(),
                          SamplingParams(max_new_tokens=6))
    seen = []
    while eng.has_work():
        read = req.prompt_pos
        before = m.latent_rows_staged.value, m.latent_rows_selected.value
        eng.step()
        eng._drain("test")
        staged = m.latent_rows_staged.value - before[0]
        selected = m.latent_rows_selected.value - before[1]
        seen.append((req.prompt_pos - read, staged, selected))
        assert m.latent_rows_staged.value <= m.latent_rows_selected.value
    chunks = [s for s in seen if s[0] > 1]
    decodes = [s for s in seen if s[0] == 0 and s[2] > 0]
    assert [c[0] for c in chunks] == [16, 16, 8]
    # a chunk's tiles of 16 hold 2 or more tokens each: every row staged
    assert all(staged == selected > 0 for _, staged, selected in chunks)
    assert len(decodes) >= 5 and all(staged == 0 for _, staged, _ in decodes)


def test_the_engine_wraps_the_config_and_refuses_a_mesh(tiny):
    cfg, _, _ = tiny
    served = as_served(cfg)
    assert isinstance(served, MLASparseServed) and served.window == 9
    assert len(served.step_stats) == 8
    with pytest.raises(NotImplementedError, match="latent"):
        served.make_step(max_q=4, mesh=object())
    # positions: 2 full layers read min(24, n), 3 sliding min(9, n)
    ctx, read = served.attention_positions(40, 3)
    n = np.arange(38, 41)
    assert ctx == 5 * n.sum() and read == 2 * 3 * 24 + 3 * 3 * 9


def test_the_cell_config_has_the_published_widths():
    """What the configuration file states is what the program is built
    with: every width published, the cut in depth, experts and vocabulary,
    and 4.09 B parameters (8.2 GB in bfloat16)."""
    manifest = bench.load_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"]
                 if bench.load_json(ROOT, c["file"]).get("model_type")
                 == "dots3_note")
    file = bench.load_json(ROOT, entry["file"])
    cfg = RUNNER.mla_sparse_config(file)
    assert cfg.full == ms.MLA(128, 1024, 512, 128, 64, 128, 8e7)
    assert cfg.sliding == ms.MLA(64, 1024, 1024, 192, 64, 128, 5e4)
    assert (cfg.hidden, cfg.window, cfg.index_heads, cfg.index_dim,
            cfg.index_topk) == (5120, 513, 64, 128, 2048)
    assert (cfg.dense_ffn, cfg.expert_ffn, cfg.num_experts, cfg.top_k,
            cfg.experts_held) == (13824, 1536, 256, 8, (0, 32))
    assert cfg.layer_types == (ms.FULL,) * 2 + (ms.SLIDING,) * 3
    assert (cfg.vocab_size, cfg.max_seq_len) == (19008, 34816)
    assert cfg == ms.MLA_SPARSE_CONFIGS[entry["name"]]
    s = rm.Sizes(file)
    assert s.n_params() == 4_087_087_104
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size", "max_position_embeddings"}
    for key in ("num_hidden_layers", "n_routed_experts", "vocab_size",
                "max_position_embeddings"):
        assert file["published"][key] != file[key]

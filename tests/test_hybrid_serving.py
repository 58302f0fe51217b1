"""The hybrid decoder (block-sparse attention layers beside lightning
linear-attention layers) served through the one ``Engine``, against the
plain reference in ``benchmark/reference_hybrid.py``, at a tiny size on
seeded weights; the served-model interface with the dense family behind
it; and the cache manager with three kinds of state.

Tolerance of the parity tests: float32 weights and the kernels' ``jnp``
paths on the CPU, so the program and the reference differ only in the
order of float32 sums: logits (deviation 0.08) agree within 2e-5 at every
decoded position.  A selection that flipped at a near-tie would show as
1e-3 or more.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference_hybrid as rh  # noqa: E402
from benchmark import run as bench  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init  # noqa: E402
from paddle_tpu.models.hybrid import (HYBRID_CONFIGS,  # noqa: E402
                                      hybrid_state_spec)
from paddle_tpu.serving import Engine, SamplingParams  # noqa: E402
from paddle_tpu.serving.kv_cache import PagedKVCache  # noqa: E402
from paddle_tpu.serving.model import (GPTServed, HybridServed,  # noqa: E402
                                      as_served)

TOL = 2e-5
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "tiny-hybrid.json")))


def program_config(fault=None):
    """The program's config as the benchmark's runner builds it from the
    configuration file, in float32."""
    runner = bench.load_module("runners", "serve_hybrid")
    return runner.hybrid_config(dict(CONFIG, dtype="float32"), fault)


@pytest.fixture(scope="module")
def tiny():
    params = rh.weights(CONFIG, 7, jnp.float32)
    return program_config(), params, rh.Model(CONFIG, "float32")


def serve(cfg, params, prompts, new_tokens, **engine):
    """Drive the engine to the end; per request the logits row each of
    its tokens was sampled from, and the engine.  The tokens are chosen
    on the device: the rows are read from the logits the step left there
    (``Engine.step_logits``, which ``step()`` itself never reads), at the
    moment the per-row hook is handed the row's id."""
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
        # a preempted request was served twice: its last pass is the one
        # that produced its tokens
        mine = np.stack(seen[r.id][-len(r.output):])
        worst = max(worst, float(np.abs(ref[: len(mine)] - mine).max()))
    return worst


def test_chunked_prefill_and_decode_equal_the_reference_forward(tiny):
    """Rows on both sides of ``dense_len`` (32) in one batch, a chunk that
    crosses it (prompt 50 in chunks of 16: positions 32..47), decode from
    under it to past it (prompt 9 + 40), and a fourth request that takes
    over the row slot of a longer one."""
    cfg, params, model = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1000, n).tolist() for n in (50, 9, 27, 70)]
    reqs, seen, eng = serve(cfg, params, prompts, (12, 40, 20, 8),
                            page_size=4, num_pages=128, max_batch_size=3,
                            chunk_len=16)
    assert all(len(r.output) == n for r, n in zip(reqs, (12, 40, 20, 8)))
    assert worst_gap(model, params, reqs, seen) < TOL
    m = eng.metrics
    assert m.state_resets.value == 4 and m.requests_preempted.value == 0
    # past dense_len the sparse layers read fewer positions than there are
    assert 0 < m.attention_selected.value < m.attention_context.value
    assert m.recurrent_state_bytes.value == 2 * 3 * 4 * 16 * 16 * 4


def test_a_preempted_request_is_recomputed_from_a_zero_state(tiny):
    """A pool too small for all three: the youngest is preempted, its row's
    state is zeroed in the step that starts it again, and its logits still
    equal the reference's."""
    cfg, params, model = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1000, n).tolist() for n in (40, 44, 36)]
    reqs, seen, eng = serve(cfg, params, prompts, (30, 30, 30),
                            page_size=4, num_pages=44, max_batch_size=3,
                            chunk_len=16)
    assert eng.metrics.requests_preempted.value > 0
    assert eng.metrics.state_resets.value > 3
    assert all(len(r.output) == 30 for r in reqs)
    assert worst_gap(model, params, reqs, seen) < TOL


def test_selection_and_decay_are_not_idle(tiny):
    """Leaving the selection or the decay out moves the logits by far more
    than the tolerance: the parity above would see either."""
    cfg, params, model = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 1000, 60).tolist()]
    for served in (HybridServed(cfg, dense_only=True),
                   program_config(fault="no_decay")):
        reqs, seen, _ = serve(served, params, prompts, (8,), page_size=4,
                              num_pages=64, max_batch_size=2, chunk_len=16)
        assert worst_gap(model, params, reqs, seen) > 100 * TOL


def test_the_step_counts_its_attention_items_and_their_pages(tiny):
    """``serving_attention_items_total`` and ``..._item_pages_total`` come
    from the device with the step's ids.  At the tiny size an item holds up
    to the whole table (64 pages of 4), so a live row is one item a sparse
    layer and key/value group.  Its pages follow from the lengths within
    ``dense_len`` (every page up to the chunk's last position); past it a
    token lists ``topk`` pages, and the kernel visits those and the few
    that tie with the last one taken (two neighbouring blocks score the
    span they share), so a row holds between ``topk`` and all it can
    reach."""
    cfg, params, model = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 1000, n).tolist() for n in (50, 9, 27)]
    eng = Engine(cfg, params, page_size=4, num_pages=128, max_batch_size=3,
                 chunk_len=16)
    served, seen, steps = eng.model, {}, []
    record, positions, sound = (served.record_stats,
                                served.attention_positions,
                                eng._sample_token)

    def spy_record(metrics, values):
        steps.append((tuple(values), []))
        return record(metrics, values)

    def spy_positions(ctx, q):
        steps[-1][1].append((ctx, q))
        return positions(ctx, q)

    def spy_token(token, req):
        row = eng.step_logits[eng._slots.index(req)]
        seen.setdefault(req.id, []).append(np.asarray(row, np.float32))
        return sound(token, req)

    served.record_stats, served.attention_positions = spy_record, \
        spy_positions
    eng._sample_token = spy_token
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, (12, 40, 20))]
    while eng.has_work():
        eng.step()
    # counting changed no token: the parity of the first test holds
    assert worst_gap(model, params, reqs, seen) < TOL

    calls = 2 * cfg.num_kv_heads            # sparse layers x groups
    ps, exact, listed, reachable = 4, 0, 0, 0
    for (items, pages), rows in steps:
        assert items == calls * len(rows)
        lo = hi = 0
        for ctx, q in rows:
            reach = least = (ctx - 1) // ps + 1
            if ctx > cfg.dense_len:
                # one sparse token's list, or its dense tokens' reach
                dense = cfg.dense_len // ps if ctx - q < cfg.dense_len \
                    else 0
                least = max(cfg.topk, dense)
            lo, hi = lo + calls * least, hi + calls * reach
        assert lo <= pages <= hi, (pages, rows)
        assert items <= pages <= 64 * items       # fill between 1 and pages
        if lo == hi:
            exact += 1
        else:
            listed, reachable = listed + pages, reachable + hi
    # steps of dense rows alone, and steps where the lists chose: there a
    # row's items hold well under what it could reach
    assert exact > 5 and len(steps) - exact > 30
    assert listed < 0.8 * reachable
    m = eng.metrics
    assert m.attention_items.value == sum(s[0][0] for s in steps) > 0
    assert m.attention_item_pages.value == sum(s[0][1] for s in steps)
    # the dense family's step counts nothing: its program is unchanged
    assert not hasattr(GPTServed, "step_stats")


def test_prefix_reuse_and_mesh_are_refused_for_a_recurrent_model(tiny):
    cfg, params, _ = tiny
    with pytest.raises(ValueError, match="recurrent state never saw"):
        Engine(cfg, params, page_size=4, num_pages=32, prefix_cache=True)
    assert Engine(cfg, params, page_size=4, num_pages=32).prefix_cache \
        is False
    with pytest.raises(NotImplementedError, match="two layer stacks"):
        Engine(cfg, params, page_size=4, num_pages=32, mesh=object())
    with pytest.raises(ValueError, match="block_size"):
        Engine(cfg, params, page_size=8, num_pages=32)


# ------------------------------------------------- the dense family, served


def test_gpt_through_the_interface_gives_the_tokens_it_gave():
    """The dense family behind the same interface: the engine's greedy
    tokens are those of the full forward recomputed at every position, and
    a config and its served form are the same thing to the engine."""
    cfg = GPTConfig(vocab_size=512, max_seq_len=64, hidden=64, num_layers=2,
                    num_heads=4, ffn_hidden=128, dtype="float32",
                    use_flash=False)
    params = gpt_init(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 19, 11)]
    knobs = dict(page_size=4, num_pages=64, max_batch_size=2, chunk_len=8)
    by_config = Engine(cfg, params, **knobs)
    assert isinstance(by_config.model, GPTServed)
    assert as_served(by_config.model) is by_config.model
    assert by_config.prefix_cache is True
    got = by_config.generate(prompts, SamplingParams(max_new_tokens=6))
    assert got == Engine(GPTServed(cfg), params, **knobs).generate(
        prompts, SamplingParams(max_new_tokens=6))
    for prompt, out in zip(prompts, got):
        toks = list(prompt)
        for _ in range(6):
            logits = gpt_forward(cfg, params, jnp.asarray([toks]))
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert out == toks[len(prompt):]
    m = by_config.metrics
    assert m.attention_selected.value == m.attention_context.value > 0
    assert m.state_resets.value == 0 == m.recurrent_state_bytes.value
    with pytest.raises(TypeError, match="cannot serve"):
        Engine(object())


# --------------------------------------------------------- cache manager


def hybrid_cache(rows=3, pages=16):
    cfg = HYBRID_CONFIGS["tiny"]
    spec = hybrid_state_spec(cfg, num_pages=pages, page_size=4,
                             max_batch_size=rows)
    return PagedKVCache(num_pages=pages, page_size=4, max_seq_len=64,
                        state=spec), spec


def test_pages_compressed_pages_and_state_live_and_die_together():
    cache, spec = hybrid_cache()
    assert list(cache.arrays) == ["k_pages", "v_pages", "kc_pages",
                                  "lin_state"]
    assert [a.shape for a in cache.state_arrays()] == [s for _, s, _, _
                                                       in spec]
    assert cache.recurrent_state_bytes() == cache.arrays["lin_state"].nbytes
    assert cache.allocate("a", 10, slot=0) and cache.allocate("b", 6, slot=2)
    assert cache.slot_of("a") == 0 and cache.slot_of("b") == 2
    assert cache.num_used_pages == 3 + 2
    cache.check_integrity()
    with pytest.raises(ValueError, match="is bound"):
        cache.allocate("c", 4, slot=2)
    with pytest.raises(ValueError, match="outside"):
        cache.allocate("c", 4, slot=3)
    assert "c" not in cache.seq_ids()
    # every page-indexed pool moves under the one set of page ids
    page = cache.page_table("b")[0]
    for name in ("k_pages", "v_pages", "kc_pages"):
        cache.arrays[name] = cache.arrays[name].at[:, page].set(1.0)
    cache.free("a")
    assert cache.slot_of("a") is None and cache.num_used_pages == 2
    cache.defrag()
    cache.check_integrity()
    page = cache.page_table("b")[0]
    for name in ("k_pages", "v_pages", "kc_pages"):
        assert float(cache.arrays[name][:, page].min()) == 1.0, name
    cache.arrays["lin_state"] = cache.arrays["lin_state"] + 1.0
    cache.reset()
    assert cache.seq_ids() == [] and cache.slot_of("b") is None
    assert all(float(jnp.abs(a).max()) == 0.0 for a in cache.state_arrays())
    cache.check_integrity()


def test_check_integrity_covers_all_three_kinds():
    cache, _ = hybrid_cache()
    cache.allocate("a", 8, slot=1)
    cache._slot_of["ghost"] = 2
    with pytest.raises(AssertionError, match="outlived"):
        cache.check_integrity()
    del cache._slot_of["ghost"]
    cache._slot_of.pop("a")
    with pytest.raises(AssertionError, match="without a row of state"):
        cache.check_integrity()
    cache._slot_of["a"] = 1
    cache.arrays["kc_pages"] = cache.arrays["kc_pages"][:, :8]
    with pytest.raises(AssertionError, match="kc_pages"):
        cache.check_integrity()
    cache, _ = hybrid_cache()
    cache.arrays["lin_state"] = cache.arrays["lin_state"][:, :2]
    with pytest.raises(AssertionError, match="lin_state"):
        cache.check_integrity()
    with pytest.raises(ValueError, match="axis 1"):
        PagedKVCache(num_pages=8, page_size=4, max_seq_len=32,
                     state=[("k_pages", (1, 4, 4, 2, 8), jnp.float32,
                             "pages")])


def test_set_state_takes_the_steps_results_in_order():
    cache, _ = hybrid_cache()
    new = tuple(a + i for i, a in enumerate(cache.state_arrays(), 1))
    cache.set_state(new)
    assert [float(a.max()) for a in cache.state_arrays()] == [1, 2, 3, 4]
    assert cache.k_pages is cache.arrays["k_pages"]
    with pytest.raises(ValueError):
        cache.set_state(new[:3])

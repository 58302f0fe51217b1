"""The parallel-mixer decoder (a state-space mixer beside grouped-query
attention in every block) served through the one ``Engine``, against the
plain reference in ``benchmark/reference_ssm.py``, at a tiny size on seeded
weights; the cache manager with pages and two per-row pools in every layer;
and the counter of the rows whose recurrent state a step advanced.

Tolerance of the parity tests: float32 weights and the kernels' ``jnp``
paths on the CPU, so the program and the reference differ only in the
order of float32 sums: logits (deviation 0.012) agree within 2e-6 at every
decoded position.
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

from benchmark import reference_ssm as rs  # noqa: E402
from benchmark import run as bench  # noqa: E402
from paddle_tpu.kernels import dispatch  # noqa: E402
from paddle_tpu.models.hybrid import HYBRID_CONFIGS, hybrid_init  # noqa: E402
from paddle_tpu.models.ragged import RaggedBatch  # noqa: E402
from paddle_tpu.models.ssm import (SSM_CONFIGS, SSMConfig,  # noqa: E402
                                   ssm_init, ssm_ragged_step,
                                   ssm_state_spec)
from paddle_tpu.serving import Engine, SamplingParams  # noqa: E402
from paddle_tpu.serving.kv_cache import PagedKVCache  # noqa: E402
from paddle_tpu.serving.model import SSMServed, as_served  # noqa: E402

TOL = 2e-6
CONFIG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                     "tiny-ssm.json")))


def program_config(fault=None):
    """The program's config as the benchmark's runner builds it from the
    configuration file, in float32."""
    runner = bench.load_module("runners", "serve_ssm")
    return runner.ssm_config(dict(CONFIG, dtype="float32"), fault)


@pytest.fixture(scope="module")
def tiny():
    params = rs.weights(CONFIG, 7, jnp.float32)
    return program_config(), params, rs.Model(CONFIG, "float32")


def serve(cfg, params, prompts, new_tokens, **engine):
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
    """Prompts in chunks of 16 beside decode rows, through pages, window
    and state, against no cache at all; a fourth request takes over the row
    slot (and the stale state) of a finished one."""
    cfg, params, model = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 1024, n).tolist() for n in (50, 9, 27, 70)]
    reqs, seen, eng = serve(cfg, params, prompts, (12, 40, 20, 8),
                            page_size=4, num_pages=128, max_batch_size=3,
                            chunk_len=16)
    assert all(len(r.output) == n for r, n in zip(reqs, (12, 40, 20, 8)))
    assert worst_gap(model, params, reqs, seen) < TOL
    m = eng.metrics
    assert m.state_resets.value == 4 and m.requests_preempted.value == 0
    assert m.attention_selected.value == m.attention_context.value > 0
    # both per-row pools: 3 layers x 3 rows of a [3, 128] window and of a
    # [4, 16, 16] state, float32 here
    assert m.recurrent_state_bytes.value == 3 * 3 * (3 * 128 + 4 * 16 * 16) * 4


@pytest.mark.parametrize("prompt_len", [17, 18, 19, 33, 35])
def test_a_chunk_boundary_inside_the_convolutions_reach(tiny, prompt_len):
    """Chunks of 16 leave a last chunk of 1, 2 or 3 tokens: shorter than
    the convolution's window, which is then made of the chunk and of the
    tail of the window before it.  The chunked prompt gives the unchunked
    one's logits, which are the reference's."""
    cfg, params, model = tiny
    rng = np.random.default_rng(prompt_len)
    prompts = [rng.integers(0, 1024, prompt_len).tolist()]
    knobs = dict(page_size=4, num_pages=32, max_batch_size=1)
    chunked = serve(cfg, params, prompts, (6,), chunk_len=16, **knobs)
    whole = serve(cfg, params, prompts, (6,), chunk_len=64, **knobs)
    assert chunked[2].metrics.prefill_chunks.value == -(-prompt_len // 16)
    assert whole[2].metrics.prefill_chunks.value == 1
    assert chunked[0][0].tokens == whole[0][0].tokens
    assert worst_gap(model, params, *chunked[:2]) < TOL
    assert worst_gap(model, params, *whole[:2]) < TOL


def test_a_preempted_request_is_recomputed_from_a_zero_state(tiny):
    """A pool too small for all three: the youngest is preempted, its row's
    window and state are zeroed in the step that starts it again, and its
    logits still equal the reference's."""
    cfg, params, model = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 1024, n).tolist() for n in (40, 44, 36)]
    reqs, seen, eng = serve(cfg, params, prompts, (30, 30, 30),
                            page_size=4, num_pages=44, max_batch_size=3,
                            chunk_len=16)
    assert eng.metrics.requests_preempted.value > 0
    assert eng.metrics.state_resets.value > 3
    assert all(len(r.output) == 30 for r in reqs)
    assert worst_gap(model, params, reqs, seen) < TOL


def _zeroed(params, name):
    blocks = dict(params["blocks"])
    blocks[name] = jnp.zeros_like(blocks[name])
    return dict(params, blocks=blocks)


@pytest.mark.parametrize("gone", ["o_w", "out_w", "mlp_down_w",
                                  "fixed_decay", "no_carry", "uniform_softmax"])
def test_no_branch_and_no_mechanism_is_idle(tiny, gone):
    """At the deviations the configuration file assumes, each of the three
    branches, the input-dependent decay, the carried window and the
    attention's scores move the logits by far more than the tolerance: the
    parity above would see any of them left out."""
    cfg, params, model = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 1024, 60).tolist()]
    served = params
    if gone in ("fixed_decay", "no_carry"):
        cfg = program_config(fault=gone)
    elif gone == "uniform_softmax":
        served = _zeroed(params, "q_w")         # every score 0
    else:
        served = _zeroed(params, gone)
    reqs, seen, _ = serve(cfg, served, prompts, (8,), page_size=4,
                          num_pages=64, max_batch_size=2, chunk_len=16)
    deviation = float(np.std(np.stack(seen[reqs[0].id])))
    assert worst_gap(model, params, reqs, seen) > max(1000 * TOL,
                                                      0.05 * deviation)


def test_the_pallas_kernels_under_the_interpreter_give_the_jnp_paths_step():
    """One step of the model with both kernels under the Pallas
    interpreter against the same step on their ``jnp`` paths: a chunk, a
    chunk of two, decode rows and an idle row."""
    cfg = SSM_CONFIGS["tiny"]
    params = rs.weights(CONFIG, 3, jnp.float32)
    B, T, page, pages = 5, 24, 8, 16
    spec = ssm_state_spec(cfg, num_pages=pages, page_size=page,
                          max_batch_size=B)
    rng = np.random.default_rng(0)
    state = [jnp.asarray(rng.standard_normal(s) * 0.1, d)
             for _, s, d, _ in spec]
    q = [13, 2, 1, 0, 1]
    ctx = [13, 10, 7, 5, 16]      # the idle row holds 5: not fresh
    tokens, rows, slots = (np.zeros(T, np.int32), np.full(T, B, np.int32),
                           np.zeros(T, np.int32))
    off = 0
    for b, n in enumerate(q):
        tokens[off:off + n] = rng.integers(0, 1024, n)
        rows[off:off + n], slots[off:off + n] = b, np.arange(n)
        off += n
    tables = np.arange(B * 3, dtype=np.int32).reshape(B, 3)
    batch = RaggedBatch(*(jnp.asarray(a) for a in (
        tokens, rows, slots, np.asarray(q, np.int32),
        np.asarray(ctx, np.int32), tables)))
    run = lambda path: ssm_ragged_step(cfg, params, batch, *state, max_q=16,
                                       attn_path=path)
    ref, got = run(dispatch.REFERENCE), run(dispatch.INTERPRET)
    live = np.asarray(q) > 0
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(ref[0])[live], atol=1e-6)
    for a, b, (name, *_) in zip(got[1:], ref[1:], spec):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)
    # the idle row's window and state are as they were
    np.testing.assert_array_equal(np.asarray(got[3])[:, 3],
                                  np.asarray(state[2])[:, 3])


def test_prefix_reuse_and_mesh_are_refused(tiny):
    cfg, params, _ = tiny
    assert isinstance(as_served(cfg), SSMServed) and SSMServed.recurrent
    with pytest.raises(ValueError, match="recurrent state never saw"):
        Engine(cfg, params, page_size=4, num_pages=32, prefix_cache=True)
    assert Engine(cfg, params, page_size=4, num_pages=32).prefix_cache \
        is False
    with pytest.raises(NotImplementedError, match="state-space mixer"):
        Engine(cfg, params, page_size=4, num_pages=32, mesh=object())


def test_config_refuses_what_the_step_cannot_run():
    with pytest.raises(ValueError, match="num_kv_heads"):
        SSMConfig(num_heads=4, num_kv_heads=3)
    with pytest.raises(ValueError, match="ssm_groups"):
        SSMConfig(ssm_heads=4, ssm_groups=3)
    with pytest.raises(ValueError, match="5 entries"):
        SSMConfig(ssm_multipliers=(1.0, 1.0))
    big = SSM_CONFIGS["falcon-h1-34b-6l"]
    assert (big.d_ssm, big.conv_channels, big.in_width) == (4096, 5120, 9248)
    shapes = jax.eval_shape(lambda: ssm_init(big))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == 5_254_594_112                   # 10.51 GB in bfloat16


# --------------------------------------------------------- cache manager


def ssm_cache(rows=3, pages=16):
    cfg = SSM_CONFIGS["tiny"]
    spec = ssm_state_spec(cfg, num_pages=pages, page_size=4,
                          max_batch_size=rows)
    return PagedKVCache(num_pages=pages, page_size=4, max_seq_len=64,
                        state=spec), spec


def test_pages_window_and_state_live_and_die_together():
    cache, spec = ssm_cache()
    assert list(cache.arrays) == ["k_pages", "v_pages", "conv_state",
                                  "ssm_state"]
    assert [a.shape for a in cache.state_arrays()] == [s for _, s, _, _
                                                       in spec]
    assert [k for *_, k in spec] == ["pages", "pages", "slots", "slots"]
    assert cache.arrays["ssm_state"].dtype == jnp.float32
    assert cache.recurrent_state_bytes() == (
        cache.arrays["conv_state"].nbytes + cache.arrays["ssm_state"].nbytes)
    assert cache.allocate("a", 10, slot=0) and cache.allocate("b", 6, slot=2)
    assert cache.slot_of("a") == 0 and cache.slot_of("b") == 2
    cache.check_integrity()
    with pytest.raises(ValueError, match="is bound"):
        cache.allocate("c", 4, slot=2)
    cache.free("a")
    assert cache.slot_of("a") is None and cache.num_used_pages == 2
    cache.defrag()
    cache.check_integrity()
    for name in ("conv_state", "ssm_state"):
        cache.arrays[name] = cache.arrays[name] + 1.0
    cache.reset()
    assert cache.seq_ids() == [] and cache.slot_of("b") is None
    assert all(float(jnp.abs(a).max()) == 0.0 for a in cache.state_arrays())
    cache.check_integrity()
    # the two per-row pools must agree on the rows
    cache.arrays["conv_state"] = cache.arrays["conv_state"][:, :2]
    with pytest.raises(AssertionError, match="conv_state"):
        cache.check_integrity()
    bad = [(n, (s[0], 2) + s[2:] if n == "conv_state" else s, d, k)
           for n, s, d, k in spec]
    with pytest.raises(ValueError, match="disagree on the rows"):
        PagedKVCache(num_pages=16, page_size=4, max_seq_len=64, state=bad)


# ----------------------------------------------------------- the counter


def _hybrid_engine():
    cfg = HYBRID_CONFIGS["tiny"]
    return Engine(cfg, hybrid_init(cfg), page_size=4, num_pages=64,
                  max_batch_size=2, chunk_len=16)


def _ssm_engine():
    cfg = SSM_CONFIGS["tiny"]
    return Engine(cfg, ssm_init(cfg), page_size=4, num_pages=64,
                  max_batch_size=2, chunk_len=16)


def _gpt_engine():
    from paddle_tpu.models.gpt import GPTConfig, gpt_init

    cfg = GPTConfig(vocab_size=512, max_seq_len=64, hidden=64, num_layers=2,
                    num_heads=4, ffn_hidden=128, dtype="float32",
                    use_flash=False)
    return Engine(cfg, gpt_init(cfg), page_size=4, num_pages=64,
                  max_batch_size=2, chunk_len=16, prefix_cache=False)


@pytest.mark.parametrize("make", [_hybrid_engine, _ssm_engine, _gpt_engine])
def test_state_row_steps_count_what_the_plan_says(make):
    """``serving_state_row_steps_total``: every planned row of a recurrent
    model is counted once, by what it ran — a chunk (as many as
    ``serving_prefill_chunks``) or a decode token (every generated token
    but each request's first, which its last chunk yields) — and a model
    without recurrent state counts nothing."""
    eng = make()
    planned = {"chunk": 0, "decode": 0}
    sound = eng._commit

    def spy(step, *rest):
        for _, req, q, ctx in step.sched:
            planned["chunk" if ctx - q < len(req.prompt)
                    else "decode"] += 1
        return sound(step, *rest)

    eng._commit = spy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 500, n).tolist() for n in (21, 5, 33)]
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=7))
    assert [len(o) for o in outs] == [7, 7, 7]
    m = eng.metrics
    chunk, decode = (m.state_row_steps_chunk.value,
                     m.state_row_steps_decode.value)
    if not eng.model.recurrent:
        assert (chunk, decode) == (0, 0)
        return
    assert (chunk, decode) == (planned["chunk"], planned["decode"])
    assert chunk == m.prefill_chunks.value == 2 + 1 + 3
    assert decode == m.tokens_generated.value - len(prompts) == 18
    assert 'serving_state_row_steps_total{kind="chunk"} 6' in \
        eng.metrics.registry.expose_prometheus()

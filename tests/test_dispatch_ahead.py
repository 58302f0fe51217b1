"""One step in flight (PR 34): ``Engine.step()`` call k dispatches program k
while program k-1 runs and only then commits k-1.

What is held here: the tokens are those of the synchronous order (every
request served alone, the step in flight settled after every call, which is
the loop the engine ran before), for all three served families, greedy and
seeded draws, with a shared prefix, a stop id, a length finish and a
preemption; a stop is over-run by exactly one step and a length finish by
none; the counters say how often the host ran behind the device; nothing
is left in flight at the end; ``evacuate()`` and a failing row lose nothing
that was committed; and the decode rate is tokens over the time between two
completions, not over dispatch-to-read.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
from paddle_tpu.models.hybrid import HYBRID_CONFIGS, hybrid_init
from paddle_tpu.models.ssm import SSM_CONFIGS, ssm_init
from paddle_tpu.serving import Engine, RequestState, SamplingParams


def _gpt():
    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")
    return cfg, gpt_init(cfg, jax.random.key(0), dtype=jnp.float32)


def _hybrid():
    cfg = HYBRID_CONFIGS["tiny"]
    return cfg, hybrid_init(cfg)


def _ssm():
    cfg = SSM_CONFIGS["tiny"]
    return cfg, ssm_init(cfg)


@pytest.fixture(scope="module", params=[_gpt, _hybrid, _ssm],
                ids=["gpt", "hybrid", "ssm"])
def family(request):
    cfg, params = request.param()
    return cfg, params, request.param is _gpt


def drive_settled(eng):
    """The synchronous order: every step settled before the next is
    planned, so nothing is ever dispatched ahead."""
    while eng.has_work():
        eng.step()
        eng._drain("test")


def alone(cfg, params, prompt, sampling, **kw):
    """One request on an engine of its own, in the synchronous order."""
    eng = Engine(cfg, params, **kw)
    req = eng.add_request(prompt, sampling)
    drive_settled(eng)
    assert eng.metrics.steps_ahead.value == 0
    return req.output, req.finish_reason


def _traffic(cfg, params, shared_prefix, **kw):
    """Eight requests: long prompts that take several chunks, short ones
    that decode beside them, greedy and seeded draws, (for a model that
    may) two on one page-aligned prefix, one that ends on a stop id it
    meets mid-stream and one that ends by length.  Returns (prompts,
    samplings, what each yields alone, index of the stop request)."""
    rng = np.random.RandomState(11)
    V = cfg.vocab_size
    draw = lambda n: rng.randint(1, V - 1, n).tolist()
    prefix = draw(16)
    prompts = [draw(37), draw(5), prefix + draw(9), draw(3),
               (prefix if shared_prefix else draw(16)) + draw(4),
               draw(21), draw(8), draw(12)]
    samplings = [
        SamplingParams(max_new_tokens=6),
        SamplingParams(max_new_tokens=12, temperature=0.9, seed=5),
        SamplingParams(max_new_tokens=9, temperature=0.7, top_k=40, seed=6),
        SamplingParams(max_new_tokens=4),
        SamplingParams(max_new_tokens=10, temperature=1.1, top_p=0.9,
                       seed=7),
        SamplingParams(max_new_tokens=7),
        SamplingParams(max_new_tokens=14, temperature=1.0, seed=8),
        SamplingParams(max_new_tokens=5, temperature=0.8, seed=9),
    ]
    # the stop request: a seeded draw, stopped at the first token of its
    # unstopped stream that did not occur before it, from the third on
    stop = 6
    free, _ = alone(cfg, params, prompts[stop], samplings[stop], **kw)
    at = next(i for i in range(3, len(free)) if free[i] not in free[:i])
    samplings[stop] = dataclasses.replace(
        samplings[stop], stop_token_ids=(free[at],))
    expected = [alone(cfg, params, p, s, **kw)
                for p, s in zip(prompts, samplings)]
    assert expected[stop] == (free[:at + 1], "stop")
    assert all(reason == "length" for i, (_, reason) in enumerate(expected)
               if i != stop)
    return prompts, samplings, expected, stop


def _settled(eng):
    assert not eng.has_work() and eng._inflight is None
    assert all(r is None for r in eng._slots)
    eng.cache.check_integrity()
    assert eng.cache.num_used_pages == 0


def test_a_mixed_batch_yields_the_tokens_of_the_synchronous_order(family):
    """A roomy pool and a row for every request: nothing drains, so every
    program but the first goes out while the one before it runs; the stop
    row rides exactly one program too many and the length rows none."""
    cfg, params, prefix = family
    kw = dict(page_size=4, num_pages=128, chunk_len=8,
              prefix_cache=prefix)
    prompts, samplings, expected, stop = _traffic(
        cfg, params, prefix, max_batch_size=2, **kw)
    eng = Engine(cfg, params, max_batch_size=8, **kw)
    late = 4                 # the second request on the shared prefix
    reqs = [None if i == late else eng.add_request(p, s)
            for i, (p, s) in enumerate(zip(prompts, samplings))]
    calls = 0
    while eng.has_work():
        if reqs[late] is None and reqs[2].prompt_pos == len(prompts[2]):
            # arrives once the first's prompt is dispatched: its full
            # pages are in the radix tree though that step is in flight
            assert reqs[2].output == [] and eng._inflight is not None
            reqs[late] = eng.add_request(prompts[late], samplings[late])
        eng.step()
        calls += 1
    assert [(r.output, r.finish_reason) for r in reqs] == expected
    assert all(r.state == RequestState.FINISHED for r in reqs)
    # nothing is owed to a row that ended by length; the stop row's one
    # token too many was computed and dropped
    assert [r._pending for r in reqs] == [int(i == stop)
                                          for i in range(len(reqs))]
    m = eng.metrics
    programs = m.steps_ahead.value + m.steps_not_ahead.value
    assert m.steps_not_ahead.value == 1          # the first
    assert programs == calls - 1                 # the last call only commits
    assert m.steps_ahead.value == programs - 1 > 10
    assert m.overrun_rows.value == 1             # the stop row, once
    assert m.requests_preempted.value == 0
    assert m.pipeline_drains._series() == []     # no reason ever counted
    if prefix:
        assert m.prefix_cache_hits.value >= 1
    _settled(eng)


def test_preemption_and_a_reused_slot_keep_the_tokens(family):
    """A pool too small for the batch and fewer rows than requests: the
    plan runs out of pages with a step in flight, settles it and preempts
    on committed state; finished rows' slots (the over-run one among
    them, with its pages and, for a recurrent model, its state) go to
    queued requests.  The tokens are still those of each request alone."""
    cfg, params, prefix = family
    kw = dict(page_size=4, chunk_len=8, prefix_cache=prefix)
    prompts, samplings, expected, _ = _traffic(
        cfg, params, prefix, max_batch_size=2, num_pages=128, **kw)
    eng = Engine(cfg, params, max_batch_size=3, num_pages=20, **kw)
    reqs = [eng.add_request(p, s) for p, s in zip(prompts, samplings)]
    while eng.has_work():
        eng.step()
        eng.cache.check_integrity()
    assert [(r.output, r.finish_reason) for r in reqs] == expected
    m = eng.metrics
    drains = m.pipeline_drains.labels(reason="memory").value
    assert m.requests_preempted.value >= 1 and drains >= 1
    # ahead of the device in every step but the first, the one after each
    # drain, and one after a call that found nothing to plan
    assert 1 + drains <= m.steps_not_ahead.value < m.steps_ahead.value
    assert m.overrun_rows.value <= 1     # 0 if a drain committed the stop
    _settled(eng)


def test_a_length_finish_is_never_over_run():
    cfg, params = _gpt()
    eng = Engine(cfg, params, page_size=4, num_pages=64, max_batch_size=2,
                 chunk_len=8)
    reqs = [eng.add_request([3, 4, 5], SamplingParams(max_new_tokens=n))
            for n in (1, 4)]
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
    # programs: the prompt's chunk and three decode steps; one call more
    assert calls == 5 and [len(r.output) for r in reqs] == [1, 4]
    assert eng.metrics.overrun_rows.value == 0
    assert eng.metrics.steps_ahead.value == 3
    _settled(eng)


def test_max_seq_len_is_a_finish_the_host_foresees():
    cfg, params = _gpt()
    eng = Engine(cfg, params, page_size=8, num_pages=64, max_batch_size=1,
                 chunk_len=64)
    n = cfg.max_seq_len - 3
    req = eng.add_request(list(range(1, n + 1)),
                          SamplingParams(max_new_tokens=3))
    eng.generate([], None)
    assert req.finish_reason == "length" and len(req.tokens) == n + 3
    assert eng.metrics.overrun_rows.value == 0
    _settled(eng)


def test_generate_ends_with_every_token_committed():
    cfg, params = _gpt()
    eng = Engine(cfg, params, page_size=4, num_pages=64, max_batch_size=2,
                 chunk_len=8)
    assert not eng.has_work()
    req = eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=2))
    eng.step()
    # dispatched, nothing read: work is left though no token is visible
    assert eng.has_work() and req.output == [] and req._pending == 1
    eng.step()
    assert len(req.output) == 1 and eng.has_work()
    done = eng.step()                # nothing to plan: commits the last
    assert done == [req] and len(req.output) == 2
    assert not eng.has_work() and eng._inflight is None
    outs = eng.generate([[1, 2, 3, 4]] * 3, SamplingParams(max_new_tokens=5))
    assert [len(o) for o in outs] == [5, 5, 5]
    _settled(eng)


def test_evacuate_with_a_step_in_flight_loses_no_committed_token():
    cfg, params = _gpt()
    kw = dict(page_size=4, num_pages=64, max_batch_size=2, chunk_len=8)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 900, n).tolist() for n in (6, 19, 4)]
    sp = SamplingParams(max_new_tokens=9, temperature=0.9, seed=3)
    expected = [alone(cfg, params, p, sp, **kw)[0] for p in prompts]
    eng = Engine(cfg, params, **kw)
    reqs = [eng.add_request(p, sp) for p in prompts]
    for _ in range(4):
        eng.step()
    assert eng._inflight is not None
    before = [list(r.tokens) for r in reqs]
    assert len(reqs[0].output) > 0
    got = eng.evacuate()
    assert got == reqs and eng._inflight is None and not eng.has_work()
    for r, b in zip(reqs, before):
        assert r.tokens[:len(b)] == b and r._pending == 0
        assert r.state == RequestState.EVACUATED
    # the step in flight was settled, not thrown away
    assert len(reqs[0].tokens) == len(before[0]) + 1
    eng.cache.check_integrity()
    assert eng.cache.num_used_pages == 0
    # re-enqueued elsewhere with the output so far as prompt: the same
    # stream goes on (a draw is keyed by seed and position)
    other = Engine(cfg, params, **kw)
    for r, want in zip(reqs, expected):
        rest = other.generate(
            [r.tokens], dataclasses.replace(
                sp, max_new_tokens=9 - len(r.output)))[0]
        assert r.output + rest == want


def test_evacuate_drops_a_step_it_cannot_wait_for():
    """A dead device: the wait raises; what was committed still leaves."""
    cfg, params = _gpt()
    eng = Engine(cfg, params, page_size=4, num_pages=64, max_batch_size=2,
                 chunk_len=8)
    req = eng.add_request([9, 8, 7], SamplingParams(max_new_tokens=8))
    for _ in range(3):
        eng.step()
    before = list(req.tokens)

    class Dead:
        def block_until_ready(self):
            raise RuntimeError("device lost")

    eng._inflight.ids = Dead()
    assert eng.evacuate() == [req]
    assert req.tokens == before and req.state == RequestState.EVACUATED
    assert eng._inflight is None and not eng.has_work()
    assert eng.cache.num_used_pages == 0


def test_a_row_that_fails_in_the_hook_drops_its_step_in_flight():
    cfg, params = _gpt()
    kw = dict(page_size=4, num_pages=64, max_batch_size=2, chunk_len=8)
    sp = SamplingParams(max_new_tokens=6)
    good = [4, 5, 6, 7]
    want = alone(cfg, params, good, sp, **kw)[0]
    eng = Engine(cfg, params, **kw)
    sound, seen = eng._sample_token, []

    def hook(token, req):
        # the logits the id was chosen from are the ones at hand
        assert int(jnp.argmax(eng.step_logits[eng._slots.index(req)])) \
            == token
        seen.append(req.id)
        if req is bad and len(req.output) == 2:
            raise ValueError("row broke")
        return sound(token, req)

    eng._sample_token = hook
    ok = eng.add_request(good, sp)
    bad = eng.add_request([1, 2, 3], sp)
    while eng.has_work():
        eng.step()
    assert bad.state == RequestState.FAILED and len(bad.output) == 2
    assert ok.output == want
    # the failed row rode in the program already dispatched: dropped
    assert eng.metrics.overrun_rows.value == 1
    assert seen.count(bad.id) == 3
    _settled(eng)


def test_mesh_engine_dispatches_ahead_with_replicated_ids():
    cfg, params = _gpt()
    kw = dict(page_size=8, num_pages=64, max_batch_size=4, chunk_len=16)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 900, n).tolist() for n in (7, 20, 3)]
    sps = [SamplingParams(max_new_tokens=6),
           SamplingParams(max_new_tokens=8, temperature=0.8, seed=2),
           SamplingParams(max_new_tokens=5)]
    expected = [alone(cfg, params, p, s, **kw)[0]
                for p, s in zip(prompts, sps)]
    mesh = mesh_mod.build_mesh(mp=4)
    eng = Engine(cfg, params, mesh=mesh, **kw)
    assert eng.generate(prompts, sps) == expected
    assert eng.metrics.steps_not_ahead.value == 1
    assert eng.metrics.steps_ahead.value >= 7
    assert eng._prev_ids.sharding.is_fully_replicated
    _settled(eng)


# ----------------------------------------------------------- the decode rate


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _TimedIds:
    """The step's ids as a device would hand them over: ready at a time."""

    def __init__(self, ids, ready_at, clock):
        self.ids, self.ready_at, self.clock = ids, ready_at, clock

    def copy_to_host_async(self):
        pass

    def block_until_ready(self):
        self.clock.t = max(self.clock.t, self.ready_at)

    def __array__(self, *a, **k):
        return np.asarray(self.ids)


def _on_a_timed_device(eng, clock, busy_s):
    """Programs take ``busy_s`` each and run one after another, however
    early they were dispatched."""
    real, free_at = eng._step_fn, [0.0]

    def step_fn(*args):
        *rest, prev = args
        prev = prev.ids if isinstance(prev, _TimedIds) else prev
        ids, *out = real(*rest, prev)
        free_at[0] = max(clock.t, free_at[0]) + busy_s
        return (_TimedIds(ids, free_at[0], clock), *out)

    eng._step_fn = step_fn


def test_decode_rate_is_tokens_over_the_time_between_completions():
    """10 ms programs, 2 ms of host before each: the synchronous loop
    completes a program every 12 ms and measures each over the 10 ms from
    its dispatch to its read; dispatched ahead, a program completes every
    10 ms, 18 ms after its own dispatch.  Both engines serve the same
    schedule and must report the same rate: tokens over 10 ms."""
    cfg, params = _gpt()
    kw = dict(page_size=4, num_pages=64, max_batch_size=2, chunk_len=8)
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8]]
    sp = SamplingParams(max_new_tokens=40)
    rates = {}
    for name in ("settled", "ahead"):
        clock = _Clock()
        eng = Engine(cfg, params, clock=clock, **kw)
        _on_a_timed_device(eng, clock, busy_s=0.010)
        for p in prompts:
            eng.add_request(p, sp)
        for _ in range(12):
            clock.t += 0.002
            eng.step()
            if name == "settled":
                eng._drain("test")
        if name == "ahead":
            eng._drain("test")          # the twelfth program, as above
            assert eng.metrics.steps_ahead.value == 11
        rates[name] = (eng.decode_rate(), eng.estimated_drain_s(),
                       eng.metrics.tokens_generated.value)
    assert rates["ahead"] == pytest.approx(rates["settled"], rel=1e-9)
    assert rates["ahead"][0] == pytest.approx(2 / 0.010, rel=1e-9)

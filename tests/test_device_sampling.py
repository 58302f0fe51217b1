"""The serving step chooses its tokens on the device
(``paddle_tpu/serving/sampling.py``): the host reads ``[B]`` ids.

The reference here is the rule the engine applied on the host until the
step took it over — float64 numpy, kept in this file as
``host_distribution`` — and the full-recompute greedy oracle of
``tests/test_serving.py``.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_forward, gpt_init
from paddle_tpu.serving import Engine, SamplingParams
from paddle_tpu.serving.sampling import (GREEDY, any_stochastic,
                                         sample_tokens, slot_entry)


# ------------------------------------------------------------ the host rule
def host_distribution(logits_row, sp):
    """What the host-side sampler drew from: the arithmetic of the
    engine's former ``_sample_token`` (float64; ``logits < kth`` keeps
    the ties of the k-th; ``searchsorted(cum, top_p) + 1`` keeps the
    smallest prefix reaching ``top_p``), returning the probabilities in
    place of one draw.  The order is made stable so that a tie at the
    cut is decided by index, as the device decides it."""
    logits = np.asarray(logits_row, np.float64)
    if sp.temperature <= 0.0:
        probs = np.zeros(logits.size)
        probs[int(np.argmax(logits))] = 1.0
        return probs
    logits = logits / sp.temperature
    if sp.top_k and sp.top_k < logits.size:
        kth = np.partition(logits, -sp.top_k)[-sp.top_k]
        logits = np.where(logits < kth, -np.inf, logits)
    probs = np.exp(logits - np.max(logits))
    probs = probs / probs.sum()
    if sp.top_p < 1.0:
        order = np.argsort(-probs, kind="stable")
        cum = np.cumsum(probs[order])
        cut = int(np.searchsorted(cum, sp.top_p)) + 1
        mask = np.zeros_like(probs)
        mask[order[:cut]] = 1.0
        probs = probs * mask
        probs = probs / probs.sum()
    return probs


_draw = jax.jit(sample_tokens)


def device_draws(logits_row, sp, n, *, first_seed=0):
    """``n`` draws of the device sampler for one row of logits: ``n``
    batch rows with seeds ``first_seed..``, each a live row that is owed
    a token at position 7."""
    logits = jnp.tile(jnp.asarray(logits_row, jnp.float32)[None], (n, 1))
    table = np.array([slot_entry(dataclasses.replace(sp, seed=first_seed + i),
                                 1) for i in range(n)], np.uint32)
    ones = jnp.ones((n,), jnp.int32)
    return np.asarray(_draw(logits, jnp.asarray(table), ones, 7 * ones))


SPREAD = [2.0, 1.0, 0.5, 0.0, -0.5, -1.0, 1.5, -2.0, 0.25, -0.25, 0.75, -3.0]
TIED = [2.0, 1.0, 1.0, 1.0, 0.0, 0.0, 3.0, 3.0, -1.0, 1.0]


@pytest.mark.parametrize("logits,sp", [
    (SPREAD, SamplingParams(temperature=1.0)),
    (SPREAD, SamplingParams(temperature=0.5, top_k=5)),
    (SPREAD, SamplingParams(temperature=2.0, top_p=0.8)),
    (SPREAD, SamplingParams(temperature=0.8, top_k=8, top_p=0.9)),
    (TIED, SamplingParams(temperature=1.0, top_k=3, top_p=0.95)),
], ids=["temperature", "top_k", "top_p", "both", "ties"])
def test_frequencies_match_the_host_rule(logits, sp):
    n = 8192
    want = host_distribution(logits, sp)
    got = np.bincount(device_draws(logits, sp, n), minlength=len(logits)) / n
    assert set(np.flatnonzero(got)) <= set(np.flatnonzero(want))
    # four standard deviations of a binomial share, and the seeds are fixed
    assert (np.abs(got - want) <= 4 * np.sqrt(want * (1 - want) / n)
            + 1e-9).all(), (got, want)


@pytest.mark.parametrize("logits,sp", [
    # the k-th largest is tied three ways: all three stay
    ([3.0, 2.0, 2.0, 2.0, 1.0, 0.0], SamplingParams(temperature=1.0,
                                                    top_k=2)),
    # top_k at or over the vocabulary, and 0: everything stays
    ([3.0, 2.0, 2.0, 2.0, 1.0, 0.0], SamplingParams(temperature=1.0,
                                                    top_k=6)),
    ([3.0, 2.0, 2.0, 2.0, 1.0, 0.0], SamplingParams(temperature=1.0)),
    # masses .42 .15 .15 .15 .06 .06: the cut falls inside the tie and
    # takes the first two of the three by index
    ([2.0, 1.0, 1.0, 1.0, 0.0, 0.0], SamplingParams(temperature=1.0,
                                                    top_p=0.6)),
    # the tie sits behind the maximum in index order
    ([1.0, 1.0, 2.0, 0.0, 1.0, 0.0], SamplingParams(temperature=1.0,
                                                    top_p=0.6)),
    # top_p under the first entry's mass, and 0: the first alone
    ([2.0, 1.0, 1.0, 1.0, 0.0, 0.0], SamplingParams(temperature=1.0,
                                                    top_p=0.3)),
    ([2.0, 1.0, 1.0, 1.0, 0.0, 0.0], SamplingParams(temperature=1.0,
                                                    top_p=0.0)),
    # both filters, top_p over what top_k left
    ([3.0, 2.0, 2.0, 2.0, 1.0, 0.0], SamplingParams(temperature=2.0,
                                                    top_k=2, top_p=0.7)),
    # a sharp temperature leaves the tail a mass the cut removes
    (SPREAD, SamplingParams(temperature=0.25, top_p=0.99)),
], ids=["top_k_tie", "top_k_full", "top_k_off", "top_p_tie",
        "top_p_tie_behind", "top_p_small", "top_p_zero", "both", "sharp"])
def test_support_sets_equal_the_host_rules(logits, sp):
    want = host_distribution(logits, sp)
    # every kept entry has a mass of a few per cent: 4096 draws miss none
    assert want[want > 0].min() > 0.004
    got = set(device_draws(logits, sp, 4096).tolist())
    assert got == set(np.flatnonzero(want).tolist())


def test_greedy_is_the_first_argmax_and_rows_are_independent():
    logits = jnp.asarray([[0.0, 5.0, 5.0, 1.0],       # a tie: the first
                          [1.0, 0.0, 3.0, 3.0],
                          [9.0, 0.0, 0.0, 0.0],       # stochastic, peaked
                          [0.0, 0.0, 0.0, 7.0]], jnp.bfloat16)
    hot = slot_entry(SamplingParams(temperature=0.05, seed=3), 1)
    table = jnp.asarray(np.array([GREEDY, GREEDY, hot, GREEDY], np.uint32))
    live = jnp.ones((4,), jnp.int32)
    ids = np.asarray(_draw(logits, table, live, 5 * live))
    assert ids.dtype == np.int32
    assert ids.tolist() == [1, 2, 0, 3]
    # the same greedy rows with nobody drawing beside them
    alone = jnp.asarray(np.array([GREEDY] * 4, np.uint32))
    assert np.asarray(_draw(logits, alone, live, 5 * live)).tolist() == \
        [1, 2, 0, 3]


def test_the_draw_is_keyed_by_seed_and_position():
    sp = SamplingParams(temperature=1.0, seed=11)
    flat = jnp.zeros((6, 64), jnp.float32)
    live = jnp.ones((6,), jnp.int32)

    def draws(seeds, positions):
        table = np.array([slot_entry(dataclasses.replace(sp, seed=s), 1)
                          for s in seeds], np.uint32)
        return np.asarray(_draw(flat, jnp.asarray(table), live,
                                jnp.asarray(positions, jnp.int32))).tolist()

    base = draws([11] * 6, [4, 5, 6, 7, 8, 9])
    # same seed and position: the same token, whatever the row
    assert draws([11] * 6, [9, 8, 7, 6, 5, 4]) == base[::-1]
    assert len(set(base)) > 3            # positions draw independently
    # the high word of a 64-bit seed is part of the key, and so is a
    # seed past 32 signed bits
    assert draws([11 + (1 << 32)] * 6, [4, 5, 6, 7, 8, 9]) != base
    assert draws([(1 << 31) + 11] * 6, [4, 5, 6, 7, 8, 9]) != base


def test_the_predicate_asks_only_rows_owed_a_token():
    hot = slot_entry(SamplingParams(temperature=0.7), 10)   # prompt of 10
    table = jnp.asarray(np.array([GREEDY, hot], np.uint32))

    def pred(query_lens, context_lens):
        return bool(any_stochastic(table, jnp.asarray(query_lens, jnp.int32),
                                   jnp.asarray(context_lens, jnp.int32)))

    assert not pred([1, 4], [30, 8])         # its prompt is not read in yet
    assert pred([1, 2], [30, 10])            # the chunk that completes it
    assert pred([1, 1], [30, 15])            # decoding
    assert not pred([1, 0], [30, 15])        # the slot is idle: stale entry


def test_the_draw_sits_under_the_cond():
    """The program, whatever its rows will ask for, holds one ``cond``;
    the threshold searches and the random bits are in one of its
    branches and nowhere else, and nothing anywhere sorts."""
    jaxpr = jax.make_jaxpr(sample_tokens)(
        jnp.zeros((4, 32), jnp.bfloat16), jnp.zeros((4, 6), jnp.uint32),
        jnp.ones((4,), jnp.int32), jnp.ones((4,), jnp.int32)).jaxpr

    def names(jp):
        out = []
        for eqn in jp.eqns:
            out.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out.extend(names(sub))
        return out

    top = [e.primitive.name for e in jaxpr.eqns]
    loops = ("while", "scan")            # a fori_loop is either
    assert top.count("cond") == 1 and not set(loops) & set(top)
    assert "sort" not in names(jaxpr)
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    branches = [names(b.jaxpr) for b in cond.params["branches"]]
    assert sorted(sum(b.count(l) for l in loops)
                  for b in branches) == [0, 2], branches
    assert sorted("random_bits" in b for b in branches) == [False, True]


# ------------------------------------------------------------- in the engine
def _tiny_cfg():
    return dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    return cfg, gpt_init(cfg, jax.random.key(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def oracle(tiny_model):
    cfg, params = tiny_model
    fwd = jax.jit(lambda p, t: gpt_forward(cfg, p, t))

    def naive_generate(prompt, n_new):
        toks = list(prompt)
        for _ in range(n_new):
            logits = fwd(params, jnp.asarray([toks], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks[len(prompt):]

    return naive_generate


def _prompts(cfg, lengths, seed):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, cfg.vocab_size, n)) for n in lengths]


@pytest.mark.parametrize("lengths,new,engine,preempts", [
    # a 21-token prompt through chunks of 4
    ((21,), 6, dict(page_size=4, num_pages=32, max_batch_size=1,
                    chunk_len=4), False),
    # prompts arriving together: chunks beside decode rows
    ((5, 19, 11), 8, dict(page_size=8, num_pages=64, max_batch_size=3,
                          chunk_len=8), False),
    # a pool two sequences outgrow: the youngest is recomputed
    ((14, 14), 20, dict(page_size=8, num_pages=6, max_batch_size=2,
                        chunk_len=32), True),
], ids=["chunked", "mixed", "preempted"])
def test_greedy_outputs_equal_the_oracle(tiny_model, oracle, lengths, new,
                                         engine, preempts):
    cfg, params = tiny_model
    prompts = _prompts(cfg, lengths, seed=3)
    eng = Engine(cfg, params, **engine)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=new))
    assert (eng.metrics.requests_preempted.value > 0) == preempts
    assert outs == [oracle(p, new) for p in prompts]
    # an all-greedy run never took the other branch
    assert eng.metrics.sample_steps_stochastic.value == 0
    assert eng.metrics.sample_steps_greedy.value > 0
    snap = eng.metrics.registry.snapshot()
    assert "serving_sample_steps_total" in str(snap)


def test_a_seeded_request_draws_the_same_alone_beside_others_and_recomputed(
        tiny_model):
    cfg, params = tiny_model
    mine, other, third = _prompts(cfg, (14, 14, 9), seed=21)
    sp = SamplingParams(max_new_tokens=20, temperature=0.9, top_k=50,
                        top_p=0.95, seed=(7 << 32) + 5)
    roomy = dict(page_size=8, num_pages=64, max_batch_size=3, chunk_len=32)
    alone = Engine(cfg, params, **roomy).generate([mine], sp)[0]
    assert len(set(alone)) > 5

    eng = Engine(cfg, params, **roomy)
    beside = eng.generate(
        [other, mine, third],
        [SamplingParams(max_new_tokens=20),
         sp, dataclasses.replace(sp, seed=99, max_new_tokens=12)])
    assert beside[1] == alone
    assert eng.metrics.sample_steps_stochastic.value > 0

    # a pool the two outgrow: ``mine`` is the youngest and is recomputed
    tight = Engine(cfg, params, page_size=8, num_pages=6, max_batch_size=2,
                   chunk_len=32)
    outs = tight.generate([other, mine],
                          [dataclasses.replace(sp, seed=1), sp])
    assert tight.metrics.requests_preempted.value > 0
    assert outs[1] == alone

    # moved to another engine with its output so far as prompt (the fleet
    # router's failover): the stream goes on where it was
    moved = Engine(cfg, params, **roomy).generate(
        [mine + alone[:8]], dataclasses.replace(sp, max_new_tokens=12))[0]
    assert moved == alone[8:]


def test_greedy_rows_beside_a_stochastic_row_are_the_argmax(tiny_model,
                                                            oracle):
    cfg, params = tiny_model
    a, b, c = _prompts(cfg, (7, 12, 5), seed=8)
    eng = Engine(cfg, params, page_size=8, num_pages=64, max_batch_size=3,
                 chunk_len=8)
    outs = eng.generate(
        [a, b, c],
        [SamplingParams(max_new_tokens=10),
         SamplingParams(max_new_tokens=10, temperature=1.5, seed=4),
         SamplingParams(max_new_tokens=10, top_k=3, top_p=0.5, seed=77)])
    assert outs[0] == oracle(a, 10)
    assert outs[2] == oracle(c, 10)      # temperature 0: top_k/top_p unused
    assert outs[1] != oracle(b, 10)
    m = eng.metrics
    assert m.sample_steps_stochastic.value > 0
    # every step program is counted once, under one path (and there is
    # one call more than programs: the last only commits)
    programs = m.steps_ahead.value + m.steps_not_ahead.value
    assert (m.sample_steps_greedy.value + m.sample_steps_stochastic.value
            == programs == m.step_phases["sample"].total - 1)


def test_step_reads_ids_and_never_the_logits(tiny_model, oracle):
    """``step()`` fetches ``[B]`` ids; the ``[B, V]`` logits stay on the
    device, held and not read; the hook is handed the row's id."""
    cfg, params = tiny_model
    eng = Engine(cfg, params, page_size=8, num_pages=64, max_batch_size=2,
                 chunk_len=8)

    class Unread:
        def __init__(self, logits):
            self.shape = logits.shape

        def __array__(self, *a, **k):
            raise AssertionError("step() read the logits")

        block_until_ready = __array__

    real_fn, fetched, handed = eng._step_fn, [], []

    def step_fn(*args):
        ids, logits, *state = real_fn(*args)
        fetched.append((ids.shape, ids.dtype))
        return (ids, Unread(logits), *state)

    real_hook = eng._sample_token

    def hook(token, req):
        handed.append(token)
        return real_hook(token, req)

    eng._step_fn, eng._sample_token = step_fn, hook
    prompts = _prompts(cfg, (9, 13), seed=5)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    assert outs == [oracle(p, 6) for p in prompts]
    assert set(fetched) == {((2,), np.dtype(np.int32))}
    assert eng.step_logits.shape == (2, cfg.vocab_size)
    assert all(type(t) is int for t in handed) and len(handed) == 12
    assert "stablehlo.case" in real_fn.lower(*eng.step_args()).as_text()


def test_the_table_is_sent_when_a_slots_tenant_changed_it(tiny_model):
    cfg, params = tiny_model
    eng = Engine(cfg, params, page_size=8, num_pages=64, max_batch_size=2,
                 chunk_len=8)
    prompts = _prompts(cfg, (6, 9, 4, 7), seed=13)
    # greedy requests, whatever else they carry: one table for all of them
    eng.generate(prompts[:2], SamplingParams(max_new_tokens=3))
    first = eng._sampling_table
    assert first is not None
    eng.generate(prompts, [SamplingParams(max_new_tokens=3, seed=i, top_k=i)
                           for i in range(4)])
    assert eng._sampling_table is first
    # a tenant that draws changes its slot's entry; the next greedy one
    # changes it back
    eng.generate(prompts[:1], SamplingParams(max_new_tokens=3,
                                             temperature=1.0))
    second = eng._sampling_table
    assert second is not first
    assert np.asarray(second)[0, 5] == len(prompts[0])
    eng.generate(prompts[:1], SamplingParams(max_new_tokens=3))
    assert not np.asarray(eng._sampling_table).any()

"""The program's own tracing of its two jitted steps (PR 26).

Host side: ``Engine.step()`` is cut into ``STEP_PHASES`` through one
helper that feeds ``RecordEvent`` (the profiler's ring and, through a
``TraceAnnotation``, any ``jax.profiler`` trace), the
``serving_step_phase_seconds{phase}`` histogram and the sampler's phase
tag.  Device side: the four Pallas kernels carry a ``name=`` and the step
programs carry ``jax.named_scope``s, which are metadata only.
"""
import collections
import contextlib
import dataclasses
import glob
import os
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import dispatch
from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
from paddle_tpu.observability.metrics import Histogram
from paddle_tpu.observability.profiling import current_phase
from paddle_tpu.profiler import Profiler, RecordEvent
from paddle_tpu.resilience import faults
from paddle_tpu.serving import Engine, SamplingParams
from paddle_tpu.serving.metrics import STEP_PHASES


@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")
    return cfg, gpt_init(cfg, jax.random.key(0), dtype=jnp.float32)


def _engine(tiny_model, **kw):
    cfg, params = tiny_model
    kw = {"page_size": 4, "num_pages": 64, "max_batch_size": 4,
          "chunk_len": 8, **kw}
    return Engine(cfg, params, **kw)


def _prompts(cfg, sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, cfg.vocab_size, n)) for n in sizes]


def _phase_samples(eng):
    return {p: eng.metrics.step_phases[p].samples() for p in STEP_PHASES}


# ------------------------------------------------------------- the series


def test_every_phase_has_one_sample_per_step(tiny_model):
    eng = _engine(tiny_model)
    eng.step()                              # nothing to run: all but
    idle = _phase_samples(eng)              # admit/plan/commit read 0
    assert {p: len(v) for p, v in idle.items()} == \
        dict.fromkeys(STEP_PHASES, 1)
    assert all(idle[p] == [0.0] for p in
               ("pack", "dispatch", "device_wait", "fetch", "sample"))
    for prompt in _prompts(tiny_model[0], (5, 19, 11)):
        eng.add_request(prompt, SamplingParams(max_new_tokens=4))
    steps = 1
    while eng.has_work():
        eng.step()
        steps += 1
    assert steps > 4
    got = _phase_samples(eng)
    assert {p: len(v) for p, v in got.items()} == \
        dict.fromkeys(STEP_PHASES, steps)
    # one step is in flight: the first call dispatches and has nothing to
    # wait for, the last has nothing left to plan and commits what the
    # call before it dispatched, and every call between spent time in
    # every phase
    sent, read = ("pack", "dispatch"), ("device_wait", "fetch", "sample")
    assert all(got[p][1] > 0 for p in sent)
    assert all(got[p][1] == 0.0 for p in read)
    assert all(got[p][-1] == 0.0 for p in sent)
    assert all(got[p][-1] > 0 for p in read)
    assert all(got[p][i] > 0 for p in STEP_PHASES
               for i in range(2, steps - 1))
    family = eng.metrics.registry.get("serving_step_phase_seconds")
    assert family.labels(phase="fetch").samples() == got["fetch"]


@pytest.mark.faultinject
def test_phase_counts_stay_aligned_when_step_raises(tiny_model):
    eng = _engine(tiny_model)
    eng.add_request(_prompts(tiny_model[0], (6,))[0],
                    SamplingParams(max_new_tokens=2))
    eng.step()
    with faults.injected_faults(faults.FaultSpec("serving.step",
                                                 "io_error")):
        with pytest.raises(OSError):
            eng.step()
    eng.step()
    got = _phase_samples(eng)
    assert {p: len(v) for p, v in got.items()} == \
        dict.fromkeys(STEP_PHASES, 3)
    # the armed fault site sees committed state: the call that raised had
    # settled the step in flight first and dispatched nothing, and the
    # call after it found nothing in flight to wait for
    assert got["device_wait"][0] == 0.0 and got["dispatch"][0] > 0
    assert got["device_wait"][1] > 0 and got["dispatch"][1] == 0.0
    assert got["device_wait"][2] == 0.0 and got["dispatch"][2] > 0
    assert eng.metrics.pipeline_drains.labels(
        reason="fault_injection").value == 1


def test_histogram_samples_returns_newest_reservoir_in_order():
    h = Histogram("probe_seconds", reservoir=4)
    assert h.samples() == []
    for v in (1, 2, 3):
        h.observe(v)
    assert h.samples() == [1.0, 2.0, 3.0]
    for v in (4, 5, 6):
        h.observe(v)
    assert h.samples() == [3.0, 4.0, 5.0, 6.0]
    out = h.samples()
    out.append(7.0)                         # a copy, not the reservoir
    assert h.samples() == [3.0, 4.0, 5.0, 6.0]


# ---------------------------------------------------------------- the ring


def test_ring_phase_events_contiguous_in_order_and_cover_the_step(
        tiny_model):
    eng = _engine(tiny_model)
    for prompt in _prompts(tiny_model[0], (7, 13), seed=1):
        eng.add_request(prompt, SamplingParams(max_new_tokens=3))
    eng.step()                              # compile outside the session
    with Profiler(with_device=False) as prof:
        calls = 0
        while eng.has_work():
            eng.step()
            calls += 1
    spans = sorted((ev for ev in prof._events if ev[0] == "X"
                    and ev[1].startswith("serving::")),
                   key=lambda ev: (ev[2], -ev[3]))
    whole = [ev for ev in spans if ev[1] == "serving::step"]
    assert len(whole) == calls >= 3
    names = [f"serving::step/{p}" for p in STEP_PHASES]
    for n, (_, _, start, end, _) in enumerate(whole):
        inside = [ev for ev in spans if ev[1] in names
                  and start <= ev[2] and ev[3] <= end]
        # the last call has nothing left to plan: it only settles the
        # step the call before it left in flight
        last = n == len(whole) - 1
        assert [ev[1] for ev in inside] == [
            name for name in names if not (last and name.endswith(
                ("/pack", "/dispatch")))]
        # in order, none overlapping the next
        for a, b in zip(inside, inside[1:]):
            assert a[3] <= b[2]
        # together they are the call: what lies between and around them
        # is the helper's own bookkeeping, microseconds
        covered = sum(ev[3] - ev[2] for ev in inside)
        assert end - start - covered < 0.05 * (end - start) + 200_000
        unified = [ev for ev in spans if ev[1] == "serving::unified_step"
                   and start <= ev[2] and ev[3] <= end]
        assert len(unified) == 1
        by = {ev[1]: ev for ev in inside}
        # it spans the call's time with the device: one program sent,
        # the one before it waited for and read
        assert by["serving::step/fetch"][3] <= unified[0][3]
        assert unified[0][3] <= by["serving::step/sample"][2]
        if not last:
            assert unified[0][2] <= by["serving::step/dispatch"][2]
            assert unified[0][2] >= by["serving::step/pack"][3]


def test_phase_helper_sets_the_samplers_tag(tiny_model):
    eng = _engine(tiny_model)
    seen = {}
    real_admit, real_fn = eng._try_admit, eng._step_fn

    def admit():
        seen["admit"] = current_phase()
        return real_admit()

    def step_fn(*a):
        seen["dispatch"] = current_phase()
        return real_fn(*a)

    eng._try_admit, eng._step_fn = admit, step_fn
    eng.add_request(_prompts(tiny_model[0], (12,))[0],
                    SamplingParams(max_new_tokens=2))
    eng.step()
    assert seen == {"admit": "admission", "dispatch": "prefill_chunk"}
    while eng.has_work():
        eng.step()
    assert seen["dispatch"] == "decode"
    assert current_phase() is None


# ------------------------------------------------------ the profiler's trace


def _host_events(trace_dir, prefix):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events if ev.name.startswith(prefix)]
    return found


def test_record_event_lands_on_a_host_plane_of_a_jax_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with RecordEvent("probe::outer"):
            inner = RecordEvent("probe::inner")
            inner.begin()
            jnp.ones((8,)).block_until_ready()
            inner.end()
    finally:
        jax.profiler.stop_trace()
    assert inner.elapsed_ns > 0
    found = {n: (s, e) for n, s, e in _host_events(str(tmp_path), "probe::")}
    assert set(found) == {"probe::outer", "probe::inner"}
    assert found["probe::outer"][0] <= found["probe::inner"][0]
    assert found["probe::inner"][1] <= found["probe::outer"][1]


def test_step_phases_land_in_a_jax_trace_without_a_profiler_session(
        tiny_model, tmp_path):
    eng = _engine(tiny_model)
    eng.add_request(_prompts(tiny_model[0], (9,))[0],
                    SamplingParams(max_new_tokens=2))
    eng.step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        calls = 0
        while eng.has_work():
            eng.step()
            calls += 1
    finally:
        jax.profiler.stop_trace()
    counts = collections.Counter(
        n for n, _, _ in _host_events(str(tmp_path), "serving::"))
    # every call settles the step before it; all but the last send one
    assert counts == {"serving::step": calls,
                      "serving::unified_step": calls,
                      **{f"serving::step/{p}": calls - (p in ("pack",
                                                              "dispatch"))
                         for p in STEP_PHASES}}


# ------------------------------------------------------------ kernel names


def _tpu_lowering(fn, *shapes):
    """StableHLO text of ``fn`` lowered for the TPU platform from here:
    no compile, no libtpu."""
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def _kernel_names(text):
    return collections.Counter(
        re.findall(r'kernel_name\s*=\s*"([^"]+)"', text))


@pytest.fixture(scope="module")
def flash_lowering():
    from paddle_tpu.kernels.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, path=dispatch.MOSAIC
                               ).astype(jnp.float32).sum()

    sds = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)
    return _kernel_names(_tpu_lowering(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), sds, sds, sds))


@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dkdv",
                                  "flash_bwd_dq"])
def test_flash_kernel_lowering_carries_its_name(flash_lowering, name):
    assert flash_lowering[name] == 1
    assert set(flash_lowering) == {"flash_fwd", "flash_bwd_dkdv",
                                   "flash_bwd_dq"}


def test_ragged_kernel_lowering_carries_its_name():
    from paddle_tpu.kernels.paged_attention import ragged_paged_attention

    bf16, i32 = jnp.bfloat16, jnp.int32
    pages = jax.ShapeDtypeStruct((32, 16, 4, 128), bf16)
    tables = jax.ShapeDtypeStruct((2, 8), i32)
    lens = jax.ShapeDtypeStruct((2,), i32)
    ragged = _tpu_lowering(
        lambda *a: ragged_paged_attention(*a, path=dispatch.MOSAIC),
        jax.ShapeDtypeStruct((2, 8, 4, 128), bf16), pages, pages, tables,
        lens, lens)
    assert _kernel_names(ragged) == {"ragged_paged_attention": 1}
    # a decode call (one query slot a row) is the same kernel
    decode = _tpu_lowering(
        lambda q, kp, vp, tables, lens: ragged_paged_attention(
            q[:, None], kp, vp, tables, (lens > 0).astype(i32), lens,
            path=dispatch.MOSAIC)[:, 0],
        jax.ShapeDtypeStruct((2, 4, 128), bf16), pages, pages, tables, lens)
    assert _kernel_names(decode) == {"ragged_paged_attention": 1}


# ---------------------------------------------------- scopes are metadata


def _opcode_histogram(hlo_text):
    """opcode -> number of instructions, over an HLO module's text."""
    hist = collections.Counter()
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        if rhs.startswith("("):             # a tuple type: to its close
            depth = 0
            for i, ch in enumerate(rhs):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    rhs = rhs[i + 1:].lstrip()
                    break
        else:
            rhs = rhs.partition(" ")[2]
        m = re.match(r"([a-z][a-z0-9\-]*)\(", rhs)
        if m:
            hist[m.group(1)] += 1
    return hist


def _train_step_text():
    from paddle_tpu.distributed.engine import EngineConfig, HybridEngine

    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32",
                              num_layers=2, remat="full")
    eng = HybridEngine(cfg, devices=jax.devices()[:1],
                       engine_cfg=EngineConfig(accum_steps=1))
    params, opt = eng.init(seed=0)
    tokens = jnp.zeros((2, 32), jnp.int32)
    return eng.build_step().lower(
        params, opt, tokens, tokens, jnp.float32(1e-3),
        jnp.uint32(0)).compile().as_text()


def _serve_step_text():
    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32",
                              num_layers=2)
    eng = Engine(cfg, gpt_init(cfg, jax.random.key(0), dtype=jnp.float32),
                 page_size=4, num_pages=16, max_batch_size=2, chunk_len=4)
    return eng._step_fn.lower(*eng.step_args()).compile().as_text()


@pytest.mark.parametrize("step_text,scopes", [
    (_train_step_text, ("forward_backward", "optimizer", "attn", "mlp",
                        "ce_head")),
    (_serve_step_text, ("attn", "kv_write", "mlp", "lm_head")),
], ids=["train", "serve"])
def test_scopes_are_metadata(step_text, scopes):
    """The optimized program has the same instructions, by opcode, with
    the scopes and with ``jax.named_scope`` turned into nothing; with
    them, every scope shows in some instruction's ``op_name``."""
    # the persistent cache keys on the program without its metadata: it
    # would hand the second compile the first one's text
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with_scopes = step_text()
        with mock.patch.object(jax, "named_scope",
                               lambda name: contextlib.nullcontext()):
            without = step_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    op_names = re.findall(r'op_name="([^"]+)"', with_scopes)
    for scope in scopes:
        pat = re.compile(r"(^|[/(])" + scope + r"([/)]|$)")
        assert any(pat.search(n) for n in op_names), scope
        assert not any(pat.search(n) for n in
                       re.findall(r'op_name="([^"]+)"', without)), scope
    hist = _opcode_histogram(with_scopes)
    assert sum(hist.values()) > 100
    assert hist == _opcode_histogram(without)

"""The compile watchdog says what a watched step's compiled program is made
of: every instruction that runs as a device operation under the ``op_name``
path jax recorded for it, from shapes alone, after the engine is gone.

The TPU compiler's side (the Mosaic call under ``attn``, ``flash_fwd`` once
as ``forward`` and once as ``recompute``) is in
``tests/test_tpu_aot_compile.py``; the benchmark's readers of the table in
``benchmark/tests/test_scope_share.py``.
"""
import dataclasses
import gc
import re
import weakref
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
from paddle_tpu.observability import compile_watchdog as cw
from paddle_tpu.serving import Engine

SERVE = "serving::unified_step"
TRAIN = "hybrid_engine::step"


def _tiny_engine(**knobs):
    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32",
                              num_layers=2)
    knobs = {"page_size": 4, "num_pages": 16, "max_batch_size": 2,
             "chunk_len": 4, **knobs}
    return Engine(cfg, gpt_init(cfg, jax.random.key(0), dtype=jnp.float32),
                  **knobs)


def _toy_step():
    """A differentiated scan of checkpointed layers under nested scopes."""
    def layer(x, w):
        with jax.named_scope("mix"):
            with jax.named_scope("inner"):
                h = jnp.tanh(x @ w)
            return h * 2.0

    def loss(ws, x):
        with jax.named_scope("stack"):
            x, _ = jax.lax.scan(
                lambda x, w: (jax.checkpoint(layer)(x, w), None), x, ws)
        with jax.named_scope("head"):
            return jnp.sum(x ** 2)

    return jax.jit(jax.grad(loss)), (jnp.ones((3, 8, 8)), jnp.ones((4, 8)))


def _top_level(text):
    """The instructions of the computations that are not a fusion's or a
    reducer's insides, read off the text independently of the parser."""
    inside = set(re.findall(r" fusion\(.*?calls=%([^\s,)}]+)", text))
    inside |= {callee for line in text.splitlines() if " call(" not in line
               for callee in re.findall(r"to_apply=%([^\s,)}]+)", line)}
    names, keep = [], False
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            keep = head.group(1) not in inside
        ins = re.match(r"^\s+(?:ROOT )?%(\S+) = ", line)
        if ins and keep:
            names.append((ins.group(1), line))
    return names


def test_toy_program_every_instruction_under_its_path_and_pass():
    step, (ws, x) = _toy_step()
    wd = cw.CompileWatchdog()
    wd.watch(step, name="toy::step", abstract_args=(ws, x))
    table = wd.instruction_table("toy::step")
    text = step.lower(ws, x).compile().as_text()
    top = _top_level(text)
    assert len(top) > 20 and {n for n, _ in top} == set(table)
    for name, line in top:
        m = re.search(r'metadata=\{[^}]*op_name="([^"]*)"', line)
        if m or " fusion(" not in line:     # else: its root's path
            assert table[name] == (m.group(1) if m else ""), name
    # the scan's layers, by pass: the first run, the replay that the
    # backward pass asks for, and the transposed part
    by_pass = {}
    for path in table.values():
        if "inner" in cw.named_scopes(path) \
                and cw.leaf_primitive(path) == "dot_general":
            assert cw.named_scopes(path) == ("stack", "mix", "inner")
            by_pass.setdefault(cw.pass_of(path), []).append(path)
    assert set(by_pass) == {"forward", "recompute", "backward"}
    assert all("rematted_computation" in p for p in by_pass["recompute"])
    assert all("transpose(jvp(stack))" in p and "rematted" not in p
               for p in by_pass["backward"])
    head = [p for p in table.values() if "head" in cw.named_scopes(p)]
    assert head and all(cw.pass_of(p) in ("forward", "backward")
                        for p in head)


HAND_MADE = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%p.1, %p.1), metadata={op_name="jit(f)/attn/kv_write/mul" stack_frame_id=3}
  ROOT %scatter.2 = f32[4]{0} add(%mul.1, %p.1)
}

%fused_computation.2 (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  ROOT %neg.3 = f32[4]{0} negate(%p.2), metadata={op_name="jit(f)/mlp/neg"}
}

%region_0.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%body.6 (arg: (f32[4])) -> (f32[4]) {
  %arg = (f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg), index=0
  %fusion.10 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/closed_call/mlp/neg"}
  ROOT %tuple.2 = (f32[4]{0}) tuple(%fusion.10)
}

ENTRY %main.12 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.7 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1, backend_config={"k":"metadata={op_name=\\"no\\"}"}
  %fusion.8 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.2
  %c.1 = f32[] constant(0)
  %reduce.3 = f32[] reduce(%fusion.7, %c.1), dimensions={0}, to_apply=%region_0.5
  %kernel.4 = f32[4]{0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(f)/attn/kernel/pallas_call" stack_frame_id=9}
  %while.5 = (f32[4]{0}) while(%tuple.1), condition=%cond.6, body=%body.6, metadata={op_name="jit(f)/while"}
  ROOT %copy.4 = f32[4]{0} copy(%kernel.4), metadata={op_name="jit(f)/ssm/reshape;jit(f)/ssm/transpose"}
}
"""


def test_parser_on_a_hand_made_module():
    """What is in: the entry's and the loop body's instructions.  What is
    not: a fusion's and a reducer's insides.  A fusion without metadata
    takes its fused computation's root's path, or the first path inside;
    of paths XLA joined with ";" the first is kept."""
    table = cw.parse_instruction_table(HAND_MADE)
    assert table == {
        "x.1": "x",
        "fusion.7": "jit(f)/attn/kv_write/mul",
        "fusion.8": "jit(f)/mlp/neg",
        "c.1": "", "reduce.3": "",
        "kernel.4": "jit(f)/attn/kernel/pallas_call",
        "while.5": "jit(f)/while", "copy.4": "jit(f)/ssm/reshape",
        "arg": "", "gte.1": "", "tuple.2": "",
        "fusion.10": "jit(f)/while/body/closed_call/mlp/neg"}


@pytest.mark.parametrize("path,scopes,which,leaf", [
    ("jit(_step)/jit(step)/while/body/closed_call/mlp/tf,fd->td/dot_general",
     ("mlp",), None, "dot_general"),
    ("jit(_step)/jit(step)/while/body/closed_call/attn/kv_write/scatter",
     ("attn", "kv_write"), None, "scatter"),
    ("jit(_step_local)/forward_backward/jvp()/while/body/closed_call/attn/"
     "flash_fwd/pallas_call", ("forward_backward", "attn", "flash_fwd"),
     "forward", "pallas_call"),
    ("jit(_step_local)/forward_backward/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/attn/flash_fwd/pallas_call",
     ("forward_backward", "attn", "flash_fwd"), "recompute", "pallas_call"),
    ("jit(_step_local)/forward_backward/transpose(jvp(ce_head))/while/body/"
     "closed_call/bsd,vd->bsv/dot_general", ("forward_backward", "ce_head"),
     "backward", "dot_general"),
    ("jit(_step)/sample/cond/branch_1_fun/vmap(jit(_gumbel))/jit(_uniform)/"
     "vmap()/while/body/closed_call/add", ("sample",), None, "add"),
    ("jit(_step)/jit(step)/while/body/dynamic_slice", (), None,
     "dynamic_slice"),
    ("", (), None, ""),
])
def test_helpers_on_a_path(path, scopes, which, leaf):
    assert cw.named_scopes(path) == scopes
    assert cw.pass_of(path) == which
    assert cw.leaf_primitive(path) == leaf
    assert cw.innermost_scope(path, ("attn", "kv_write", "mlp", "ce_head")) \
        == next((s for s in reversed(scopes)
                 if s in ("attn", "kv_write", "mlp", "ce_head")), None)


def test_table_outlives_the_engine_and_keeps_no_buffer():
    eng = _tiny_engine()
    leaf = weakref.ref(jax.tree_util.tree_leaves(eng.params)[0])
    pool = weakref.ref(eng.cache.state_arrays()[0])
    engine = weakref.ref(eng)
    del eng
    gc.collect()
    assert leaf() is None and pool() is None and engine() is None
    table = cw.instruction_table(SERVE)
    scopes = {s for path in table.values() for s in cw.named_scopes(path)}
    assert {"attn", "kv_write", "mlp", "lm_head", "sample"} <= scopes
    assert cw.instruction_table(SERVE) is table        # parsed once


def test_registration_and_steps_lower_and_compile_nothing_extra():
    """Building an engine and stepping it never reaches the ahead-of-time
    stages (``jit(...).lower``, ``Lowered.compile``): the table costs
    nothing until somebody asks, and then one of each."""
    lowered, compiled = [], []
    lower, compile_ = jax.stages.Traced.lower, jax.stages.Lowered.compile

    def counted_lower(self, *a, **k):
        lowered.append(1)
        return lower(self, *a, **k)

    def counted_compile(self, *a, **k):
        compiled.append(1)
        return compile_(self, *a, **k)

    with mock.patch.object(jax.stages.Traced, "lower", counted_lower), \
            mock.patch.object(jax.stages.Lowered, "compile",
                              counted_compile):
        eng = _tiny_engine()
        eng.add_request([1, 2, 3, 4, 5])
        for _ in range(4):
            eng.step()
        assert (lowered, compiled) == ([], [])
        assert cw.instruction_table(SERVE)
        assert (lowered, compiled) == ([1], [1])
        cw.instruction_table(SERVE)
        assert (lowered, compiled) == ([1], [1])


def test_abstract_lowering_is_the_program_that_runs():
    eng = _tiny_engine()
    real = eng._step_fn.lower(*eng.step_args()).compile().as_text()
    assert cw.instruction_table(SERVE) == cw.parse_instruction_table(real)
    args, kwargs = eng._step_fn.abstract_args
    assert not kwargs and all(
        isinstance(a, jax.ShapeDtypeStruct)
        for a in jax.tree_util.tree_leaves(args))
    assert eng._step_fn.lower(*args).compile().as_text() == real


def test_newest_registration_of_a_name_wins():
    _tiny_engine(max_batch_size=2)
    first = cw.instruction_table(SERVE)
    _tiny_engine(max_batch_size=3)
    (*_, batch, sampling, _), _ = \
        cw.default_watchdog()._programs[SERVE].abstract_args
    assert sampling.shape[0] == batch.query_lens.shape[0] == 3
    second = cw.instruction_table(SERVE)
    assert second is not first and second


def test_unknown_name_and_undescribed_function_give_none():
    wd = cw.CompileWatchdog()
    assert wd.instruction_table("nobody::step") is None
    fn = wd.watch(jax.jit(lambda x: x + 1), name="plain::fn")
    assert fn.abstract_args is None
    fn(jnp.ones(3))
    assert wd.instruction_table("plain::fn") is None
    # a program that cannot lower from what it was described with says so
    # in the log and gives None
    bad = wd.watch(jax.jit(lambda x, y: x @ y), name="bad::fn")
    bad.describe(jnp.ones((2, 3)), jnp.ones((4, 5)))
    assert wd.instruction_table("bad::fn") is None


def test_abstract_like_keeps_shape_type_and_commitment():
    device = jax.devices()[0]
    put = jax.device_put(jnp.ones((2, 3), jnp.bfloat16), device)
    free = jnp.ones((4,), jnp.int32)
    shapes = cw.abstract_like({"put": put, "free": free,
                               "host": np.zeros((5,), np.float32),
                               "scalar": 2.0,
                               "given": jax.ShapeDtypeStruct(
                                   (7,), jnp.uint32, sharding=put.sharding)})
    assert (shapes["put"].shape, shapes["put"].dtype) == \
        ((2, 3), jnp.bfloat16)
    assert shapes["put"].sharding == put.sharding
    assert shapes["free"].sharding is None and not free.committed
    assert shapes["host"].sharding is None
    assert shapes["scalar"].weak_type and shapes["scalar"].shape == ()
    assert shapes["given"].sharding == put.sharding
    assert not any(isinstance(a, jax.Array)
                   for a in jax.tree_util.tree_leaves(shapes))


def test_train_engine_describes_its_step_at_the_first_call():
    from paddle_tpu.distributed.engine import EngineConfig, HybridEngine

    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32",
                              num_layers=2, remat="full")
    eng = HybridEngine(cfg, devices=jax.devices()[:1],
                       engine_cfg=EngineConfig(accum_steps=1))
    params, opt = eng.init(seed=0)
    tokens = jnp.zeros((2, 32), jnp.int32)
    fn = eng.build_step()
    assert fn.abstract_args is None
    params, opt, _ = eng.step(params, opt, tokens, tokens)
    described = fn.abstract_args
    assert described is not None
    params, opt, _ = eng.step(params, opt, tokens, tokens)
    assert fn.abstract_args is described                # once
    table = cw.instruction_table(TRAIN)
    passes = {cw.pass_of(p) for p in table.values()}
    assert passes == {None, "forward", "recompute", "backward"}
    scopes = {s for p in table.values() for s in cw.named_scopes(p)}
    assert {"forward_backward", "optimizer", "attn", "mlp",
            "ce_head"} <= scopes
    replayed = [p for p in table.values() if cw.pass_of(p) == "recompute"]
    assert any("attn" in cw.named_scopes(p) for p in replayed)
    real = fn.lower(params, opt, tokens, tokens, jnp.float32(1e-3),
                    jnp.uint32(0)).compile().as_text()
    assert table == cw.parse_instruction_table(real)

"""AOT-compile for a TPU topology on a host that has no chip.

``jax.experimental.topologies`` describes a v5e host to the installed
libtpu, and ``jit(...).lower(...).compile()`` against its devices runs
the real TPU compiler — Mosaic included — without a device.  That answers
"does it compile, and how much memory does XLA plan for it" at no chip
cost; whether it *executes* correctly only ``chip_smoke.py`` can say.

- ``tests/test_tpu_aot_compile.py`` (tier-1) compiles the Pallas kernels
  through :func:`compile_kernels`, the kernel path forced to Mosaic by the
  kernels' explicit ``path=`` argument, and the serving step at a small
  pool through :func:`lower_serve_step`, to see that nothing in it moves
  a page pool.
- ``python -m tools.tpu_aot`` also compiles the two whole steps
  ``chip_smoke.py`` runs, at its shapes, and prints XLA's memory analysis:
  the check to make before spending a chip call.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

TOPOLOGY = "v5e:2x2"


def topology_devices():
    """The four TpuDevice descriptions of one v5e host (no chip needed)."""
    return topologies.get_topology_desc(TOPOLOGY, "tpu").devices


def _on(sharding, *shape_dtypes):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shape_dtypes]


def compile_kernels(device):
    """{name: Compiled} for the equal-heads ragged kernel at the GPT
    serving cell's shapes (16 rows, 16 heads of 128, pages of 16, a table
    128 wide) — a 128-token chunk on one layer's pool with the work list
    built inside (``ragged_q128``: the wide and the narrow body), the
    one query slot of a decode-only call (``ragged_q1``), the serving step's
    call, a layer of a stacked pool with the list handed in
    (``ragged_stacked``), the 4 heads a chip holds under ``mp=4``
    (``ragged_h4``) — and flash forward+backward at head dims 64 and 128,
    the shapes ``gpt3-1.3b``/``gpt2-medium`` give them."""
    from paddle_tpu.kernels import dispatch
    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.kernels.paged_attention import (ragged_paged_attention,
                                                    ragged_work_items)

    one = SingleDeviceSharding(device)
    bf16, i32 = jnp.bfloat16, jnp.int32
    out = {}

    B, hd, pages, ps, max_pages = 16, 128, 256, 16, 128

    @functools.partial(jax.jit, static_argnames="listed")
    def ragged(*a, layer=None, listed=False):
        items = ragged_work_items(a[4], a[5], ps, max_pages) if listed \
            else None
        return ragged_paged_attention(*a, path=dispatch.MOSAIC, layer=layer,
                                      items=items)

    def ragged_args(Q, *stack, H=16):
        pool = ((*stack, pages, ps, H, hd), bf16)
        return _on(one, ((B, Q, H, hd), bf16), pool, pool,
                   ((B, max_pages), i32), ((B,), i32), ((B,), i32))

    for Q in (128, 1):
        out[f"ragged_q{Q}"] = ragged.lower(*ragged_args(Q)).compile()
    out["ragged_h4"] = ragged.lower(*ragged_args(128, H=4)).compile()
    out["ragged_stacked"] = ragged.lower(
        *ragged_args(128, 4), layer=_on(one, ((), i32))[0],
        listed=True).compile()

    def flash_loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               path=dispatch.MOSAIC).astype(jnp.float32).sum()

    flash = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2)))
    for shape in ((4, 16, 2048, 64), (2, 16, 2048, 128)):
        out[f"flash_hd{shape[-1]}"] = flash.lower(
            *_on(one, *[(shape, bf16)] * 3)).compile()
    return out


@contextlib.contextmanager
def as_if_on_tpu():
    """Whole programs pick their kernel path (and the serving engine its
    donation) from ``jax.default_backend()``; under this it answers "tpu",
    so the program lowered here is the one the chip machine lowers."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        yield


def lower_train_step(devices, batch=8, seq=2048, **layout):
    """The ``HybridEngine`` step ``chip_smoke.py``'s train leg runs,
    lowered for ``devices`` under ``layout`` (dp/pp/sharding/sep/mp)."""
    from paddle_tpu.distributed.engine import EngineConfig, HybridEngine
    from paddle_tpu.models.gpt import GPT_CONFIGS

    cfg = dataclasses.replace(GPT_CONFIGS["gpt3-1.3b"], use_flash=True,
                              remat="full", dtype="bfloat16")
    pp = layout.get("pp", 1)
    eng = HybridEngine(cfg, devices=devices, engine_cfg=EngineConfig(
        accum_steps=1, opt_dtype="bfloat16",
        num_microbatches=4 if pp > 1 else 1), **layout)

    def sharded(shapes, specs):
        return jax.tree_util.tree_map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(eng.mesh, spec)),
            shapes, specs)

    params = sharded(jax.eval_shape(eng.model.init, jax.random.key(0)),
                     eng.param_specs())
    opt = sharded(jax.eval_shape(eng._init_opt, params), eng.opt_specs())
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32,
        sharding=NamedSharding(eng.mesh, eng.batch_spec()))
    rep = NamedSharding(eng.mesh, P())
    with as_if_on_tpu():
        return eng.build_step().lower(
            params, opt, tokens, tokens,
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep))


def lower_serve_step(devices, num_pages=1024, max_batch_size=8,
                     chunk_len=128, mp=1):
    """The serving engine's unified step at ``chip_smoke.py``'s knobs, on
    one device or (``mp`` > 1) on a ``build_mesh(mp=mp)`` engine."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
    from paddle_tpu.serving import Engine

    cfg = GPT_CONFIGS["gpt3-1.3b"]
    params = jax.eval_shape(lambda: gpt_init(cfg))
    mesh = mesh_mod.build_mesh(mp=mp, devices=devices[:mp]) if mp > 1 \
        else None
    # the engine places real arrays while it is built; these devices are
    # descriptions, so placement is skipped and only shapes go through
    with as_if_on_tpu(), mock.patch.object(jax, "device_put",
                                           lambda x, *a, **k: x):
        # 1 page: the engine allocates its pool on the host here; only the
        # lowered shapes below are the real ones
        eng = Engine(cfg, params, page_size=16, num_pages=1,
                     max_batch_size=max_batch_size, chunk_len=chunk_len,
                     mesh=mesh)
        pool = (cfg.num_layers, num_pages, 16, cfg.num_heads, cfg.head_dim)
        if mesh is None:
            rep = pages = SingleDeviceSharding(devices[0])
            p_sh = jax.tree_util.tree_map(lambda _: rep, params)
        else:
            rep, pages = NamedSharding(mesh, P()), eng._page_sharding
            p_sh = mesh_mod.sharding_tree(params, mesh)
        return eng._step_fn.lower(*eng.step_args(
            jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                params, p_sh),
            _on(pages, (pool, cfg.jdtype()), (pool, cfg.jdtype())),
            sharding=rep))


def _lower_recurrent_serve_step(devices, cfg, init, num_pages,
                                max_batch_size, chunk_len, page_size,
                                num_window_pages=None,
                                held_window_pages=None):
    """The unified step of a served model with state of its own kinds
    (per-row state, or window pools: ``num_window_pages`` lowered,
    ``held_window_pages`` held here), on one device, every state pool at
    its real shape and donated."""
    from paddle_tpu.serving import Engine

    params = jax.eval_shape(lambda: init(cfg))
    one = SingleDeviceSharding(devices[0])
    with as_if_on_tpu():
        # 1 page held here (and the rows' state, on the host); the lowered
        # shapes are the real ones
        window = {} if num_window_pages is None \
            else {"num_window_pages": num_window_pages}
        eng = Engine(cfg, params, page_size=page_size, num_pages=1,
                     max_batch_size=max_batch_size, chunk_len=chunk_len,
                     num_window_pages=held_window_pages)
        state = [(shape, dtype) for _, shape, dtype, _ in
                 eng.model.state_spec(num_pages=num_pages,
                                      page_size=page_size,
                                      max_batch_size=max_batch_size,
                                      **window)]
        return eng._step_fn.lower(*eng.step_args(
            jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one), params),
            _on(one, *state), sharding=one))


def lower_hybrid_serve_step(devices, num_pages=8192, max_batch_size=16,
                            chunk_len=512, page_size=64,
                            config="minicpm-sala-8l"):
    """The unified step of the sparse-plus-lightning decoder at the
    benchmark cell's knobs, on one device: its four state pools donated."""
    from paddle_tpu.models.hybrid import HYBRID_CONFIGS, hybrid_init

    return _lower_recurrent_serve_step(
        devices, HYBRID_CONFIGS[config], hybrid_init, num_pages,
        max_batch_size, chunk_len, page_size)


def lower_ssm_serve_step(devices, num_pages=256, max_batch_size=64,
                         chunk_len=128, page_size=512,
                         config="falcon-h1-34b-6l"):
    """The unified step of the parallel-mixer decoder (a state-space mixer
    beside grouped-query attention in every block) at the benchmark cell's
    knobs, on one device: its four state pools donated."""
    from paddle_tpu.models.ssm import SSM_CONFIGS, ssm_init

    return _lower_recurrent_serve_step(
        devices, SSM_CONFIGS[config], ssm_init, num_pages, max_batch_size,
        chunk_len, page_size)


def lower_moe_window_serve_step(devices, num_pages=2240,
                                num_window_pages=256, max_batch_size=64,
                                chunk_len=1024, page_size=512,
                                config="laguna-s-2.1-8l"):
    """The unified step of the sparse-expert decoder with sliding-window
    layers at the benchmark cell's knobs, on one device: its two groups of
    page pools donated, both page tables in the batch."""
    from paddle_tpu.models.moe_window import (MOE_WINDOW_CONFIGS,
                                              moe_window_init)
    from paddle_tpu.serving.kv_cache import window_pages_per_row

    cfg = MOE_WINDOW_CONFIGS[config]
    # one row's window pages held here, on the host
    return _lower_recurrent_serve_step(
        devices, cfg, moe_window_init, num_pages, max_batch_size, chunk_len,
        page_size, num_window_pages=num_window_pages,
        held_window_pages=window_pages_per_row(cfg.window, page_size,
                                               chunk_len))


def _report(name, compile_fn):
    t0 = time.perf_counter()
    compiled = compile_fn()
    dt = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    mosaic = "tpu_custom_call" in compiled.as_text()
    print(f"{name}: {dt:.1f}s  args "
          f"{mem.argument_size_in_bytes / gib:.2f} GiB + temp "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB (alias "
          f"{mem.alias_size_in_bytes / gib:.2f})  tpu_custom_call={mosaic}",
          flush=True)
    return mosaic


def main(argv):
    devices = topology_devices()
    ok = True
    ok &= _report("kernels (ragged q128/q1/h4/stacked, flash hd64/hd128; "
                  "memory is the last one's)",
                  lambda: list(compile_kernels(devices[0]).values())[-1])
    ok &= _report("train 1.3b b8xs2048 one chip",
                  lambda: lower_train_step(devices[:1]).compile())
    ok &= _report("serve 1.3b B16 chunk128 1024 pages",
                  lambda: lower_serve_step(devices,
                                           max_batch_size=16).compile())
    ok &= _report("serve hybrid 8l B16 chunk512 8192 pages of 64",
                  lambda: lower_hybrid_serve_step(devices).compile())
    ok &= _report("serve ssm 6l B64 chunk128 256 pages of 512",
                  lambda: lower_ssm_serve_step(devices).compile())
    ok &= _report("serve moe-window 8l B64 chunk1024 2240 + 256 pages of 512",
                  lambda: lower_moe_window_serve_step(devices).compile())
    if "--four" in argv:
        ok &= _report("train pp=2 x mp=2",
                      lambda: lower_train_step(devices, pp=2, mp=2).compile())
        ok &= _report("train dp=2 x mp=2",
                      lambda: lower_train_step(devices, dp=2, mp=2).compile())
        ok &= _report("serve mp=4 (per chip)",
                      lambda: lower_serve_step(devices, mp=4).compile())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

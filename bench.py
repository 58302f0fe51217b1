#!/usr/bin/env python
"""Benchmark harness — BASELINE.md protocol on the real chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

Primary metric: GPT tokens/sec/chip on the largest BASELINE GPT config
that fits one chip's HBM (gpt3-1.3b headline, gpt2-medium continuity),
measured with the Benchmark timer (reference semantics:
python/paddle/profiler/timer.py:325 — skip warmup, steady-state ips).

Process architecture: every section runs in its OWN subprocess.  One
section's OOM must not poison another — in round 4 a single 1.3B compile
OOM cascaded into RESOURCE_EXHAUSTED failures for gpt2-large AND the
flash microbenchmark in the same process.  On an HBM OOM the subprocess
stderr carries XLA's memory breakdown; the orchestrator greps it and
records the peak-bytes summary in the bench extra.

vs_baseline derivation (north star: GPT-3 6.7B at >=50% of A100+NCCL
tokens/sec/chip): A100 bf16 peak 312 TF at the ~45% MFU Megatron reports
=> ~140 TF effective => 50% of that is 70 TF effective per chip.  Hitting
70 TF on this chip's peak is an MFU target of 70/peak; vs_baseline is
measured_MFU / that target, so vs_baseline >= 1.0 means the per-chip
efficiency bar of the north star is met on this hardware.

Progress goes to stderr; stdout carries only the JSON line.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


A100_EFFECTIVE_TF = 312.0 * 0.45      # Megatron-class A100 utilisation
NORTH_STAR_FRACTION = 0.5

# The 1.3B single-chip ladder: each rung is tried in its own subprocess,
# first success wins.  Memory levers walked: batch size, then sequence
# length (VERDICT r4 weak #2: the ladder must walk memory levers, not
# just configs).  All rungs use master-less bf16 Adam slots (8 B/param
# steady state) + full per-block remat.
LADDER_13B = [
    # measured r5: b8 10,827 tok/s 46.7% MFU; b16 10,126 (43.7%); b4
    # 9,905 (42.7%); b8 remat=dots compile-OOMs by 1.45G
    ("gpt3-1.3b", dict(batch=8, seq=2048, accum=1, remat="full",
                       opt_dtype="bfloat16")),
    ("gpt3-1.3b", dict(batch=4, seq=2048, accum=1, remat="full",
                       opt_dtype="bfloat16")),
    ("gpt3-1.3b", dict(batch=2, seq=2048, accum=1, remat="full",
                       opt_dtype="bfloat16")),
    ("gpt3-1.3b", dict(batch=2, seq=1024, accum=1, remat="full",
                       opt_dtype="bfloat16")),
    ("gpt2-large", dict(batch=8, seq=1024, accum=2, remat="dots",
                        opt_dtype="bfloat16")),
]


def device_peak_tflops():
    # the per-device-kind peak table lives with the MFU estimator
    # (observability.goodput.PEAK_FLOPS, PADDLE_TPU_PEAK_FLOPS env
    # override) — bench and the training goodput monitor must agree on
    # the denominator or their MFU numbers silently diverge
    from paddle_tpu.observability.goodput import device_peak_flops

    flops, kind = device_peak_flops()
    if flops is None:
        raise RuntimeError(f"no peak FLOP/s known for device kind {kind!r}")
    return flops / 1e12, kind


def gpt_nparams(cfg):
    D, F, L, V = cfg.hidden, cfg.ffn_hidden, cfg.num_layers, cfg.vocab_size
    per_block = 3 * D * D + D * D + 2 * D * F + 3 * D + 2 * F + 4 * D
    return V * D + cfg.max_seq_len * D + L * per_block + 2 * D


def bench_gpt(name, steps, warmup, batch, seq, accum=4, remat="dots",
              opt_dtype="float32"):
    """One single-chip GPT training-throughput measurement with the full
    BASELINE.md §3 protocol fields recorded."""
    import dataclasses

    import jax

    from paddle_tpu.distributed.engine import EngineConfig, HybridEngine
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.profiler.timer import Benchmark

    from paddle_tpu.core.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = GPT_CONFIGS[name]
    n_params = gpt_nparams(cfg)
    seq = min(seq, cfg.max_seq_len)
    cfg = dataclasses.replace(cfg, use_flash=True, remat=remat,
                              dtype="bfloat16")
    log(f"[gpt] config={name} params={n_params/1e6:.0f}M batch={batch} "
        f"seq={seq} accum={accum} remat={remat} opt_dtype={opt_dtype}")

    eng = HybridEngine(cfg, dp=1, pp=1, sharding=1, sep=1, mp=1,
                       devices=jax.devices()[:1],
                       engine_cfg=EngineConfig(accum_steps=accum,
                                               opt_dtype=opt_dtype))
    params, opt = eng.init(seed=0)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.concatenate(
        [tokens[:, 1:], np.full((batch, 1), -100)], 1).astype(np.int32)

    t0 = time.perf_counter()
    params, opt, loss = eng.step(params, opt, tokens, labels)
    first_loss = float(loss)
    log(f"[gpt] compile+first step {time.perf_counter()-t0:.1f}s "
        f"loss={first_loss:.3f}")

    # steady-state: dispatch the whole window, sync once at the end
    # (donation chains the steps, so the final loss value implies all
    # steps executed)
    for _ in range(warmup):
        params, opt, loss = eng.step(params, opt, tokens, labels)
    float(loss)
    bm = Benchmark(warmup_steps=0)
    bm.step_start()
    for _ in range(steps):
        params, opt, loss = eng.step(params, opt, tokens, labels)
    final_loss = float(loss)
    bm.step_end(num_samples=steps * batch * seq)
    info = bm.step_info(unit="tokens")
    tok_s = info["ips"]
    info["avg_batch_cost"] = info["avg_batch_cost"] / max(steps, 1)
    loss = final_loss

    D, L = cfg.hidden, cfg.num_layers
    flops_per_token = 6 * n_params + 6 * L * seq * D   # causal-aware
    peak_tf, kind = device_peak_tflops()
    mfu = tok_s * flops_per_token / (peak_tf * 1e12)
    target_mfu = (NORTH_STAR_FRACTION * A100_EFFECTIVE_TF) / peak_tf
    # publish so the section's embedded registry snapshot (and a
    # scraping operator) sees the same number the JSON reports
    from paddle_tpu.observability import default_registry

    default_registry().gauge(
        "training_mfu", "model FLOPs utilisation vs device peak").set(mfu)
    log(f"[gpt] {tok_s:.0f} tokens/s/chip  mfu={mfu*100:.1f}%  "
        f"({kind}, target mfu {target_mfu*100:.1f}%)")
    return {
        "config": name, "tokens_per_sec_per_chip": tok_s, "mfu": mfu,
        "target_mfu": target_mfu, "device": kind,
        "avg_step_ms": info["avg_batch_cost"] * 1e3,
        "final_loss": loss,
        # BASELINE.md §3 protocol fields
        "protocol": {
            "params_m": round(n_params / 1e6, 1),
            "chips": 1,
            "mesh": {"dp": 1, "tp": 1, "pp": 1, "sharding": 1},
            "global_batch": batch, "micro_batch": batch // accum,
            "seq_len": seq, "dtype": "bfloat16", "opt_dtype": opt_dtype,
            "remat": remat,
            "compiler": f"jax {jax.__version__}",
        },
    }


def bench_flash_vs_xla():
    """Microbenchmark: pallas flash kernel vs naive XLA attention,
    fwd+bwd, causal, bf16."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    flash_attention_available)
    from paddle_tpu.ops.attention import _naive_attention

    B, H, S, D = 4, 16, 2048, 64
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(k2, (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(k3, (B, H, S, D), jnp.bfloat16)
    if not flash_attention_available(q, k, v, None):
        return None

    def run(fn):
        g = jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        sync = lambda o: float(o[0].astype(jnp.float32).ravel()[0])
        sync(g(q, k, v))
        t0 = time.perf_counter()
        for _ in range(10):
            out = g(q, k, v)
        sync(out)          # in-order device queue: last done => all done
        return (time.perf_counter() - t0) / 10

    t_flash = run(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t_naive = run(lambda q, k, v: _naive_attention(q, k, v, causal=True,
                                                   training=False))
    log(f"[flash] {B}x{H}x{S}x{D} fwd+bwd: flash {t_flash*1e3:.1f}ms "
        f"vs xla {t_naive*1e3:.1f}ms ({t_naive/t_flash:.2f}x)")
    return {"flash_ms": t_flash * 1e3, "xla_ms": t_naive * 1e3,
            "speedup": t_naive / t_flash, "shape": [B, H, S, D]}


def bench_resnet(batch=32, steps=5):
    """ResNet-50 imgs/sec: bf16 compute via op-level AMP (O1 autocast —
    white-listed convs/matmuls run bf16, norms/softmax and the fp32
    master params stay fp32), train-mode BN, SGD-momentum optimizer step
    included — BASELINE.md protocol item 3 (VERDICT r4 weak #3: fp32
    fwd+bwd w/o optimizer is not comparable to any published ResNet-50
    training number)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    model = resnet50(num_classes=1000)
    model.train()
    params0, buffers0 = model.raw_state()
    images = jnp.asarray(
        np.random.RandomState(0).rand(batch, 3, 224, 224).astype(np.float32))
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, 1000, (batch,)))

    def loss_and_buffers(params, buffers, images, labels):
        # framework AMP: white-listed convs/matmuls run bf16, norms stay
        # fp32 — the op-level autocast handles the dtype joins a blanket
        # param cast cannot (BN emits fp32 into bf16-weight convs)
        with model.swap_state(params, buffers), \
                paddle.amp.auto_cast(dtype="bfloat16"):
            logits = model(paddle.Tensor(images))
            loss = paddle.nn.functional.cross_entropy(
                logits.astype("float32"), paddle.Tensor(labels))
            # train-mode BN mutated the buffer Tensors in place; capture
            # the traced values before swap_state restores storage
            new_buffers = {k: v.data for k, v in model.named_buffers()
                           if v is not None}
        return (loss.data if hasattr(loss, "data") else loss), new_buffers

    mu, lr = 0.9, 0.1

    def train_step(params, vel, buffers, images, labels):
        (loss, new_buffers), grads = jax.value_and_grad(
            loss_and_buffers, has_aux=True)(params, buffers, images, labels)
        new_vel = {k: mu * vel[k] + grads[k].astype(jnp.float32)
                   for k in vel}
        new_params = {k: params[k] - lr * new_vel[k] for k in params}
        return new_params, new_vel, new_buffers, loss

    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    vel = {k: jnp.zeros_like(v) for k, v in params0.items()}
    params, buffers = params0, buffers0
    t0 = time.perf_counter()
    params, vel, buffers, loss = step(params, vel, buffers, images, labels)
    float(loss)
    log(f"[resnet] compile+first step {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(steps):
        params, vel, buffers, loss = step(params, vel, buffers, images,
                                          labels)
    float(loss)
    step_t = (time.perf_counter() - t0) / steps
    ips = batch / step_t
    log(f"[resnet] {ips:.1f} imgs/sec (bf16 fwd+bwd+momentum)")
    return {"imgs_per_sec": ips, "batch": batch,
            "protocol": {"model": "resnet50", "chips": 1,
                         "mesh": {"dp": 1}, "global_batch": batch,
                         "image_size": 224, "dtype": "bfloat16",
                         "norms_dtype": "float32",
                         "direction": "fwd+bwd+momentum step (train BN)",
                         "compiler": f"jax {jax.__version__}"}}


def _long_prompt_interference(cfg, params, *, chunk_len, long_len,
                              n_decode=3, n_late=2, max_new=8, seed=0):
    """One long prompt arriving into a saturated decode batch.

    Runs the unified-step engine at the given ``chunk_len`` and measures
    what the long prompt's prefill does to everyone else:

    - ``decode_stall_ms`` — the worst step wall time while the long
      prompt is mid-prefill.  Decode rows emit one token per step, so
      this IS the worst inter-token gap a decoding request saw.
    - ``ttft_late_*`` — TTFT of short requests submitted right behind
      the long prompt (they must share steps with its chunks).

    ``chunk_len == long_len`` emulates the old phase-split scheduler:
    the whole prompt runs as one mega-row, stalling the batch for the
    full prompt length — the head-of-line blocking chunked prefill
    removes."""
    from paddle_tpu.serving import Engine, SamplingParams

    rng = np.random.RandomState(seed)
    eng = Engine(cfg, params, page_size=16, num_pages=256,
                 max_batch_size=n_decode + n_late + 1, chunk_len=chunk_len)
    # compile the unified step before the clock starts
    eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))

    def prompt(n):
        return rng.randint(0, cfg.vocab_size, n).tolist()

    # saturate: n_decode requests decoding steadily
    deco = [eng.add_request(prompt(8), SamplingParams(
        max_new_tokens=long_len // max(1, chunk_len) * 4 + 32))
        for _ in range(n_decode)]
    for _ in range(3):
        eng.step()
    assert all(r.prompt_pos == len(r.prompt) for r in deco)

    long_r = eng.add_request(prompt(long_len),
                             SamplingParams(max_new_tokens=max_new))
    # the late shorts "arrive" now — while the long prompt's first
    # prefill step is about to be in flight.  They can only be submitted
    # at the next step boundary, so measuring their TTFT from t_arrive
    # charges them the in-flight step they had to wait out (the whole
    # prompt under phase-split, one bounded chunk under chunked prefill)
    t_arrive = time.perf_counter()
    late = []
    stall, prefill_steps = 0.0, 0
    while eng.has_work():
        pos_before = long_r.prompt_pos
        t0 = time.perf_counter()
        eng.step()
        dt = time.perf_counter() - t0
        if not late:
            late = [eng.add_request(prompt(8),
                                    SamplingParams(max_new_tokens=4))
                    for _ in range(n_late)]
        if long_r.prompt_pos > pos_before:   # this step ran prompt chunks
            prefill_steps += 1
            stall = max(stall, dt)
    ttft_late = [r.t_first_token - t_arrive for r in late
                 if r.t_first_token is not None]
    return {
        "chunk_len": chunk_len,
        "decode_stall_ms": stall * 1e3,
        "prefill_steps": prefill_steps,
        "ttft_long_ms": (long_r.t_first_token - long_r.t_submit) * 1e3,
        "ttft_late_p95_ms": float(np.percentile(ttft_late, 95)) * 1e3
        if ttft_late else None,
    }


def _shared_prefix_trace(cfg, params, *, warm, n_replicas=2, n_requests=16,
                         rate_per_s=40.0, sys_len=192, tail_len=8,
                         max_new=8, seed=0):
    """Shared-system-prompt Poisson trace through a small fleet — the
    millions-of-users chat shape: every request carries the same system
    prompt plus a short unique tail.  ``warm=True`` runs the radix
    prefix cache + cache-aware dispatch with each replica primed once
    by the system prompt (a steady-state fleet); ``warm=False`` is the
    PR 9 cold fleet — every replica re-prefills the shared prefix on
    every request.  Returns TTFT percentiles plus hit / prefill token
    accounting (the FLOPs-avoided evidence)."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.serving import Engine, FleetRouter, SamplingParams

    rng = np.random.RandomState(seed)
    system = rng.randint(0, cfg.vocab_size, sys_len).tolist()
    prompts = [system + rng.randint(0, cfg.vocab_size, tail_len).tolist()
               for _ in range(n_requests)]

    def factory():
        return Engine(cfg, params, page_size=16, num_pages=512,
                      max_batch_size=4, chunk_len=32, prefix_cache=warm)

    warm_sp = SamplingParams(max_new_tokens=2)
    router = FleetRouter(
        [factory] * n_replicas, cache_aware=warm, stall_timeout_s=5.0,
        registry=MetricsRegistry(),
        warmup=lambda eng: eng.generate([[1, 2, 3]], warm_sp))
    base = []
    for rep in router.replicas:
        rep.engine.generate([[1, 2, 3]], warm_sp)     # compile
        if warm:
            rep.engine.generate([system], warm_sp)    # prime the radix tree
        # priming/compile prefill is steady-state cost, not trace cost
        base.append(int(rep.engine.metrics.prefill_tokens.value))

    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, n_requests))
    sp = SamplingParams(max_new_tokens=max_new)
    reqs = []
    t0 = time.perf_counter()
    i = 0
    while i < n_requests or router.has_work():
        now = time.perf_counter() - t0
        while i < n_requests and arrivals[i] <= now:
            reqs.append(router.submit(prompts[i], sp))
            i += 1
        if not router.has_work():
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
            continue
        router.step()
    wall = time.perf_counter() - t0

    ttfts = [r.t_first_token - r.t_submit for r in reqs
             if r.t_first_token is not None]
    hits = hit_tokens = prefill = cached_pages = 0
    for rep, b in zip(router.replicas, base):
        stats = rep.engine.cache.prefix_stats()
        hits += stats["hits"]
        hit_tokens += stats["hit_tokens"]
        cached_pages += stats["cached_pages"]
        prefill += int(rep.engine.metrics.prefill_tokens.value) - b
    snap = router.metrics.snapshot()
    return {
        "requests": n_requests, "wall_s": wall,
        "finished": sum(1 for r in reqs if r.state == "finished"),
        "lost_requests": sum(1 for r in reqs if r.state != "finished"),
        "ttft_ms_p50": float(np.percentile(ttfts, 50)) * 1e3,
        "ttft_ms_p95": float(np.percentile(ttfts, 95)) * 1e3,
        "prefix_hits": hits, "prefix_hit_tokens": hit_tokens,
        "prefix_cached_pages": cached_pages,
        "prefill_tokens_computed": prefill,
        "cache_aware_dispatches": snap["cache_aware_dispatches"],
    }


def bench_serving(n_requests=24, rate_per_s=8.0, max_new=32, seed=0):
    """Serving scenario: the continuous-batching engine under a synthetic
    Poisson arrival trace (open-loop — arrival times don't wait on the
    engine, so queueing shows up in TTFT exactly as live traffic would).
    Reports generated tokens/sec, TTFT/queue-wait percentiles, page-pool
    occupancy, and the long-prompt-interference trace (chunked prefill
    vs an emulated phase-split baseline)."""
    import dataclasses

    import jax

    from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
    from paddle_tpu.serving import Engine, SamplingParams, ServingMetrics

    on_tpu = jax.devices()[0].platform not in ("cpu", "gpu", "cuda")
    name = "gpt2-small" if on_tpu else "tiny"
    cfg = dataclasses.replace(GPT_CONFIGS[name], dtype="bfloat16")
    params = gpt_init(cfg, jax.random.key(0))
    eng = Engine(cfg, params, page_size=16,
                 num_pages=2048 if on_tpu else 512, max_batch_size=8,
                 chunk_len=min(32, cfg.max_seq_len),
                 # production posture: shed at 95% pool / deep queue
                 # rather than letting TTFT collapse for everyone
                 shed_occupancy_high=0.95, shed_queue_high=4 * n_requests)
    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, n_requests))
    max_prompt = min(64, cfg.max_seq_len - max_new)
    prompts = [rng.randint(0, cfg.vocab_size,
                           rng.randint(8, max_prompt)).tolist()
               for _ in range(n_requests)]
    sp = SamplingParams(max_new_tokens=max_new)

    # compile prefill+decode before the clock starts (serving steady
    # state, not compile latency, is the metric)
    eng.generate([prompts[0][:8]], SamplingParams(max_new_tokens=2))
    eng.metrics = ServingMetrics()

    log(f"[serving] {name}: {n_requests} requests, Poisson "
        f"{rate_per_s}/s, max_new={max_new}")
    t0 = time.perf_counter()
    i = 0
    while i < n_requests or eng.has_work():
        now = time.perf_counter() - t0
        while i < n_requests and arrivals[i] <= now:
            eng.add_request(prompts[i], sp)
            i += 1
        if not eng.has_work():
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
            continue
        eng.step()
    wall = time.perf_counter() - t0

    def ms(v):                  # empty histogram stats are None
        return v * 1e3 if v is not None else None

    snap = eng.metrics.snapshot()
    out = {
        "model": name, "requests": n_requests, "wall_s": wall,
        "tokens_per_sec": snap["tokens"]["generated"] / wall,
        "ttft_ms_p50": ms(snap["ttft_s"]["p50"]),
        "ttft_ms_p95": ms(snap["ttft_s"]["p95"]),
        "queue_wait_ms_p50": ms(snap["queue_wait_s"]["p50"]),
        "decode_token_ms_p50": ms(snap["decode_token_s"]["p50"]),
        "page_occupancy_peak": snap["page_occupancy"]["peak"],
        "decode_rate_tok_s": eng.decode_rate(),
        "estimated_drain_s": eng.estimated_drain_s(),
        "preempted": snap["requests"]["preempted"],
        "finished": snap["requests"]["finished"],
        "shed": snap["requests"]["shed"],
        "deadline_evicted": snap["requests"]["deadline_evicted"],
        "engine_healthy": snap["engine_healthy"],
        "prefill_chunks": snap["tokens"]["prefill_chunks"],
    }
    log(f"[serving] {out['tokens_per_sec']:.1f} tok/s, TTFT p50 "
        f"{out['ttft_ms_p50'] or 0:.0f}ms p95 "
        f"{out['ttft_ms_p95'] or 0:.0f}ms, "
        f"pool peak {out['page_occupancy_peak']*100:.0f}%, "
        f"shed {out['shed']}, deadline-evicted {out['deadline_evicted']}, "
        f"{'healthy' if out['engine_healthy'] else 'degraded'}")

    # head-of-line blocking probe: one long prompt into a saturated
    # decode batch, chunked prefill vs the emulated phase-split baseline.
    # The probe engines deliberately use different static shapes, which
    # would read as recompiles of the main engine's program — keep their
    # compiles out of this section's watchdog telemetry.
    from paddle_tpu.observability.compile_watchdog import default_watchdog

    probe_max_new = 8
    long_len = min(2048, cfg.max_seq_len - 4 * probe_max_new)
    probe_chunk = max(16, min(32, long_len // 8))
    wd = default_watchdog()
    wd_prev, wd.enabled = wd.enabled, False
    try:
        chunked = _long_prompt_interference(
            cfg, params, chunk_len=probe_chunk, long_len=long_len,
            max_new=probe_max_new, seed=seed)
        split = _long_prompt_interference(
            cfg, params, chunk_len=long_len, long_len=long_len,
            max_new=probe_max_new, seed=seed)
    finally:
        wd.enabled = wd_prev
    out["long_prompt_interference"] = {
        "long_prompt_tokens": long_len,
        "chunked": chunked,
        "phase_split_emulated": split,
        "decode_stall_ratio": (split["decode_stall_ms"]
                               / max(chunked["decode_stall_ms"], 1e-9)),
    }
    log(f"[serving] long-prompt interference ({long_len} tok): decode "
        f"stall {chunked['decode_stall_ms']:.1f}ms chunked vs "
        f"{split['decode_stall_ms']:.1f}ms phase-split "
        f"({out['long_prompt_interference']['decode_stall_ratio']:.1f}x), "
        f"late TTFT p95 {chunked['ttft_late_p95_ms'] or 0:.0f}ms vs "
        f"{split['ttft_late_p95_ms'] or 0:.0f}ms")

    # shared-system-prompt trace: radix prefix cache + cache-aware
    # routing (warm) vs the PR 9 cold fleet.  Separate engines compile
    # their own unified steps — keep them out of watchdog telemetry.
    sys_len = min(192, cfg.max_seq_len - 64)
    wd_prev, wd.enabled = wd.enabled, False
    try:
        cold = _shared_prefix_trace(cfg, params, warm=False,
                                    sys_len=sys_len, seed=seed)
        warmed = _shared_prefix_trace(cfg, params, warm=True,
                                      sys_len=sys_len, seed=seed)
    finally:
        wd.enabled = wd_prev
    # one prefill token forward ≈ 2 FLOPs per parameter (matmul MACs)
    n_params = int(sum(int(np.prod(x.shape))
                       for x in jax.tree_util.tree_leaves(params)))
    flops_per_token = 2 * n_params
    avoided_tokens = warmed["prefix_hit_tokens"]
    out["shared_prefix"] = {
        "protocol": {"replicas": 2, "system_prompt_tokens": sys_len,
                     "tail_tokens": 8, "requests": 16,
                     "poisson_rate_per_s": 40.0, "max_new": 8,
                     "model": name},
        "cold_fleet": cold,
        "warm_fleet": warmed,
        "ttft_ms_p50_cold": cold["ttft_ms_p50"],
        "ttft_ms_p50_warm": warmed["ttft_ms_p50"],
        "ttft_speedup_p50": cold["ttft_ms_p50"]
        / max(warmed["ttft_ms_p50"], 1e-9),
        "prefill_tokens_avoided": avoided_tokens,
        "flops_per_prefill_token": flops_per_token,
        "prefill_flops_avoided": avoided_tokens * flops_per_token,
    }
    # the acceptance contract of the prefix cache: a warm fleet answers
    # strictly faster and demonstrably skipped prefill work
    assert warmed["ttft_ms_p50"] < cold["ttft_ms_p50"], \
        (f"warm TTFT p50 {warmed['ttft_ms_p50']:.1f}ms not below cold "
         f"{cold['ttft_ms_p50']:.1f}ms")
    assert out["shared_prefix"]["prefill_flops_avoided"] > 0
    assert cold["lost_requests"] == 0 and warmed["lost_requests"] == 0
    log(f"[serving] shared-prefix trace ({sys_len}-tok system prompt): "
        f"TTFT p50 {warmed['ttft_ms_p50']:.0f}ms warm vs "
        f"{cold['ttft_ms_p50']:.0f}ms cold "
        f"({out['shared_prefix']['ttft_speedup_p50']:.1f}x), "
        f"{warmed['prefix_hits']} hits, {avoided_tokens} prefill tokens "
        f"({avoided_tokens * flops_per_token / 1e9:.1f} GFLOPs) avoided")
    return out


def bench_fleet(n_requests=30, rate_per_s=12.0, max_new=16, n_replicas=3,
                seed=0):
    """Serving-fleet failover scenario: replay a recorded Poisson
    arrival trace through ``n_replicas`` in-process engines behind a
    FleetRouter, hard-kill one replica mid-trace (then relaunch it),
    and roll-restart another under a drain deadline — measuring what
    fleet-level robustness costs:

    - ``fleet_tokens_per_sec`` — goodput across the surviving fleet;
    - ``failover_added_ttft_p95_ms`` — TTFT p95 of requests that were
      re-dispatched off a dead/drained replica minus the p95 of
      untouched requests (the latency price of exactly-once recovery);
    - ``lost_requests`` — requests not FINISHED at trace end.  The
      zero-loss contract: this MUST be 0.

    A second sub-scenario (``poison_storm`` in the payload) drives the
    blast-radius containment machinery: 3 query-of-death requests into
    a fresh 3-replica fleet (cascade breaker K=2, autoscaler attached
    for zero-capacity recovery), asserting every poison ends terminal
    QUARANTINED, uncontrolled replica kills stay <= K+1, and every
    innocent finishes token-identical to a poison-free replay.
    """
    import dataclasses

    import jax

    from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.resilience import FaultSpec, injected_faults
    from paddle_tpu.serving import (Autoscaler, Engine, FleetRouter,
                                    SamplingParams)

    on_tpu = jax.devices()[0].platform not in ("cpu", "gpu", "cuda")
    name = "gpt2-small" if on_tpu else "tiny"
    cfg = dataclasses.replace(GPT_CONFIGS[name], dtype="bfloat16")
    params = gpt_init(cfg, jax.random.key(0))

    def factory():
        return Engine(cfg, params, page_size=16,
                      num_pages=1024 if on_tpu else 256,
                      max_batch_size=4, chunk_len=min(32, cfg.max_seq_len))

    # each replica engine compiles its own unified_step (separate jit
    # closures, as separate processes would); that is not a recompile
    # bug, so this section keeps the fleet out of watchdog telemetry
    from paddle_tpu.observability.compile_watchdog import default_watchdog

    wd = default_watchdog()
    wd_prev, wd.enabled = wd.enabled, False
    try:
        warm = SamplingParams(max_new_tokens=2)
        router = FleetRouter(
            [factory] * n_replicas, stall_timeout_s=5.0,
            drain_deadline_s=0.5,
            # a restarted replica re-enters rotation warm (compiled)
            warmup=lambda eng: eng.generate([[1, 2, 3]], warm))
        for rep in router.replicas:          # compile before the clock
            rep.engine.generate([[1, 2, 3]], warm)

        rng = np.random.RandomState(seed)
        arrivals = np.cumsum(rng.exponential(1.0 / rate_per_s, n_requests))
        max_prompt = min(48, cfg.max_seq_len - max_new)
        prompts = [rng.randint(0, cfg.vocab_size,
                               rng.randint(8, max_prompt)).tolist()
                   for _ in range(n_requests)]
        sp = SamplingParams(max_new_tokens=max_new)
        kill_at, relaunch_at, drain_at = (n_requests // 3,
                                          n_requests // 2,
                                          2 * n_requests // 3)
        log(f"[fleet] {name}: {n_replicas} replicas, {n_requests} "
            f"requests Poisson {rate_per_s}/s; kill replica 0 at "
            f"#{kill_at}, relaunch at #{relaunch_at}, rolling-restart "
            f"replica 1 at #{drain_at}")

        reqs, events = [], []
        t0 = time.perf_counter()
        i = 0
        while i < n_requests or router.has_work():
            now = time.perf_counter() - t0
            while i < n_requests and arrivals[i] <= now:
                reqs.append(router.submit(prompts[i], sp))
                i += 1
                if i == kill_at:
                    router.kill_replica(0)
                    events.append({"at_request": i, "event": "kill",
                                   "replica": 0})
                elif i == relaunch_at:
                    router.restart_replica(0)
                    events.append({"at_request": i, "event": "relaunch",
                                   "replica": 0})
                elif i == drain_at:
                    router.drain(1, deadline_s=0.5)
                    events.append({"at_request": i, "event": "drain",
                                   "replica": 1})
            if not router.has_work():
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.05))
                continue
            router.step()
        wall = time.perf_counter() - t0
    finally:
        wd.enabled = wd_prev

    lost = [r for r in reqs if r.state != "finished"]
    tokens = sum(len(r.tokens_out) for r in reqs)

    def p95_ms(ttfts):
        return (float(np.percentile(ttfts, 95)) * 1e3 if ttfts else None)

    clean = [r.t_first_token - r.t_submit for r in reqs
             if r.redispatches == 0 and r.t_first_token is not None]
    moved = [r.t_first_token - r.t_submit for r in reqs
             if r.redispatches > 0 and r.t_first_token is not None]
    snap = router.metrics.snapshot()
    out = {
        "model": name, "replicas": n_replicas, "requests": n_requests,
        "wall_s": wall,
        "fleet_tokens_per_sec": tokens / wall,
        "lost_requests": len(lost),
        "finished": sum(1 for r in reqs if r.state == "finished"),
        "redispatched_requests": sum(1 for r in reqs
                                     if r.redispatches > 0),
        "ttft_p95_ms_clean": p95_ms(clean),
        "ttft_p95_ms_failover": p95_ms(moved),
        "failover_added_ttft_p95_ms": (
            p95_ms(moved) - p95_ms(clean)
            if clean and moved else None),
        "events": events,
        "router": snap,
    }
    assert out["lost_requests"] == 0, \
        f"fleet lost {out['lost_requests']} requests: zero-loss contract"
    log(f"[fleet] {out['fleet_tokens_per_sec']:.1f} tok/s over "
        f"{n_replicas} replicas, {out['finished']}/{n_requests} "
        f"finished, lost {out['lost_requests']}, "
        f"{out['redispatched_requests']} redispatched; TTFT p95 "
        f"{out['ttft_p95_ms_clean'] or 0:.0f}ms clean vs "
        f"{out['ttft_p95_ms_failover'] or 0:.0f}ms failover")

    # ---- poison-storm containment sub-scenario --------------------------
    pattern = (7, 8, 9)
    n_innocent = max(8, n_requests // 3)
    innocent_prompts = [rng.randint(0, cfg.vocab_size,
                                    rng.randint(8, max_prompt)).tolist()
                        for _ in range(n_innocent)]
    storm_sp = SamplingParams(max_new_tokens=max_new)
    # the poison-free oracle: one clean engine, batch-composition-
    # independent greedy decode — what every innocent must emit
    refs = factory().generate(innocent_prompts, storm_sp)
    log(f"[fleet] poison storm: 3 poisons (pattern {list(pattern)}) "
        f"into a fresh {n_replicas}-replica fleet, K=2, "
        f"{n_innocent} innocents")
    wd_prev, wd.enabled = wd.enabled, False
    try:
        registry = MetricsRegistry()
        storm_router = FleetRouter(
            [factory] * n_replicas, registry=registry,
            stall_timeout_s=5.0, drain_deadline_s=0.5,
            canary_threshold=2, cascade_threshold=2,
            cascade_window_s=2.0,
            warmup=lambda eng: eng.generate([[1, 2, 3]], warm))
        scaler = Autoscaler(
            storm_router, factory, registry=registry,
            min_replicas=1, max_replicas=n_replicas,
            up_pressure_s=2.0, down_pressure_s=0.1,
            scale_up_cooldown_s=0.5, scale_down_cooldown_s=5.0,
            spawn_max_retries=2)
        for rep in storm_router.replicas:
            rep.engine.generate([[1, 2, 3]], warm)
        with injected_faults(FaultSpec("serving.step", "poison_request",
                                       pattern=pattern)):
            storm_reqs = [storm_router.submit(p, storm_sp)
                          for p in innocent_prompts[:n_innocent // 2]]
            poisons = [storm_router.submit(list(pattern) + [10],
                                           storm_sp) for _ in range(3)]
            storm_reqs += [storm_router.submit(p, storm_sp)
                           for p in innocent_prompts[n_innocent // 2:]]
            t1 = time.perf_counter()
            while storm_router.has_work():
                storm_router.step()
                scaler.tick()
                if time.perf_counter() - t1 > 120.0:
                    raise AssertionError(
                        "poison storm did not settle in 120s")
    finally:
        wd.enabled = wd_prev
    storm_snap = storm_router.metrics.snapshot()
    storm_out = {
        "poisons": len(poisons),
        "quarantined": [r.state == "quarantined" for r in poisons],
        "innocents": n_innocent,
        "innocents_finished": sum(1 for r in storm_reqs
                                  if r.state == "finished"),
        "innocents_token_identical": sum(
            1 for r, ref in zip(storm_reqs, refs) if r.output == ref),
        "uncontrolled_replica_kills": storm_snap["failure_events"],
        "canary_deaths": storm_snap["canary_deaths"],
        "cascade_breaker_opens": storm_snap["cascade_breaker_opens"],
        "lost_requests": int(storm_snap["lost"]),
    }
    out["poison_storm"] = storm_out
    assert all(storm_out["quarantined"]), \
        f"poisons not all quarantined: {[r.state for r in poisons]}"
    assert storm_out["uncontrolled_replica_kills"] <= 3, \
        f"blast radius exceeded K+1: {storm_out}"
    assert storm_out["innocents_finished"] == n_innocent, storm_out
    assert storm_out["innocents_token_identical"] == n_innocent, \
        "innocent output diverged from the poison-free replay"
    assert storm_out["lost_requests"] == 0, storm_out
    log(f"[fleet] poison storm contained: 3/3 quarantined, "
        f"{storm_out['uncontrolled_replica_kills']} uncontrolled kills "
        f"(+{storm_out['canary_deaths']} canary), "
        f"{storm_out['innocents_token_identical']}/{n_innocent} "
        f"innocents token-identical, lost 0")
    return out


def bench_soak(horizon_s=60.0, base_rate_per_s=None, seed=0):
    """Chaos soak — the long variant of the tier-1 compressed soak
    (tests/test_soak.py), both backed by ``serving.run_soak``: a seeded
    diurnal + bursty + shared-prefix trace replayed through an
    **autoscaled** fleet while the chaos timeline fires hard kills,
    admission stalls, control-loop stalls, and spawn io_errors.  The
    invariants are the soak's exit criteria, asserted here exactly as
    in CI:

    - ``lost_requests`` MUST be 0 (exactly-once failover held across
      every kill, stall, drain, and scale event);
    - TTFT p99 bounded;
    - at least one scale-up AND one scale-down recorded in ``/fleet``
      (scraped over live HTTP from the run's own telemetry server);
    - every chaos event visible as a ``soak::*`` flight record.
    """
    import dataclasses

    import jax

    from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
    from paddle_tpu.serving import (ChaosEvent, Engine, TrafficGenerator,
                                    run_soak)

    on_tpu = jax.devices()[0].platform not in ("cpu", "gpu", "cuda")
    name = "gpt2-small" if on_tpu else "tiny"
    cfg = dataclasses.replace(GPT_CONFIGS[name], dtype="bfloat16")
    params = gpt_init(cfg, jax.random.key(0))
    if base_rate_per_s is None:
        # the offered load must be inside the max-replicas fleet's
        # capacity or the TTFT bound measures saturation, not recovery
        # (CPU tiny goodput is ~8 req/s; bursts still 4x past it)
        base_rate_per_s = 8.0 if on_tpu else 3.0

    def factory():
        return Engine(cfg, params, page_size=16,
                      num_pages=1024 if on_tpu else 256,
                      max_batch_size=4,
                      chunk_len=min(32, cfg.max_seq_len),
                      shed_queue_high=8, shed_queue_low=2)

    # like bench_fleet: N engines jit N unified_step closures by
    # design, so keep the fleet out of recompile telemetry
    from paddle_tpu.observability.compile_watchdog import default_watchdog

    traffic = TrafficGenerator(
        base_rate_per_s=base_rate_per_s, diurnal_amplitude=0.8,
        day_period_s=horizon_s / 2.0,
        bursts=((horizon_s * 0.1, horizon_s * 0.15, 3.0),
                (horizon_s * 0.6, horizon_s * 0.1, 4.0)),
        n_cohorts=3, cohort_prefix_len=16, cohort_fraction=0.5,
        prompt_len=(8, 40), max_new_tokens=(8, 16),
        vocab_size=cfg.vocab_size, seed=seed)
    chaos = [
        ChaosEvent(t=horizon_s * 0.08, action="spawn_io_error"),
        ChaosEvent(t=horizon_s * 0.2, action="stall_admit", stall_s=0.4),
        ChaosEvent(t=horizon_s * 0.35, action="kill"),
        ChaosEvent(t=horizon_s * 0.5, action="stall_poll", stall_s=0.3),
        ChaosEvent(t=horizon_s * 0.65, action="kill"),
        ChaosEvent(t=horizon_s * 0.8, action="stall_admit", stall_s=0.4),
    ]
    log(f"[soak] {name}: {horizon_s:.0f}s horizon, base "
        f"{base_rate_per_s}/s diurnal+burst, {len(chaos)} chaos events")
    wd = default_watchdog()
    wd_prev, wd.enabled = wd.enabled, False
    try:
        report = run_soak(
            factory, traffic, horizon_s=horizon_s,
            initial_replicas=2, chaos=chaos,
            scaler_kw=dict(min_replicas=1, max_replicas=4,
                           up_pressure_s=1.0, down_pressure_s=0.15,
                           up_pending_depth=6,
                           scale_up_cooldown_s=horizon_s / 20.0,
                           scale_down_cooldown_s=horizon_s / 12.0,
                           spawn_max_retries=2),
            deadline_s=horizon_s * 4.0, grace_s=horizon_s / 4.0,
            ttft_bound_s=30.0)
    finally:
        wd.enabled = wd_prev

    events = report["scale_events"]
    assert report["lost_requests"] == 0, \
        f"soak lost {report['lost_requests']} requests: zero-loss contract"
    assert report["ttft_p99_ok"], \
        f"soak TTFT p99 {report['ttft_p99_s']:.1f}s over the bound"
    assert events.get("up", 0) >= 1 and events.get("down", 0) >= 1, \
        f"soak must scale both ways, got {events}"
    assert report["scraped"]["fleet"]["autoscaler"]["scale_events"], \
        "scale events missing from the scraped /fleet payload"
    out = {
        "model": name,
        "horizon_s": horizon_s,
        "wall_s": report["wall_s"],
        "timed_out": report["timed_out"],
        "requests": report["requests_submitted"],
        "finished": report["requests_finished"],
        "lost_requests": report["lost_requests"],
        "ttft_p50_s": report["ttft_p50_s"],
        "ttft_p99_s": report["ttft_p99_s"],
        "ttft_p99_ok": report.get("ttft_p99_ok"),
        "redispatched": report["redispatched"],
        "scale_events": events,
        "spawn_failures": report["spawn_failures"],
        "chaos": report["chaos"],
        "injector_fired": report["injector_fired"],
        "traffic": report["traffic"],
    }
    log(f"[soak] {out['finished']}/{out['requests']} finished, lost "
        f"{out['lost_requests']}, scale up×{events.get('up', 0)} "
        f"down×{events.get('down', 0)}, TTFT p99 "
        f"{(out['ttft_p99_s'] or 0) * 1e3:.0f}ms, "
        f"{len(out['chaos'])} chaos events fired")
    return out


def bench_ps(rows=100_000, dim=64, batch=4096):
    """Sparse parameter-server scale check: a 100k-row table pulled and
    pushed through the PSClient in loader-sized batches, reporting
    pull/push latency (VERDICT r4 weak #8: the PS was never exercised at
    its stated scale).  Pure host benchmark — no TPU."""
    from paddle_tpu.distributed.ps import PSClient, PSServer, SparseTable

    servers = [PSServer(), PSServer()]
    try:
        client = PSClient([s.endpoint for s in servers])
        table = SparseTable(client, "bench_emb", dim=dim, init_std=0.01,
                            seed=0)
        ids = np.arange(rows)
        pull_ts, push_ts = [], []
        t_all = time.perf_counter()
        for lo in range(0, rows, batch):
            chunk = ids[lo:lo + batch]
            t0 = time.perf_counter()
            vals = table.pull(chunk)
            pull_ts.append(time.perf_counter() - t0)
            grad = np.full((len(chunk), dim), 1e-3, np.float32)
            t0 = time.perf_counter()
            table.push(chunk, grad)
            push_ts.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t_all
        assert vals.shape == (len(chunk), dim)
        out = {
            "rows": rows, "dim": dim, "batch": batch,
            "rows_per_sec": rows / wall,
            "pull_ms_p50": float(np.median(pull_ts) * 1e3),
            "push_ms_p50": float(np.median(push_ts) * 1e3),
            "servers": len(servers),
        }
        log(f"[ps] {rows} rows dim={dim}: {out['rows_per_sec']:.0f} "
            f"rows/s, pull p50 {out['pull_ms_p50']:.1f}ms, "
            f"push p50 {out['push_ms_p50']:.1f}ms")
        return out
    finally:
        for s in servers:
            s.stop()


def bench_resilience(param_mb=64, steps=8, save_every=2):
    """Checkpoint-overlap measurement: how much save wall-clock async
    mode hides from the training thread.  A synthetic ~param_mb state
    tree is checkpointed every ``save_every`` of ``steps`` simulated
    train steps, once with blocking saves and once async — the
    training-thread cost (``checkpoint_save_seconds{mode=sync|async}``)
    against the overlapped write (``mode="background"``) is the goodput
    accountant's evidence that async checkpointing actually overlaps.
    Pure host benchmark — no TPU."""
    import shutil
    import tempfile

    from paddle_tpu.observability import default_registry
    from paddle_tpu.resilience import CheckpointManager

    rng = np.random.RandomState(0)
    n = int(param_mb * (1 << 20) / 8 / 4)
    tree = {f"layer{i}": rng.randn(n).astype(np.float32)
            for i in range(8)}
    out = {"param_mb": param_mb, "steps": steps, "save_every": save_every}
    for mode, async_save in (("sync", False), ("async", True)):
        root = tempfile.mkdtemp(prefix=f"bench_ckpt_{mode}_")
        mgr = CheckpointManager(root, keep_last_n=2,
                                async_save=async_save)
        blocked, wall0 = [], time.perf_counter()
        try:
            for s in range(1, steps + 1):
                time.sleep(0.01)            # the "train step"
                if s % save_every == 0:
                    t0 = time.perf_counter()
                    mgr.save(tree, step=s)
                    blocked.append(time.perf_counter() - t0)
            mgr.wait()
            out[mode] = {
                "train_thread_save_s_p50": float(np.median(blocked)),
                "train_thread_save_s_total": float(np.sum(blocked)),
                "wall_s": time.perf_counter() - wall0,
            }
        finally:
            shutil.rmtree(root, ignore_errors=True)
    h = default_registry().get("checkpoint_save_seconds")
    if h is not None:
        out["checkpoint_save_seconds"] = {
            lv[0] if lv else "": child.summary()
            for lv, child in h._series()}
    out["overlap_ratio"] = 1.0 - (
        out["async"]["train_thread_save_s_total"]
        / max(out["sync"]["train_thread_save_s_total"], 1e-9))
    log(f"[resilience] ckpt {param_mb}MB: sync blocks "
        f"{out['sync']['train_thread_save_s_total']:.3f}s, async "
        f"blocks {out['async']['train_thread_save_s_total']:.3f}s "
        f"({out['overlap_ratio']*100:.0f}% of save wall hidden)")
    return out


def bench_distributed(iters=4000, shape=(1024,), reps=5):
    """Flight-recorder overhead on the collective hot path: an eager
    ``all_reduce`` loop instrumented (the shipping path) vs bare (the
    decorator's ``__wrapped__``), medians over ``reps`` windows.  The
    recorder must be invisible at step granularity: the documented
    bound is <3% of step time for a 1.3B-class step (~1.5 s/step at
    BENCH_r05 throughput) issuing ~64 grad-sync collectives — a tier-1
    smoke test asserts ``implied_step_overhead_ratio`` stays under it.
    Pure host benchmark — no TPU."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import collective
    from paddle_tpu.observability import (FlightRecorder, MetricsRegistry,
                                          Tracer, use_flight_recorder)

    x = jnp.ones(shape, jnp.float32)
    bare = collective.all_reduce.__wrapped__
    # a private bounded recorder: the measurement pays realistic
    # ring/metric/span costs without flooding process-wide telemetry
    rec = FlightRecorder(capacity=512, registry=MetricsRegistry(),
                         tracer=Tracer(max_traces=64))

    def per_op(fn, n):
        t0 = time.perf_counter()
        for _ in range(n):
            fn(x)
        return (time.perf_counter() - t0) / n

    n = max(50, iters // reps)
    per_op(bare, n)                          # warmup both paths
    with use_flight_recorder(rec):
        per_op(collective.all_reduce, n)
        inst_s = float(np.median(
            [per_op(collective.all_reduce, n) for _ in range(reps)]))
    bare_s = float(np.median([per_op(bare, n) for _ in range(reps)]))
    overhead_s = max(0.0, inst_s - bare_s)

    COLLECTIVES_PER_STEP = 64   # generous: per-bucket grad sync, GPT-class
    STEP_SECONDS = 1.5          # 1.3B step wall at BENCH_r05 throughput
    ratio = overhead_s * COLLECTIVES_PER_STEP / STEP_SECONDS
    out = {
        "iters_per_window": n, "windows": reps,
        "per_op_bare_us": bare_s * 1e6,
        "per_op_instrumented_us": inst_s * 1e6,
        "per_op_overhead_us": overhead_s * 1e6,
        "collectives_per_step": COLLECTIVES_PER_STEP,
        "step_seconds_model": STEP_SECONDS,
        "implied_step_overhead_ratio": ratio,
        "bound_ratio": 0.03,
        "ring": rec.summary(),
    }
    log(f"[distributed] all_reduce {bare_s*1e6:.1f}us bare vs "
        f"{inst_s*1e6:.1f}us instrumented -> recorder overhead "
        f"{overhead_s*1e6:.1f}us/op, implied {ratio*100:.3f}% of a "
        f"{STEP_SECONDS}s step ({COLLECTIVES_PER_STEP} collectives) "
        f"[bound 3%]")
    return out


def bench_tracing(iters=3000, reps=5):
    """Distributed-tracing overhead on the request hot path: one full
    request-shaped trace lifecycle (root + queued/dispatch/decode-class
    child spans with attributes, all ended) per iteration, under the
    three shipping tracer postures — **full** (tail retention at
    ``sample_rate=1.0``), **sampled** (boring traces kept at 1%;
    shed/evicted/failover/slow still always retained), **disabled**
    (``Tracer(enabled=False)`` — the shared null span).  Medians over
    ``reps`` windows, pure host benchmark — no TPU.

    The documented bound is <1% of a 50 ms TTFT-class request (the
    tiny-model service time ``--section serving`` measures) with full
    tracing on — a tier-1 smoke test asserts
    ``implied_request_overhead_ratio`` stays under ``bound_ratio``."""
    from paddle_tpu.observability.tracing import TailRetention, Tracer

    SPANS_PER_REQUEST = 4       # root + queued + dispatch + decode
    REQUEST_SECONDS = 0.05      # 50 ms TTFT-class request (tiny model)

    def lifecycle(tracer, now):
        root = tracer.start_trace("request#bench", start_s=now,
                                  attributes={"prompt_len": 32})
        for name in ("queued", "router::dispatch", "decode"):
            sp = tracer.start_span(name, root, start_s=now)
            sp.set_attribute("outcome", "ok")
            sp.end(now + 0.001)
        root.end(now + 0.002)

    def per_request(tracer, n):
        t0 = time.perf_counter()
        for i in range(n):
            lifecycle(tracer, float(i))
        return (time.perf_counter() - t0) / n

    n = max(100, iters // reps)
    modes = {
        "full": Tracer(clock=time.perf_counter, max_traces=256),
        "sampled": Tracer(clock=time.perf_counter, max_traces=256,
                          retention=TailRetention(sample_rate=0.01)),
        "disabled": Tracer(clock=time.perf_counter, enabled=False),
    }
    per_req = {}
    for mode, tracer in modes.items():
        per_request(tracer, n)               # warmup
        per_req[mode] = float(np.median(
            [per_request(tracer, n) for _ in range(reps)]))
    ratio = per_req["full"] / REQUEST_SECONDS
    out = {
        "iters_per_window": n, "windows": reps,
        "per_request_full_us": per_req["full"] * 1e6,
        "per_request_sampled_us": per_req["sampled"] * 1e6,
        "per_request_disabled_us": per_req["disabled"] * 1e6,
        "spans_per_request": SPANS_PER_REQUEST,
        "request_seconds_model": REQUEST_SECONDS,
        "implied_request_overhead_ratio": ratio,
        "bound_ratio": 0.01,
        # retention proof: sampled mode actually dropped boring traces
        "ring_full": modes["full"].summary(),
        "ring_sampled": modes["sampled"].summary(),
    }
    log(f"[tracing] per-request {per_req['full']*1e6:.1f}us full / "
        f"{per_req['sampled']*1e6:.1f}us sampled / "
        f"{per_req['disabled']*1e6:.1f}us disabled "
        f"({SPANS_PER_REQUEST} spans), implied {ratio*100:.3f}% of a "
        f"{REQUEST_SECONDS*1e3:.0f}ms request [bound 1%]")
    return out


def bench_slo(iters=400, reps=5):
    """SLO-engine overhead on the control path: one full
    scrape+evaluate cycle — the TimeSeriesStore walking a realistic
    serving-sized metric population (the real ServingMetrics /
    RouterMetrics / AutoscalerMetrics facades, three replicas' label
    children, live TTFT histograms) and the SLOEngine re-computing
    burn rates, budgets and alert state for the standing objective set
    (availability + goodput + TTFT latency, each with the page+ticket
    alert pair).  Each cycle is timed individually and a window
    reports its fastest cycle (timeit discipline: the minimum is the
    intrinsic cost — slower cycles measure scheduler preemption by
    unrelated threads, not the engine); the result is the median of
    ``reps`` window minima.  Pure host benchmark — no TPU.

    The documented bound matches the tracing/flight-recorder
    precedent: one cycle costs <1% of a 50 ms TTFT-class request even
    if a cycle ran per request (in production it runs per poll
    interval, amortized over many requests) — a tier-1 smoke test
    asserts ``implied_request_overhead_ratio`` stays under
    ``bound_ratio``."""
    from paddle_tpu.observability.metrics import MetricsRegistry
    from paddle_tpu.observability.slo import (BurnRateAlert, SLO,
                                              SLOEngine)
    from paddle_tpu.observability.timeseries import TimeSeriesStore
    from paddle_tpu.serving.metrics import (AutoscalerMetrics,
                                            RouterMetrics,
                                            ServingMetrics)

    REQUEST_SECONDS = 0.05      # 50 ms TTFT-class request (tiny model)
    reg = MetricsRegistry()
    serving = ServingMetrics(registry=reg)
    router = RouterMetrics(registry=reg)
    AutoscalerMetrics(registry=reg)
    rng = np.random.default_rng(7)

    def traffic_beat(i):
        # the serving-shaped population a real fleet scrape sees:
        # per-replica label children plus live histograms
        for rep in range(3):
            router.dispatches.labels(replica=rep).inc()
            if i % 7 == rep:
                router.backpressure_retries.labels(replica=rep).inc()
        router.finished.inc(3)
        serving.requests_submitted.inc(3)
        ttft = float(0.02 + 0.08 * rng.random())
        serving.ttft.observe(ttft)
        router.ttft.observe(ttft)

    alerts = (BurnRateAlert("page", burn_rate_threshold=14.4,
                            long_window_seconds=2.0,
                            short_window_seconds=0.5),
              BurnRateAlert("ticket", burn_rate_threshold=3.0,
                            long_window_seconds=8.0,
                            short_window_seconds=1.0))
    slos = (
        SLO("availability", target=0.999,
            bad=("serving_requests_shed_total",
                 "router_requests_lost_total"),
            total=("serving_requests_submitted_total",),
            alerts=alerts, budget_window_seconds=30.0),
        SLO("goodput", target=0.95,
            good=("router_requests_finished_total",),
            total=("router_dispatches_total",),
            alerts=alerts, budget_window_seconds=30.0),
        SLO("ttft_fast", target=0.99,
            histogram="serving_ttft_seconds", threshold_seconds=0.2,
            alerts=alerts, budget_window_seconds=30.0),
    )
    store = TimeSeriesStore(reg, max_points=256)
    engine = SLOEngine(store, slos, registry=reg)

    def cycle(n):
        best = float("inf")
        for i in range(n):
            t0 = time.perf_counter()
            store.scrape_once()
            engine.evaluate()
            dt = time.perf_counter() - t0
            if dt < best:
                best = dt
        return best

    n = max(50, iters // reps)
    for i in range(200):            # warm population + ring
        traffic_beat(i)
    cycle(n)                        # warmup
    windows = []
    for w in range(reps):
        for i in range(20):
            traffic_beat(w * 20 + i)
        windows.append(cycle(n))
    per_cycle = float(np.median(windows))
    ratio = per_cycle / REQUEST_SECONDS
    out = {
        "iters_per_window": n, "windows": reps,
        "per_cycle_us": per_cycle * 1e6,
        "series": store.stats()["series"],
        "points": store.stats()["points"],
        "slos": len(slos),
        "request_seconds_model": REQUEST_SECONDS,
        "implied_request_overhead_ratio": ratio,
        "bound_ratio": 0.01,
        "page_active": engine.page_active(),
    }
    log(f"[slo] scrape+evaluate {per_cycle*1e6:.1f}us over "
        f"{out['series']} series / {len(slos)} slos, implied "
        f"{ratio*100:.3f}% of a {REQUEST_SECONDS*1e3:.0f}ms request "
        f"[bound 1%]")
    return out


def bench_profiling(iters=300, reps=5, workers=4, depth=24):
    """Continuous-profiler overhead: the cost of ONE stack-sampler walk
    over a realistic thread population — ``workers`` threads parked
    ``depth`` frames deep (the recursion gives the collapser real
    stacks to intern) plus the process's own threads.  Each window
    reports its fastest walk (timeit discipline: the minimum is the
    intrinsic cost; slower walks measure preemption) and the result is
    the median of ``reps`` window minima.  Pure host benchmark.

    The documented bound: at the always-on default rate (one walk per
    ``interval_seconds=0.1``) the sampler steals
    ``per_sample/interval`` of wall time — the
    ``implied_request_overhead_ratio`` a 50 ms request pays, and a
    tier-1 smoke asserts it stays under ``bound_ratio`` (1%).  The
    escalated/capture rows show the same cost at anomaly-capture
    rates: escalation is bounded by the capture window, so those may
    exceed 1% *briefly* by design and are reported, not gated."""
    import threading

    from paddle_tpu.observability.profiling import StackSampler

    REQUEST_SECONDS = 0.05      # 50 ms TTFT-class request (tiny model)
    RATES = {"default": 0.1, "escalated": 0.02, "capture": 0.01}

    stop = threading.Event()
    parked = []

    def park(d):
        if d:
            return park(d - 1)
        parked.append(None)
        stop.wait()

    threads = [threading.Thread(target=park, args=(depth,), daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    while len(parked) < workers:     # wait until every stack is deep
        time.sleep(0.001)

    sampler = StackSampler()
    try:

        def window(n):
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                sampler.sample_once()
                dt = time.perf_counter() - t0
                if dt < best:
                    best = dt
            return best

        n = max(50, iters // reps)
        window(n)                    # warmup: intern the stack table
        per_sample = float(np.median([window(n) for _ in range(reps)]))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=2.0)
    stats = sampler.stats()
    rates = {label: {
        "interval_seconds": interval,
        "samples_per_request": REQUEST_SECONDS / interval,
        "overhead_ratio": per_sample / interval,
    } for label, interval in RATES.items()}
    ratio = rates["default"]["overhead_ratio"]
    out = {
        "iters_per_window": n, "windows": reps,
        "workers": workers, "stack_depth": depth,
        "per_sample_us": per_sample * 1e6,
        "stacks_interned": stats["stacks_interned"],
        "request_seconds_model": REQUEST_SECONDS,
        "rates": rates,
        "implied_request_overhead_ratio": ratio,
        "bound_ratio": 0.01,
    }
    log(f"[profiling] stack walk {per_sample*1e6:.1f}us over "
        f"{workers} parked threads ({stats['stacks_interned']} stacks),"
        f" always-on {ratio*100:.4f}% of wall time [bound 1%], "
        f"capture {rates['capture']['overhead_ratio']*100:.3f}%")
    return out


def bench_integrity(steps=20, fp_reps=9, replay_reps=5, hidden=1024,
                    batch=128, fingerprint_every=25, replay_every=100):
    """Silent-corruption sentinel overhead: the per-call cost of a
    parameter-tree fingerprint and a sampled step replay, amortized
    over their sampling intervals (defaults N=25 / M=100) as a
    fraction of the measured train-step wall — the documented bound is
    a combined <3% of step time at this config.  An end-to-end ``fit``
    with the callback enabled rides along as a sanity check that the
    amortized model reflects the real loop.  Pure host benchmark — no
    TPU."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.core.random import get_rng_state
    from paddle_tpu.io import Dataset
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.resilience.integrity import (IntegrityCallback,
                                                 tree_fingerprint)

    paddle.seed(0)
    model = paddle.Model(nn.Sequential(
        nn.Linear(hidden, hidden), nn.ReLU(),
        nn.Linear(hidden, hidden), nn.ReLU(), nn.Linear(hidden, 10)))
    opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                    parameters=model.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    x = rng.randn(batch, hidden).astype(np.float32)
    y = rng.randint(0, 10, (batch,)).astype(np.int64)

    model.train_batch(x, y)                  # compile outside the clock
    t = []
    for _ in range(steps):
        t0 = time.perf_counter()
        model.train_batch(x, y)
        t.append(time.perf_counter() - t0)
    step_s = float(np.median(t))

    params, buffers = model.network.raw_state()
    n_params = sum(int(np.asarray(v).size) for v in params.values())
    tree = {"params": dict(params)}
    tree_fingerprint(tree)                   # warm the digest path
    fp_s = float(np.median([_timed(tree_fingerprint, tree)
                            for _ in range(fp_reps)]))
    snapshot = {"params": dict(params), "buffers": dict(buffers),
                "opt_state": model._opt_state,
                "rng": dict(get_rng_state()), "lr": float(opt.get_lr())}
    model.replay_train_batch(snapshot, (x, y))
    replay_s = float(np.median(
        [_timed(model.replay_train_batch, snapshot, (x, y))
         for _ in range(replay_reps)]))
    ratio = (fp_s / fingerprint_every + replay_s / replay_every) / step_s

    # the loop-level evidence: same model trained with the sentinel
    # sampling every step vs every N/M steps — wall ratio is noisy on
    # CPU, reported as corroboration, not bounded
    class _Flat(Dataset):
        def __len__(self):
            return batch * 8

        def __getitem__(self, i):
            return x[i % batch], y[i % batch]

    def fit_wall(cb):
        t0 = time.perf_counter()
        model.fit(_Flat(), batch_size=batch, epochs=1, shuffle=False,
                  verbose=0, callbacks=cb)
        return time.perf_counter() - t0

    fit_wall([])                             # warm the fit loop
    bare = fit_wall([])
    guarded = fit_wall([IntegrityCallback(
        fingerprint_every=2, replay_every=4,
        registry=MetricsRegistry())])
    out = {
        "params": n_params,
        "params_mb": n_params * 4 / (1 << 20),
        "step_seconds_p50": step_s,
        "fingerprint_seconds_p50": fp_s,
        "replay_seconds_p50": replay_s,
        "fingerprint_every": fingerprint_every,
        "replay_every": replay_every,
        "amortized_overhead_ratio": ratio,
        "bound_ratio": 0.03,
        "fit_probe": {"bare_s": bare, "guarded_s": guarded,
                      "fingerprint_every": 2, "replay_every": 4,
                      "overhead_ratio": max(0.0, guarded / bare - 1.0)},
    }
    log(f"[integrity] step {step_s*1e3:.1f}ms, fingerprint "
        f"{fp_s*1e3:.2f}ms/{fingerprint_every} steps + replay "
        f"{replay_s*1e3:.1f}ms/{replay_every} steps -> "
        f"{ratio*100:.2f}% of step time [bound 3%] "
        f"({n_params/1e6:.1f}M params)")
    return out


def bench_lint(reps=3):
    """Static-analysis suite cost: wall time of the unified
    ``python -m tools.analysis`` run (all passes over one shared
    parsed-module cache), so lint cost shows up in the perf trajectory
    alongside everything else.  Each rep builds a FRESH Project — the
    one-pass parse cache is part of what is being measured.  The tier-1
    budget this must stay under is 10s."""
    from tools.analysis.core import Project, run_all
    from tools.analysis.passes import (collective_discipline,
                                       sharding_spec)

    walls, report = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        report = run_all(Project())
        walls.append(time.perf_counter() - t0)
    wall_s = float(np.median(walls))
    # coverage proof for the two SPMD passes: how much of the repo's
    # collective plane / axis universe they actually see (an empty
    # reach would make the clean run vacuous)
    proj = Project()
    sites = collective_discipline.collective_sites(proj)
    out = {
        "passes": len(report["passes"]),
        "files_scanned": report["files_scanned"],
        "new_findings": len(report["new"]),
        "baselined_findings": len(report["baselined"]),
        "wall_seconds_p50": wall_s,
        "budget_seconds": 10.0,
        "per_pass_seconds": {rule: stats["seconds"]
                             for rule, stats in report["passes"].items()},
        "collective_sites": len(sites),
        "collective_site_files": len({s[0] for s in sites}),
        "declared_mesh_axes": sharding_spec.declared_axes(proj),
    }
    log(f"[lint] {out['passes']} passes over {out['files_scanned']} "
        f"files in {wall_s:.2f}s (budget 10s), "
        f"{out['new_findings']} new / {out['baselined_findings']} "
        f"baselined findings")
    return out


def _timed(fn, *args):
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


# ------------------------------------------------------------- multichip


def _force_host_devices(n=8):
    """Mirror __graft_entry__.dryrun_multichip's env dance: force the
    CPU platform with ``n`` virtual host devices BEFORE jax's backend
    initializes, so the multichip section is self-sufficient in any
    subprocess.  Real multi-chip hardware (>= n accelerator devices)
    is used as-is."""
    if os.environ.get("PADDLE_TPU_MULTICHIP_REAL"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={n}"
    pat = r"--xla_force_host_platform_device_count=\d+"
    flags = re.sub(pat, want, flags) if re.search(pat, flags) \
        else (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass


def bench_multichip(steps=8, warmup=2, batch=16, seq=64):
    """REAL GSPMD execution over ``distributed.mesh`` — replaces the
    dry-run loss checks the MULTICHIP_r01..r05 artifacts recorded.

    Per hybrid-parallel config (pure-dp, dp x mp, dp x mp x sharding):
    one jitted train step with in/out shardings from the mesh.py rule
    table runs ``steps`` measured iterations on 8 devices, recording
    tokens/s/device — and the section FAILS (placement_ok=False) unless
    ``addressable_shards`` confirms the intended layout for params,
    ZeRO optimizer slots, and the serving engine's mp-sharded KV page
    pool.  Placement is asserted on live arrays BETWEEN steps, so a
    silent GSPMD fallback to replication cannot masquerade as a win."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from paddle_tpu.optimizer.optimizers import Adam

    n = len(jax.devices())
    if n < 8:
        return {"skipped": True,
                "reason": f"need 8 devices, have {n}"}
    cfg = GPTConfig(vocab_size=1024, max_seq_len=128, hidden=128,
                    num_layers=4, num_heads=8, ffn_hidden=512,
                    dtype="float32", use_flash=False, remat="nothing")
    opt = Adam(learning_rate=1e-3)
    rng = np.random.RandomState(0)
    tok_h = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    lab_h = np.concatenate([tok_h[:, 1:], np.full((batch, 1), -100)],
                           axis=1).astype(np.int32)

    configs = {
        "pure_dp": dict(dp=8),
        "dp_mp": dict(dp=2, mp=4),
        "dp_mp_sharding": dict(dp=2, mp=2, sharding=2),
    }
    out = {"n_devices": n, "protocol": {"steps": steps, "warmup": warmup,
                                        "global_batch": batch,
                                        "seq_len": seq,
                                        "config": "gpt-bench-tiny"},
           "configs": {}}
    placement_ok = True
    for name, axes in configs.items():
        mesh = mesh_mod.build_mesh(**axes)
        params = mesh_mod.shard_params(gpt_init(cfg), mesh)
        pspecs = mesh_mod.param_specs(params, mesh)
        opt_state = opt.init_state(params)
        ospecs = {"step": P(),
                  "slots": mesh_mod.zero_opt_specs(
                      pspecs, opt_state["slots"], mesh)}
        opt_state = mesh_mod.shard_tree(opt_state, mesh, ospecs)
        ns = lambda s: NamedSharding(mesh, s)
        as_sh = lambda t: jax.tree_util.tree_map(
            ns, t, is_leaf=lambda x: isinstance(x, P))
        p_sh, o_sh = as_sh(pspecs), as_sh(ospecs)
        batch_sh, rep = ns(P("dp")), ns(P())

        def train_step(params, opt_state, tok, lab):
            loss, grads = jax.value_and_grad(
                lambda p: gpt_loss(cfg, p, tok, lab))(params)
            params, opt_state = opt.apply_gradients(
                params, grads, opt_state, 1e-3)
            return params, opt_state, loss

        step_fn = jax.jit(train_step,
                          in_shardings=(p_sh, o_sh, batch_sh, batch_sh),
                          out_shardings=(p_sh, o_sh, rep))
        tok, lab = mesh_mod.shard_batch(mesh, tok_h, lab_h)
        losses = []
        for _ in range(warmup):
            params, opt_state, loss = step_fn(params, opt_state, tok,
                                              lab)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step_fn(params, opt_state, tok,
                                              lab)
            losses.append(float(loss))
        jax.block_until_ready(loss)
        wall = time.perf_counter() - t0
        devs = int(mesh.devices.size)
        entry = {
            "mesh": {a: v for a, v in axes.items()},
            "devices": devs,
            "tokens_per_sec": round(batch * seq * steps / wall, 1),
            "tokens_per_sec_per_device": round(
                batch * seq * steps / wall / devs, 1),
            "step_seconds_p50": round(wall / steps, 5),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
        }
        # the non-dry-run proof: what the devices actually hold
        try:
            mesh_mod.assert_placement(
                params["blocks"]["qkv_w"], mesh, P(None, None, "mp"),
                f"{name}: qkv_w")
            mesh_mod.assert_placement(
                params["wte"], mesh, P("mp", None), f"{name}: wte")
            m1 = opt_state["slots"]["blocks"]["qkv_w"]["moment1"]
            want = (P(None, "sharding", "mp")
                    if axes.get("sharding", 1) > 1
                    else P(None, None, "mp"))
            mesh_mod.assert_placement(m1, mesh, want,
                                      f"{name}: adam moment1")
            entry["placement"] = {
                **mesh_mod.placement_report(
                    {"qkv_w": params["blocks"]["qkv_w"],
                     "wte": params["wte"], "adam_moment1": m1}),
            }
            entry["placement_ok"] = True
        except AssertionError as e:
            placement_ok = False
            entry["placement_ok"] = False
            entry["placement_error"] = str(e)
        out["configs"][name] = entry
        log(f"[multichip] {name}: "
            f"{entry['tokens_per_sec_per_device']} tok/s/dev over "
            f"{devs} devices, loss {entry['loss_first']} -> "
            f"{entry['loss_last']}, placement_ok="
            f"{entry['placement_ok']}")

    # serving: KV page pool mp-sharded, greedy parity vs unsharded
    from paddle_tpu.serving.engine import Engine, SamplingParams

    scfg = GPTConfig(vocab_size=512, max_seq_len=128, hidden=64,
                     num_layers=2, num_heads=4, ffn_hidden=256,
                     dtype="float32", use_flash=False, remat="nothing")
    sparams = gpt_init(scfg)
    prompts = [list(np.random.RandomState(i).randint(1, 500, 8))
               for i in range(4)]
    sp = SamplingParams(max_new_tokens=8)
    ref = Engine(scfg, sparams, page_size=8, num_pages=64,
                 max_batch_size=4, chunk_len=16).generate(prompts, sp)
    smesh = mesh_mod.build_mesh(mp=4)
    eng = Engine(scfg, sparams, page_size=8, num_pages=64,
                 max_batch_size=4, chunk_len=16, mesh=smesh)
    t0 = time.perf_counter()
    got = eng.generate(prompts, sp)
    decode_wall = time.perf_counter() - t0
    try:
        mesh_mod.assert_placement(eng.cache.k_pages, smesh,
                                  P(None, None, None, "mp"), "k_pages")
        pages_ok = True
    except AssertionError as e:
        pages_ok, placement_ok = False, False
        out["kv_pages_placement_error"] = str(e)
    out["serving_mp"] = {
        "mesh": {"mp": 4},
        "token_identical_to_unsharded": got == ref,
        "decode_wall_s": round(decode_wall, 4),
        "kv_pages_placement_ok": pages_ok,
        "kv_pages": mesh_mod.placement_report(
            {"k_pages": eng.cache.k_pages}),
    }
    out["placement_ok"] = placement_ok
    out["ok"] = placement_ok and \
        out["serving_mp"]["token_identical_to_unsharded"] and \
        all(np.isfinite(c["loss_last"])
            for c in out["configs"].values())
    return out


# ----------------------------------------------------- section telemetry


def _section_telemetry(out):
    """Attach the global observability snapshot to one section's JSON:
    ``metrics`` is the default MetricsRegistry (serving counters, jit
    compile counters, ...), ``jit`` the compile watchdog's per-function
    report (compiles/recompiles/compile wall-time/cost analysis),
    ``traces`` the flight recorder's digest (per-root-name counts and
    durations — serving request / hapi step spans), and ``resources``
    one ResourceSampler reading (RSS / fds / GC / live jax bytes at
    section end).  The watchdog is enabled at section start by
    _enable_watchdog."""
    if not isinstance(out, dict):
        return out
    from paddle_tpu.observability import (ResourceSampler,
                                          default_registry,
                                          default_tracer,
                                          default_watchdog)

    out["resources"] = ResourceSampler().sample_once()
    out["metrics"] = default_registry().snapshot()
    report = default_watchdog().report()
    if report:
        out["jit"] = report
    trace_digest = default_tracer().summary()
    if trace_digest["completed"]:
        out["traces"] = trace_digest
    from paddle_tpu.observability.goodput import last_report

    goodput = last_report()
    if goodput:
        out["goodput"] = goodput
    return out


def _enable_watchdog():
    """Every bench section runs with the compile watchdog on: any
    recompile during a steady-state window is a perf bug, and the
    WARNING lands in the section's stderr next to the measurements."""
    from paddle_tpu.observability import enable_compile_watchdog

    enable_compile_watchdog()


# -------------------------------------------------- subprocess plumbing


def _oom_summary(text):
    """Extract XLA's HBM OOM breakdown from subprocess output, if any."""
    m = re.search(r"Ran out of memory in memory space hbm\..*?hbm", text)
    if not m:
        return None
    out = {"oom": m.group(0)[:300]}
    mb = re.search(
        r"Total hbm usage[^\n]*\n(?:[^\n]*\n){0,4}", text)
    if mb:
        out["breakdown"] = " | ".join(
            line.strip() for line in mb.group(0).splitlines() if line.strip())
    return out


def _last_json(text):
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except Exception:
                continue
    return None


def _run_section(args_list, timeout_s, tag):
    """Run `python bench.py <args_list>` in a subprocess; return its JSON
    or an error dict with the OOM breakdown when XLA ran out of HBM."""
    log(f"[{tag}] subprocess: {' '.join(args_list)}")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + args_list,
            capture_output=True, text=True, timeout=timeout_s, cwd=HERE)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s}s"}
    data = _last_json(proc.stdout)
    if proc.returncode == 0 and data is not None:
        return data
    err = {"error": f"rc={proc.returncode}: {proc.stderr[-300:]}"}
    oom = _oom_summary(proc.stderr + proc.stdout)
    if oom:
        err["hbm"] = oom
        err["error"] = f"rc={proc.returncode}: HBM OOM (see hbm)"
    return err


# ---------------------------------------------------- regression gating


def _current_round():
    """The round now being benched: VERDICT.md says the PREVIOUS round
    (judge output), so current = that + 1.  Fallback: one past the
    highest BENCH_r*.json on disk."""
    try:
        with open(os.path.join(HERE, "VERDICT.md")) as f:
            m = re.search(r"Round (\d+)", f.read(2000))
        if m:
            return int(m.group(1)) + 1
    except Exception:
        pass
    rounds = []
    for p in glob.glob(os.path.join(HERE, "BENCH_r*.json")):
        m = re.match(r"BENCH_r(\d+)\.json", os.path.basename(p))
        if m:
            rounds.append(int(m.group(1)))
    return (max(rounds) + 1) if rounds else 1


def prior_best():
    """Best tokens/s per (config, batch, seq) across PRIOR rounds'
    BENCH_r*.json — the regression baseline (reference:
    tools/check_op_benchmark_result.py gates op benches against logged
    history the same way).  The current round's own file is excluded so a
    same-round rerun never gates against its own noise (ADVICE r4)."""
    cur = _current_round()
    best = {}
    for path in sorted(glob.glob(os.path.join(HERE, "BENCH_r*.json"))):
        m = re.match(r"BENCH_r(\d+)\.json", os.path.basename(path))
        if m and int(m.group(1)) >= cur:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except Exception:
            continue
        parsed = data.get("parsed") or data
        extra = (parsed or {}).get("extra") or {}
        for entry in extra.values():
            if isinstance(entry, dict) and "tokens_per_sec_per_chip" in entry:
                cfgname = entry.get("config")
                proto = entry.get("protocol") or {}
                # legacy rounds (no protocol block) ran the defaults
                key = (cfgname, proto.get("global_batch", 32),
                       proto.get("seq_len", 1024))
                tok = float(entry["tokens_per_sec_per_chip"])
                if cfgname and tok > best.get(key, 0.0):
                    best[key] = tok
    return best


# -------------------------------------------------------- orchestration


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--accum", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--no-resnet", action="store_true")
    ap.add_argument("--no-13b", action="store_true",
                    help="skip the gpt3-1.3b headline ladder")
    ap.add_argument("--no-flash-micro", action="store_true")
    ap.add_argument("--no-ps", action="store_true")
    ap.add_argument("--no-serving", action="store_true")
    ap.add_argument("--section",
                    choices=["gpt", "rung", "flash", "resnet", "ps",
                             "serving", "fleet", "soak", "resilience",
                             "distributed", "tracing", "integrity",
                             "lint", "multichip", "slo", "profiling"],
                    help="internal: run ONE section in-process, print "
                         "its JSON")
    ap.add_argument("--rung", type=int, default=0,
                    help="internal: LADDER_13B index for --section rung")
    ap.add_argument("--gpt-config", default="gpt2-medium",
                    help="internal: config for --section gpt")
    args = ap.parse_args()

    # ---- section mode: one measurement, one JSON line ----
    if args.section == "multichip":
        # env dance BEFORE any jax import can initialize the backend
        _force_host_devices(8)
    if args.section:
        _enable_watchdog()
    if args.section == "multichip":
        print(json.dumps(_section_telemetry(bench_multichip(
            steps=args.steps, warmup=args.warmup))))
        return
    if args.section == "gpt":
        # no in-process fallback: a failed attempt can poison the process
        # (r4 cascade) — the orchestrator retries gpt2-small in a FRESH
        # subprocess via --gpt-config
        out = bench_gpt(args.gpt_config, args.steps, args.warmup,
                        args.batch, args.seq, accum=args.accum)
        print(json.dumps(_section_telemetry(out)))
        return
    if args.section == "rung":
        name, kw = LADDER_13B[args.rung]
        print(json.dumps(_section_telemetry(bench_gpt(
            name, max(args.steps // 2, 5), args.warmup, **kw))))
        return
    if args.section == "flash":
        out = bench_flash_vs_xla()
        # None = flash kernel not available on this backend: a clean
        # skip, not a failure
        print(json.dumps(_section_telemetry(out)
                         if out is not None else {"skipped": True}))
        return
    if args.section == "resnet":
        print(json.dumps(_section_telemetry(bench_resnet())))
        return
    if args.section == "ps":
        print(json.dumps(_section_telemetry(bench_ps())))
        return
    if args.section == "serving":
        print(json.dumps(_section_telemetry(bench_serving())))
        return
    if args.section == "fleet":
        print(json.dumps(_section_telemetry(bench_fleet())))
        return
    if args.section == "soak":
        print(json.dumps(_section_telemetry(bench_soak())))
        return
    if args.section == "resilience":
        print(json.dumps(_section_telemetry(bench_resilience())))
        return
    if args.section == "distributed":
        print(json.dumps(_section_telemetry(bench_distributed())))
        return
    if args.section == "tracing":
        print(json.dumps(_section_telemetry(bench_tracing())))
        return
    if args.section == "slo":
        print(json.dumps(_section_telemetry(bench_slo())))
        return
    if args.section == "profiling":
        print(json.dumps(_section_telemetry(bench_profiling())))
        return
    if args.section == "integrity":
        print(json.dumps(_section_telemetry(bench_integrity())))
        return
    if args.section == "lint":
        print(json.dumps(_section_telemetry(bench_lint())))
        return

    # ---- orchestrator: every section in its own subprocess ----
    extra = {}

    # continuity config (same protocol as r03/r04, feeds the regression
    # gate)
    common = ["--steps", str(args.steps), "--warmup", str(args.warmup),
              "--batch", str(args.batch), "--seq", str(args.seq),
              "--accum", str(args.accum)]
    gpt = _run_section(["--section", "gpt"] + common,
                       timeout_s=3600, tag="gpt")
    if "tokens_per_sec_per_chip" not in gpt:
        log(f"[gpt] gpt2-medium failed ({gpt.get('error', '?')[:150]}); "
            f"retrying gpt2-small in a fresh subprocess")
        small = _run_section(
            ["--section", "gpt", "--gpt-config", "gpt2-small"] + common,
            timeout_s=3600, tag="gpt-small")
        if "tokens_per_sec_per_chip" in small:
            small["fallback_from"] = gpt.get("error", "gpt2-medium failed")
            gpt = small
    extra["gpt"] = gpt
    headline = gpt if "tokens_per_sec_per_chip" in gpt else None

    if not args.no_13b:
        errors = []
        for i, (name, kw) in enumerate(LADDER_13B):
            r = _run_section(["--section", "rung", "--rung", str(i),
                              "--steps", str(args.steps),
                              "--warmup", str(args.warmup)],
                             timeout_s=3900, tag=f"rung{i}:{name}")
            if "tokens_per_sec_per_chip" in r:
                r["fallbacks_tried"] = errors
                extra["gpt_1p3b"] = r
                headline = r
                break
            errors.append({"rung": f"{name} {kw}", **r})
            log(f"[rung{i}] failed: {r.get('error', '?')[:200]}")
        else:
            extra["gpt_1p3b"] = {"error": "all rungs failed",
                                 "rungs": errors}

    if not args.no_flash_micro:
        fm = _run_section(["--section", "flash"], timeout_s=1500,
                          tag="flash")
        if fm != {"skipped": True}:
            extra["flash_vs_xla"] = fm
    if not args.no_resnet:
        extra["resnet"] = _run_section(["--section", "resnet"],
                                       timeout_s=1500, tag="resnet")
    if not args.no_ps:
        extra["ps"] = _run_section(["--section", "ps"],
                                   timeout_s=600, tag="ps")
    if not args.no_serving:
        extra["serving"] = _run_section(["--section", "serving"],
                                        timeout_s=1500, tag="serving")
        extra["fleet"] = _run_section(["--section", "fleet"],
                                      timeout_s=1500, tag="fleet")
        extra["soak"] = _run_section(["--section", "soak"],
                                     timeout_s=1500, tag="soak")
    extra["resilience"] = _run_section(["--section", "resilience"],
                                       timeout_s=600, tag="resilience")
    extra["distributed"] = _run_section(["--section", "distributed"],
                                        timeout_s=600, tag="distributed")
    extra["slo"] = _run_section(["--section", "slo"],
                                timeout_s=600, tag="slo")
    extra["profiling"] = _run_section(["--section", "profiling"],
                                      timeout_s=300, tag="profiling")
    extra["tracing"] = _run_section(["--section", "tracing"],
                                    timeout_s=300, tag="tracing")
    extra["integrity"] = _run_section(["--section", "integrity"],
                                      timeout_s=600, tag="integrity")
    extra["lint"] = _run_section(["--section", "lint"],
                                 timeout_s=300, tag="lint")
    extra["multichip"] = _run_section(["--section", "multichip"],
                                      timeout_s=900, tag="multichip")

    # ---- regression gate: >5% drop vs any prior round fails the bench
    best = prior_best()
    regression = False
    for entry in extra.values():
        if not (isinstance(entry, dict)
                and "tokens_per_sec_per_chip" in entry):
            continue
        proto = entry.get("protocol") or {}
        prior = best.get((entry["config"], proto.get("global_batch"),
                          proto.get("seq_len")))
        if prior and entry["tokens_per_sec_per_chip"] < 0.95 * prior:
            log(f"[gate] REGRESSION {entry['config']}: "
                f"{entry['tokens_per_sec_per_chip']:.0f} < 95% of prior "
                f"best {prior:.0f}")
            regression = True
    extra["regression_gate"] = {
        "prior_best": {f"{k[0]}@b{k[1]}s{k[2]}": v for k, v in best.items()},
        "regression": regression}

    if headline is None:
        print(json.dumps({
            "metric": "GPT tokens/sec/chip", "value": 0.0,
            "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "regression": regression, "extra": extra}))
        sys.exit(1)

    vs_baseline = headline["mfu"] / headline["target_mfu"]
    print(json.dumps({
        "metric": f"GPT tokens/sec/chip ({headline['config']})",
        "value": round(headline["tokens_per_sec_per_chip"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 3),
        "regression": regression,
        "extra": extra,
    }))
    if regression:
        sys.exit(1)


if __name__ == "__main__":
    main()

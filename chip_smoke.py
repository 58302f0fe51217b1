#!/usr/bin/env python3
"""chip_smoke.py — the one command that proves the main path runs on the TPU.

    python chip_smoke.py                  one chip (one device on any host)
    python chip_smoke.py --chips 4        the four-chip host's layouts instead
    python chip_smoke.py --cpu-dry-run    control flow only, tiny model, CPU

It drives ``gpt3-1.3b`` (hidden 2048, 24 layers, 16 heads x 128, vocab
50,304, context 2048; random weights from seed 0) through the entry points
a user calls, and checks what comes out:

- **train**   ``HybridEngine`` — 6 steps on one seeded 8x2048 batch: first
  loss near ln(vocab), all finite, falling, flash kernel in the lowered
  step, exactly one compile.
- **serve**   ``inference.create_predictor(Config().enable_tpu()
  .enable_generation(...))`` — 8 greedy requests of 32 new tokens added
  over several steps (prompt chunks share steps with decode rows, two
  prompts share a 256-token prefix): all FINISHED with 32 tokens, 0
  failed, one compile, >= 1 prefix hit, ragged kernel in the lowered step.
- **kernels** numerics on the chip: one mixed prefill+decode step's logits
  through the Mosaic ragged kernel against the same step through the jnp
  reference; flash forward and gradients against ``_naive_attention``.
- **cache**   train and serve run a second time: the step programs must
  then come out of the persistent compile cache.

One process holds a chip at a time, so this parent imports neither jax nor
paddle_tpu and runs the legs as child processes, one after another.  Every
leg first prints what jax found and FAILS unless the platform is ``tpu``:
no leg carries on on the CPU.  Times and sizes printed here are smoke
output — what one run saw — not a benchmark.

The last line of stdout is the result, printed only when every leg passed:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import logging
import math
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "LEG_RESULT "

# per-leg wall limits; a default run's legs sum to under the 1200 s the
# whole smoke may take, compilation from an empty cache included
LEG_TIMEOUT_S = {"train": 300, "serve": 200, "kernels": 150,
                 "four_train": 420, "four_serve": 420}

# what is cut for the CPU dry run (tiny model, short everything); the real
# sizes are the ones the docstring states
SIZES = {
    False: dict(model="gpt3-1.3b", batch=8, seq=2048, steps=6,
                page_size=16, num_pages=1024, four_serve_pages=384,
                chunk_len=128, new_tokens=32,
                prompts=(1900, 17, 64, 1200, 129, 500), shared_prefix=256,
                prefix_tails=(44, 64),
                mixed_rows=((64, 64), (64, 700), (1, 17), (1, 130),
                            (1, 513), (1, 1200), (1, 1932), (0, 0)),
                flash_shapes=((4, 16, 2048, 64), (2, 16, 2048, 128))),
    True: dict(model="tiny", batch=4, seq=128, steps=6,
               page_size=4, num_pages=128, four_serve_pages=128,
               chunk_len=16, new_tokens=8,
               prompts=(100, 5, 17, 60, 33, 41), shared_prefix=32,
               prefix_tails=(7, 11),
               mixed_rows=((8, 8), (8, 40), (1, 5), (1, 17), (1, 33),
                           (1, 64), (1, 120), (0, 0)),
               flash_shapes=((1, 2, 256, 64), (1, 2, 256, 128))),
}

# Stated tolerances (each leg prints the difference it measured).
# first loss: ln(vocab) = 10.83 for a model that knows nothing, plus half
# the variance of its random logits (2048 x 0.02^2 / 2 = 0.41): 11.22 on
# the chip in PR 21
FIRST_LOSS_TOL = 0.5
# logits are O(1) (std ~0.9): a wrong mask, page or position moves them
# by whole units, bf16 rounding through 24 layers by hundredths — PR 21's
# chip run measured max 0.059, mean 0.010; the bound is 4x that maximum
LOGIT_TOL = 0.25
# flash vs naive, both bf16: 4 bf16 ulps (2^-8 each) of the largest
# reference value — PR 21's chip run measured at most 1.6 of them; a
# wrong block or mask is off by the value itself
FLASH_TOL_REL = 2.0 ** -6
# the same seed and batch under another layout: bf16 matmuls split and
# summed in another order (PR 21's chip run: at most 0.0033 over 3 steps)
LAYOUT_LOSS_TOL = 0.05


# ----------------------------------------------------------------- parent


def run_leg(name, extra):
    """Run one leg as a child, echo its output, return its result dict
    (None when it failed, timed out or printed no result)."""
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--leg", name,
           *extra]
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                            text=True)
    try:
        out, _ = proc.communicate(timeout=LEG_TIMEOUT_S[name])
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        print(out, end="")
        print(f"[smoke] leg {name} killed at its {LEG_TIMEOUT_S[name]} s "
              f"limit", flush=True)
        return None
    print(out, end="")
    wall = time.perf_counter() - t0
    last = out.strip().splitlines()[-1] if out.strip() else ""
    if proc.returncode != 0 or not last.startswith(RESULT_TAG):
        print(f"[smoke] leg {name} FAILED (exit {proc.returncode}, "
              f"{wall:.0f} s)", flush=True)
        return None
    print(f"[smoke] leg {name} passed in {wall:.0f} s (process wall, "
          f"smoke output)", flush=True)
    return json.loads(last[len(RESULT_TAG):])


def parent(args):
    if not os.path.isdir(os.path.join(HERE, "paddle_tpu")):
        print("chip_smoke: no paddle_tpu package beside this script — it "
              "drives the repository and is nothing without it",
              file=sys.stderr)
        return 1
    extra = ["--cpu-dry-run"] if args.cpu_dry_run else []
    if args.cpu_dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        print("[smoke] CPU DRY RUN: tiny model, interpreted kernels — a "
              "check of this script's control flow, NOT a smoke result")

    results = {}

    def leg(key, name, more=()):
        results[key] = run_leg(name, [*extra, *more])
        return results[key] is not None

    # the first leg finds out in seconds whether there is a chip at all
    if not leg("train", "train"):
        return 1
    if args.chips == 4:
        # the one-chip train leg above gave the losses every layout must
        # reproduce; the rest of the one-chip smoke is the default run's
        loss = ["--expect-losses",
                ",".join(map(repr, results["train"]["losses"][:3]))]
        ok = leg("four_pp2_mp2", "four_train", ["--layout", "pp2mp2", *loss])
        ok &= leg("four_dp2_mp2", "four_train", ["--layout", "dp2mp2", *loss])
        ok &= leg("four_serve", "four_serve")
    else:
        ok = leg("serve", "serve")
        ok &= leg("kernels", "kernels")
        ok &= leg("train_warm", "train", ["--warm"])
        ok &= leg("serve_warm", "serve", ["--warm"])

    for cold, warm in (("train", "train_warm"), ("serve", "serve_warm")):
        if results.get(cold) and results.get(warm):
            c, w = results[cold], results[warm]
            print(f"[smoke] {cold}: set-up + first step "
                  f"{c['setup_s'] + c['first_step_s']:.1f} s with the cache "
                  f"as found, {w['setup_s'] + w['first_step_s']:.1f} s on "
                  f"the second run (step from cache: "
                  f"{w['cache']['step_from_cache']}) — smoke output")
    print("[smoke] summary " + json.dumps(results, sort_keys=True))
    if not ok:
        print("[smoke] FAILED: " + ", ".join(
            k for k, v in results.items() if v is None), flush=True)
        return 1
    if args.cpu_dry_run:
        print("[smoke] CPU dry run finished: control flow only, no device "
              "result printed")
        return 0
    print(json.dumps({"ok": True, "device": results["train"]["device"]}),
          flush=True)
    return 0


# ------------------------------------------------------- shared leg parts


def open_leg(name, dry_run):
    """Print what jax found; refuse anything but a TPU (or, for the dry
    run, anything but the CPU).  Returns (jax.devices(), cache_dir)."""
    import importlib.metadata

    import jax
    import jaxlib

    from paddle_tpu.core.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    if dry_run:     # the tiny steps compile in less than the threshold
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    d0 = devices[0]
    print(f"[{name}] platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"cache_dir={cache_dir}", flush=True)
    want = "cpu" if dry_run else "tpu"
    if d0.platform != want:
        sys.exit(f"chip_smoke: leg {name} needs platform {want!r} but "
                 f"jax.devices() is {devices} — no TPU, no smoke (this "
                 f"script never carries on on the CPU; --cpu-dry-run "
                 f"checks its control flow there)")
    return devices, cache_dir


class CacheLog(logging.Handler):
    """Names of the programs jax read from / missed in the persistent
    compile cache, from its own debug log (the only place it names them)."""

    _PAT = re.compile(r"(cache hit|CACHE MISS) for '([^']+)'")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.hits, self.misses = [], []
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False       # jax's own handler would print it all
        log.addHandler(self)

    def emit(self, record):
        if record.levelno >= logging.WARNING:
            print(self.format(record), file=sys.stderr)
        m = self._PAT.search(record.getMessage())
        if m:
            (self.hits if m.group(1) == "cache hit"
             else self.misses).append(m.group(2))

    def report(self, step_fn, cache_dir, warm):
        module = "jit_" + step_fn.__wrapped__.__name__
        rep = {"dir": cache_dir, "step_module": module,
               "step_from_cache": module in self.hits,
               "hits": len(self.hits), "misses": len(self.misses)}
        print(f"[cache] {rep}", flush=True)
        # the watchdog counts argument signatures; this counts what jax
        # handed to the compiler (a changed sharding or commitment of an
        # argument is a new executable under an unchanged signature)
        compiles = (self.hits + self.misses).count(module)
        check(compiles == 1, f"jax compiled {module} {compiles} times")
        if warm:
            check(rep["step_from_cache"],
                  f"second run: {module} was compiled afresh instead of "
                  f"read from {cache_dir} (hits: {sorted(set(self.hits))})")
        return rep


def check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke CHECK FAILED: {msg}")


def device_info(devices):
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def peak_hbm_gib(device, dry_run):
    """The allocator's high-water mark.  On this runtime it counts live
    arrays only — a running program's temporaries are not in it (PR 21's
    chip run: 7.38 GiB for a step XLA plans at 7.35 + 8.15 GiB) — so the
    legs print XLA's plan beside it."""
    if dry_run:
        return None         # the CPU backend reports no memory stats
    return round(device.memory_stats()["peak_bytes_in_use"] / 2.0 ** 30, 2)


def planned_memory_gib(lowered):
    """XLA's memory plan for the step.  Called after the steps ran, so the
    compile is a read from the persistent cache."""
    mem = lowered.compile().memory_analysis()
    return {k: round(getattr(mem, f"{k}_size_in_bytes") / 2.0 ** 30, 2)
            for k in ("argument", "temp", "alias", "output")}


def finish(result):
    # the legs drive jax only: nothing here may have loaded the lazily
    # g++-built TCPStore library
    native = sys.modules.get("paddle_tpu.native")
    check(native is None or native._LIB is None,
          "a smoke leg loaded paddle_tpu/native/_libtcpstore.so")
    print(RESULT_TAG + json.dumps(result), flush=True)


def train_batch(sz, vocab):
    import numpy as np

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, vocab, (sz["batch"], sz["seq"])).astype(np.int32)
    labels = np.concatenate(
        [tokens[:, 1:], np.full((sz["batch"], 1), -100)], 1).astype(np.int32)
    return tokens, labels


def run_train_steps(eng, params, opt, tokens, labels, n):
    """n steps, each waited for: ([loss], [wall seconds])."""
    import jax

    losses, walls = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt, loss = eng.step(params, opt, tokens, labels)
        jax.block_until_ready(loss)
        walls.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss))
    return params, opt, losses, walls


def check_losses(losses, vocab, dry_run):
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if not dry_run:
        check(abs(losses[0] - math.log(vocab)) <= FIRST_LOSS_TOL,
              f"first loss {losses[0]:.3f} is not within {FIRST_LOSS_TOL} "
              f"of ln({vocab}) = {math.log(vocab):.3f}")


def one_compile(name):
    from paddle_tpu.observability.compile_watchdog import default_watchdog

    compiles = default_watchdog().report()[name]["compiles"]
    check(compiles == 1, f"{name} compiled {compiles} times, expected 1")


def init_params(cfg):
    """``gpt_init`` as ONE program.  Called eagerly it is a small program
    per random matrix, and from an empty cache the serve leg's set-up took
    67 s on the chip that way against 18 s this way (PR 21)."""
    import jax

    from paddle_tpu.models.gpt import gpt_init

    return jax.jit(lambda key: gpt_init(cfg, key))(jax.random.key(0))


def train_config(sz):
    from paddle_tpu.models.gpt import GPT_CONFIGS

    return dataclasses.replace(GPT_CONFIGS[sz["model"]], use_flash=True,
                               remat="full", dtype="bfloat16")


# -------------------------------------------------------------- train leg


def leg_train(args):
    sz = SIZES[args.cpu_dry_run]
    devices, cache_dir = open_leg("train", args.cpu_dry_run)
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.engine import EngineConfig, HybridEngine
    from paddle_tpu.observability.compile_watchdog import (
        enable_compile_watchdog)
    from paddle_tpu.observability.goodput import device_peak_flops

    peak, kind = device_peak_flops(devices[0])
    check(peak is not None or args.cpu_dry_run,
          f"device kind {kind!r} is not in goodput.PEAK_FLOPS: no MFU "
          f"could be reported for it")
    enable_compile_watchdog()
    cache_log = CacheLog()

    t0 = time.perf_counter()
    cfg = train_config(sz)
    eng = HybridEngine(cfg, devices=devices[:1],
                       engine_cfg=EngineConfig(accum_steps=1,
                                               opt_dtype="bfloat16"))
    params, opt = eng.init(seed=0)
    jax.block_until_ready((params, opt))
    setup_s = time.perf_counter() - t0
    tokens, labels = train_batch(sz, cfg.vocab_size)

    lowered = eng.build_step().lower(
        params, opt, tokens, labels, jnp.asarray(eng.ec.lr, jnp.float32),
        jnp.asarray(0, jnp.uint32))
    check("tpu_custom_call" in lowered.as_text() or args.cpu_dry_run,
          "no tpu_custom_call in the lowered train step: attention is not "
          "the flash kernel")

    params, opt, losses, walls = run_train_steps(
        eng, params, opt, tokens, labels, sz["steps"])
    print(f"[train] {sz['model']} b{sz['batch']}xs{sz['seq']} full remat, "
          f"bf16 Adam: losses {[round(x, 4) for x in losses]}")
    print(f"[train] set-up {setup_s:.1f} s, step wall seconds (first "
          f"includes compile) {walls} — smoke output")
    check_losses(losses, cfg.vocab_size, args.cpu_dry_run)
    one_compile("hybrid_engine::step")
    cache = cache_log.report(eng.build_step(), cache_dir, args.warm)
    peak_gib = peak_hbm_gib(devices[0], args.cpu_dry_run)
    plan = planned_memory_gib(lowered)
    print(f"[train] peak_bytes_in_use {peak_gib} GiB; XLA's plan for the "
          f"step (GiB) {plan}")
    finish({"device": device_info(devices), "losses": losses,
            "step_wall_s": walls, "setup_s": round(setup_s, 2),
            "first_step_s": walls[0], "peak_hbm_gib": peak_gib,
            "planned_gib": plan, "cache": cache})


# -------------------------------------------------------------- serve leg


def smoke_prompts(sz, vocab):
    """The plain prompts, then the two that share a prefix."""
    import numpy as np

    rng = np.random.RandomState(1)
    plain = [rng.randint(0, vocab, n).tolist() for n in sz["prompts"]]
    prefix = rng.randint(0, vocab, sz["shared_prefix"]).tolist()
    pair = [prefix + rng.randint(0, vocab, n).tolist()
            for n in sz["prefix_tails"]]
    return plain, pair


def drive_requests(server, has_work, plain, pair, new_tokens):
    """Add the requests in waves between steps, the second of the prefix
    pair only once the first's prompt is cached.  ``server`` is a
    predictor or an engine.  Returns (requests, steps taken, steps that ran
    a prompt chunk beside a decode row, first step's wall seconds)."""
    from paddle_tpu.serving import RequestState, SamplingParams

    sampling = SamplingParams(max_new_tokens=new_tokens)     # greedy
    add = lambda p: server.add_request(p, sampling)
    reqs = [add(plain[0]), add(plain[1]), add(pair[0])]
    waves = [plain[2:4], plain[4:6]]
    steps = mixed = 0
    first_step_s = None
    while has_work() or len(reqs) < 8:
        live = [r for r in reqs if r.state == RequestState.RUNNING]
        prefilling = sum(r.prompt_pos < len(r.prompt) for r in live)
        if 0 < prefilling < len(live):
            mixed += 1
        t0 = time.perf_counter()
        server.step()
        if first_step_s is None:
            first_step_s = time.perf_counter() - t0
        steps += 1
        check(steps < 2000, "the serving loop does not terminate")
        if waves and steps % 3 == 0:
            reqs += [add(p) for p in waves.pop(0)]
        if len(reqs) == 7 and reqs[2].t_first_token is not None:
            reqs.append(add(pair[1]))
    return reqs, steps, mixed, first_step_s


def check_requests(reqs, new_tokens):
    from paddle_tpu.serving import RequestState

    states = [r.state for r in reqs]
    check(len(reqs) >= 8 and all(s == RequestState.FINISHED for s in states),
          f"request states {states} (reasons "
          f"{[r.finish_reason for r in reqs]})")
    check(all(len(r.output) == new_tokens for r in reqs),
          f"output lengths {[len(r.output) for r in reqs]}, expected "
          f"{new_tokens} each")


def lowered_serve_step(engine):
    return engine._step_fn.lower(*engine.step_args())


def leg_serve(args):
    sz = SIZES[args.cpu_dry_run]
    devices, cache_dir = open_leg("serve", args.cpu_dry_run)
    import jax

    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.observability.compile_watchdog import (
        enable_compile_watchdog)

    enable_compile_watchdog()
    cache_log = CacheLog()

    t0 = time.perf_counter()
    cfg = GPT_CONFIGS[sz["model"]]
    params = init_params(cfg)
    config = Config().enable_generation(
        cfg, params, page_size=sz["page_size"], num_pages=sz["num_pages"],
        max_batch_size=8, chunk_len=sz["chunk_len"])
    if not args.cpu_dry_run:
        config.enable_tpu()
    pred = create_predictor(config)
    jax.block_until_ready((pred.engine.params, pred.engine.cache.k_pages))
    setup_s = time.perf_counter() - t0

    lowered = lowered_serve_step(pred.engine)
    check("tpu_custom_call" in lowered.as_text() or args.cpu_dry_run,
          "no tpu_custom_call in the lowered serving step: attention is "
          "not the ragged kernel")

    plain, pair = smoke_prompts(sz, cfg.vocab_size)
    t0 = time.perf_counter()
    reqs, steps, mixed, first_step_s = drive_requests(
        pred, pred.engine.has_work, plain, pair, sz["new_tokens"])
    wall = time.perf_counter() - t0
    check_requests(reqs, sz["new_tokens"])
    snap = pred.metrics()
    print(f"[serve] {len(reqs)} requests, prompts "
          f"{[len(r.prompt) for r in reqs]}, {steps} steps ({mixed} ran "
          f"prompt chunks beside decode rows) in {wall:.1f} s, compile "
          f"included — smoke output")
    print(f"[serve] requests {snap['requests']} prefix_cache "
          f"{snap['prefix_cache']}")
    check(snap["requests"]["failed"] == 0,
          f"serving_requests_failed_total = {snap['requests']['failed']}")
    check(snap["prefix_cache"]["hits"] >= 1, "no prefix-cache hit")
    check(mixed >= 1, "no step mixed prompt chunks with decode rows")
    one_compile("serving::unified_step")
    cache = cache_log.report(pred.engine._step_fn, cache_dir, args.warm)
    peak_gib = peak_hbm_gib(devices[0], args.cpu_dry_run)
    plan = planned_memory_gib(lowered)
    print(f"[serve] set-up {setup_s:.1f} s, first step (compile) "
          f"{first_step_s:.1f} s, peak_bytes_in_use {peak_gib} GiB; XLA's "
          f"plan for the step (GiB) {plan}")
    finish({"device": device_info(devices), "steps": steps,
            "mixed_steps": mixed, "requests": snap["requests"],
            "prefix_cache": snap["prefix_cache"],
            "setup_s": round(setup_s, 2),
            "first_step_s": round(first_step_s, 2),
            "peak_hbm_gib": peak_gib, "planned_gib": plan,
            "tokens": [r.output for r in reqs], "cache": cache})


# ------------------------------------------------------------ kernels leg


def ragged_logits_diff(sz, kernel_path):
    """One mixed prefill+decode step through ``gpt_ragged_step`` twice —
    the kernel and ``_ragged_attention_ref`` — over the same random page
    pool.  Returns (max |dlogit|, mean |dlogit|, rows whose argmax agree,
    active rows)."""
    import jax
    import numpy as np

    from paddle_tpu.kernels import dispatch
    from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_ragged_step
    from paddle_tpu.models.ragged import empty_batch

    cfg = GPT_CONFIGS[sz["model"]]
    params = init_params(cfg)
    rows, ps, Q = sz["mixed_rows"], sz["page_size"], sz["chunk_len"]
    B = len(rows)
    T = Q + B - 1                                # the engine's token budget
    max_pages = math.ceil(cfg.max_seq_len / ps)
    rng = np.random.RandomState(2)
    batch = empty_batch(B, T, max_pages)
    tokens, row_of, slot_of, qlens, ctxs, tables = batch
    off = page = 0
    for b, (qlen, ctx) in enumerate(rows):
        n = math.ceil(ctx / ps)
        tables[b, :n] = np.arange(page, page + n)
        page += n
        tokens[off:off + qlen] = rng.randint(0, cfg.vocab_size, qlen)
        row_of[off:off + qlen] = b
        slot_of[off:off + qlen] = np.arange(qlen)
        qlens[b], ctxs[b] = qlen, ctx
        off += qlen
    pool = (cfg.num_layers, page + 8, ps, cfg.num_heads, cfg.head_dim)
    kk, kv = jax.random.split(jax.random.key(3))
    k_pages = jax.random.normal(kk, pool, cfg.jdtype())
    v_pages = jax.random.normal(kv, pool, cfg.jdtype())

    def logits(path):
        step = jax.jit(functools.partial(gpt_ragged_step, cfg, max_q=Q,
                                         attn_path=path))
        out, _, _ = step(params, batch, k_pages, v_pages)
        return np.asarray(out, np.float32)[qlens > 0]

    ker, ref = logits(kernel_path), logits(dispatch.REFERENCE)
    check(np.isfinite(ker).all() and np.isfinite(ref).all(),
          "non-finite logits from the ragged step")
    diff = np.abs(ker - ref)
    agree = int((ker.argmax(-1) == ref.argmax(-1)).sum())
    return float(diff.max()), float(diff.mean()), agree, len(ker)


def flash_diffs(shape, kernel_path):
    """Flash forward and (dq, dk, dv) against ``_naive_attention``, bf16
    causal, under one random cotangent: {name: (max |diff|, max |ref|)}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import flash_attention
    from paddle_tpu.ops.attention import _naive_attention

    q, k, v, g = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in jax.random.split(jax.random.key(4), 4))

    def out_and_grads(attn):
        def loss(q, k, v):
            return (attn(q, k, v).astype(jnp.float32)
                    * g.astype(jnp.float32)).sum()

        return jax.jit(lambda q, k, v: (
            attn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

    got = out_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, path=kernel_path))(q, k, v)
    want = out_and_grads(lambda q, k, v: _naive_attention(
        q, k, v, causal=True, training=False))(q, k, v)
    f32 = lambda a: np.asarray(a, np.float32)
    return {name: (float(np.abs(f32(a) - f32(b)).max()),
                   float(np.abs(f32(b)).max()))
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)}


def leg_kernels(args):
    sz = SIZES[args.cpu_dry_run]
    devices, _ = open_leg("kernels", args.cpu_dry_run)
    from paddle_tpu.kernels import dispatch

    path = dispatch.INTERPRET if args.cpu_dry_run else dispatch.MOSAIC
    result = {"device": device_info(devices), "flash": {}}
    for shape in sz["flash_shapes"]:
        diffs = flash_diffs(shape, path)
        print(f"[kernels] flash vs _naive_attention {list(shape)} bf16 "
              f"causal, (max |diff|, max |ref|): {diffs}; tolerance "
              f"{FLASH_TOL_REL} x max |ref|")
        for name, (d, scale) in diffs.items():
            check(d <= FLASH_TOL_REL * scale,
                  f"flash {name} at {shape}: max |diff| {d} > "
                  f"{FLASH_TOL_REL} x {scale}")
        result["flash"]["x".join(map(str, shape))] = diffs
    dmax, dmean, agree, n = ragged_logits_diff(sz, path)
    print(f"[kernels] ragged kernel vs _ragged_attention_ref, logits of one "
          f"mixed step (rows (query, context) = {list(sz['mixed_rows'])}): "
          f"max |diff| {dmax:.4f}, mean {dmean:.5f}, argmax agrees on "
          f"{agree}/{n} rows; tolerance {LOGIT_TOL}")
    check(dmax <= LOGIT_TOL, f"ragged logits max |diff| {dmax} > {LOGIT_TOL}")
    result["ragged_logits"] = {"max_diff": dmax, "mean_diff": dmean,
                               "argmax_agree": [agree, n]}
    result["peak_hbm_gib"] = peak_hbm_gib(devices[0], args.cpu_dry_run)
    finish(result)


# ------------------------------------------------------- four-chip legs


def bytes_in_use(devices, dry_run):
    if dry_run:
        return None
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def leg_four_train(args):
    """The train leg's step under a four-chip layout, placement asserted
    from the live arrays (``mesh.resolve_spec`` degrades silently to
    replication, so a spec proves nothing)."""
    sz = SIZES[args.cpu_dry_run]
    devices, cache_dir = open_leg(f"four_train {args.layout}",
                                  args.cpu_dry_run)
    check(len(devices) >= 4, f"{len(devices)} devices: not a four-chip host")
    import jax

    from paddle_tpu.distributed.engine import EngineConfig, HybridEngine
    from paddle_tpu.observability.compile_watchdog import (
        enable_compile_watchdog)

    enable_compile_watchdog()
    cache_log = CacheLog()
    # by hand only (``--leg four_train --layout ...``): mp2zr2 (ZeRO-2)
    # takes minutes to compile; sep2mp2 splits the sequence through
    # kernels/ring_attention.py
    layout = {"pp2mp2": dict(pp=2, mp=2), "dp2mp2": dict(dp=2, mp=2),
              "mp2zr2": dict(mp=2, sharding=2),
              "sep2mp2": dict(sep=2, mp=2)}[args.layout]
    cfg = dataclasses.replace(train_config(sz), seq_parallel="ring")
    t0 = time.perf_counter()
    eng = HybridEngine(cfg, devices=devices[:4], engine_cfg=EngineConfig(
        accum_steps=1, opt_dtype="bfloat16",
        num_microbatches=4 if "pp" in layout else 1), **layout)
    params, opt = eng.init(seed=0)
    jax.block_until_ready((params, opt))
    setup_s = time.perf_counter() - t0

    qkv = params["blocks"]["qkv_w"]
    L, D, E = qkv.shape
    want = (L // layout.get("pp", 1), D, E // layout["mp"])
    shards = qkv.addressable_shards
    check(all(s.data.shape == want for s in shards),
          f"qkv_w shards {[s.data.shape for s in shards]}, expected {want}")
    check(len({s.device.id for s in shards}) == 4,
          f"qkv_w shards sit on devices {[s.device.id for s in shards]}")
    windows = {tuple((sl.start, sl.stop) for sl in s.index) for s in shards}
    check(len(windows) == layout.get("pp", 1) * layout["mp"],
          f"qkv_w has {len(windows)} distinct shard windows")

    used = bytes_in_use(devices[:4], args.cpu_dry_run)
    if used is not None:
        print(f"[four_train {args.layout}] bytes_in_use per device after "
              f"init {[round(b / 2.0 ** 30, 2) for b in used]} GiB")
        check(max(used) <= 4 * min(used),
              f"device memory is not of one order: {used}")

    # the one-chip leg's own first losses, step for step: at lr 1e-4
    # without warm-up the third is above the first, so "falling" cannot
    # be asked of three steps — "the same as on one chip" can
    want_losses = [float(x) for x in args.expect_losses.split(",")]
    tokens, labels = train_batch(sz, cfg.vocab_size)
    params, opt, losses, walls = run_train_steps(
        eng, params, opt, tokens, labels, len(want_losses))
    deltas = [round(a - b, 5) for a, b in zip(losses, want_losses)]
    print(f"[four_train {args.layout}] qkv_w {qkv.shape} -> 4 shards of "
          f"{want} on devices {sorted(s.device.id for s in shards)}; "
          f"losses {[round(x, 4) for x in losses]}, minus the one-chip "
          f"leg's {deltas} (tolerance {LAYOUT_LOSS_TOL}); set-up "
          f"{setup_s:.1f} s, step wall seconds (first includes compile) "
          f"{walls} — smoke output")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(all(abs(d) <= LAYOUT_LOSS_TOL for d in deltas),
          f"losses {losses} differ from the one-chip leg's {want_losses} "
          f"by more than {LAYOUT_LOSS_TOL}")
    one_compile("hybrid_engine::step")
    finish({"device": device_info(devices), "losses": losses,
            "loss_minus_one_chip": deltas,
            "step_wall_s": walls, "setup_s": round(setup_s, 2),
            "bytes_in_use": used,
            "cache": cache_log.report(eng.build_step(), cache_dir, False)})


def leg_four_serve(args):
    """``Engine(mesh=build_mesh(mp=4))`` answers the serve leg's requests
    token-identically to the unsharded engine; then four one-device
    engines, to see where their page pools land.  The model is float32
    here: token identity across two summation orders is a fair demand
    only where rounding cannot flip a near-tie between random logits (in
    bf16 the kernels leg sees argmax move at a 0.06 logit difference)."""
    sz = SIZES[args.cpu_dry_run]
    devices, _ = open_leg("four_serve", args.cpu_dry_run)
    check(len(devices) >= 4, f"{len(devices)} devices: not a four-chip host")
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.mesh import assert_placement, build_mesh
    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.serving import Engine, SamplingParams

    cfg = dataclasses.replace(GPT_CONFIGS[sz["model"]], dtype="float32")
    params = init_params(cfg)
    # float32 pages are twice the bytes: 384 of them hold the 296 the
    # requests need and leave the one-chip engine inside 16 GiB
    knobs = dict(page_size=sz["page_size"], num_pages=sz["four_serve_pages"],
                 max_batch_size=8, chunk_len=sz["chunk_len"])
    plain, pair = smoke_prompts(sz, cfg.vocab_size)

    def answers(engine):
        reqs, _, _, _ = drive_requests(engine, engine.has_work, plain, pair,
                                       sz["new_tokens"])
        check_requests(reqs, sz["new_tokens"])
        return [r.output for r in reqs]

    single = answers(Engine(cfg, params, **knobs))
    mesh = build_mesh(mp=4, devices=devices[:4])
    sharded_engine = Engine(cfg, params, mesh=mesh, **knobs)
    pool = sharded_engine.cache.k_pages
    assert_placement(pool, mesh, P(None, None, None, "mp"), "k_pages")
    heads = {s.data.shape[3] for s in pool.addressable_shards}
    check(heads == {cfg.num_heads // 4} and
          len({s.device.id for s in pool.addressable_shards}) == 4,
          f"page pool shards hold {heads} heads on devices "
          f"{[s.device.id for s in pool.addressable_shards]}")
    sharded = answers(sharded_engine)
    same = sum(a == b for a, b in zip(single, sharded))
    print(f"[four_serve] mp=4 engine: page pool {pool.shape} in 4 shards "
          f"of {cfg.num_heads // 4} heads; {same}/{len(single)} requests "
          f"token-identical to the unsharded engine")
    first_diff = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                       None) for a, b in zip(single, sharded)]
    check(same == len(single),
          f"mp=4 outputs differ from the unsharded engine's; first "
          f"differing token per request: {first_diff}")
    del sharded_engine, pool

    # replicas: Engine has no device argument; a one-device mesh is the
    # only existing way to name one
    short = [p[:sz["chunk_len"]] for p in plain[:2]]
    greedy = SamplingParams(max_new_tokens=4)
    pools, outs = [], []
    for d in devices[:4]:
        eng = Engine(cfg, params, mesh=build_mesh(devices=[d]),
                     **{**knobs, "num_pages": knobs["num_pages"] // 4})
        outs.append(eng.generate(short, greedy))
        pools.append(sorted(x.id for x in eng.cache.k_pages.devices()))
    spread = pools == [[d.id] for d in devices[:4]]
    print(f"[four_serve] four one-device-mesh engines: page pools on "
          f"devices {pools} (one each: {spread}); answers equal: "
          f"{all(o == outs[0] for o in outs)}")
    finish({"device": device_info(devices), "mp4_token_identical": same,
            "replica_pool_devices": pools, "replicas_spread": spread,
            "replica_answers_equal": all(o == outs[0] for o in outs),
            "bytes_in_use": bytes_in_use(devices[:4], args.cpu_dry_run)})


LEGS = {"train": leg_train, "serve": leg_serve, "kernels": leg_kernels,
        "four_train": leg_four_train, "four_serve": leg_four_serve}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the train leg, then the four-chip host's "
                         "layouts (trainer pp2 x mp2 and dp2 x mp2, mp=4 "
                         "server, four replicas)")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny model on the CPU: checks this script's "
                         "control flow, prints no device result")
    ap.add_argument("--leg", choices=sorted(LEGS), help=argparse.SUPPRESS)
    ap.add_argument("--warm", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--layout", help=argparse.SUPPRESS)
    ap.add_argument("--expect-losses", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg:
        LEGS[args.leg](args)
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())

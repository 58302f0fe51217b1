"""Runner ``serve_hybrid``: the closed loop of runner ``serve`` around a
model of two layer kinds (block-sparse attention layers beside lightning
linear-attention layers), served through the same
``create_predictor(...).add_request / step``.

The clients' loop, the sampling of finished requests and the counts are the
accepted runner's (``Server.turn``, ``warm``, ``sample``, ``percentile``,
``mean_context``); what differs is what is built and what it is compared
with: the program's ``HybridConfig`` from the configuration file, weights
from ``reference_hybrid.make_weights``, and the sampled requests' prompt
and served tokens through ``reference_hybrid.Model``'s full forward, one
request at a time.  The numbers compared are the accepted cell's: the
widest and the mean gap by which a served token's logit lies below the
reference's best, over the positions the program decoded.

``--rehearse``: ``run.py`` swaps in the dense tiny configuration, which
this model cannot use; the cell file's ``rehearse`` block names the tiny
hybrid configuration (``"config"``) and this runner loads it.

Planted faults (``ctx["fault"]``, for ``calibrate.py`` and the tests): the
program itself run with part of the mathematics left out — ``"dense"``
(the sparse layers attend over everything past ``dense_len``: selection
left out) and ``"no_decay"`` (``lambda_h = 1``) — must each fail a limit,
as must the control (the reference in float8 in the program's place).
"""
from __future__ import annotations

import itertools
import time

import numpy as np

from benchmark import reference_hybrid as reference
from benchmark import roofline_hybrid
from benchmark import run as bench
from benchmark import traffic_gen

_serve = bench.load_module("runners", "serve")
Served, percentile, sample, mean_context = (
    _serve.Served, _serve.percentile, _serve.sample, _serve.mean_context)
WINDOW_SPAN, SPANS, COMPARED = (_serve.WINDOW_SPAN, _serve.SPANS,
                                _serve.COMPARED)
FAULTS = ("dense", "no_decay")


def pinned_requests(traffic, vocab, seed):
    """The accepted generator's stream with the place where the cycle is
    entered pinned: the sizes of one cycle and their order are
    ``traffic_gen.serving_requests``' own at ``traffic["entry_seed"]``,
    the token ids are drawn from the run's seed.

    A request of this mix lives longer than the window (some 900 steps of
    48 ms against 30 s), so a window sees less than one turn of the cycle,
    and which part it sees decided the numbers: entered at a seed-drawn
    place, 6 seeds read 290 to 319 tokens/s and an ``itl_p95_ms`` of 59 or
    66 (PERF.md).  A closed loop's schedule follows from the order step by
    step; with the entry pinned every seed runs the same schedule on other
    tokens, and the spread is the clock's.  The entry is one at which the
    window also closes on cheap steps (a prompt's first chunks): where it
    closes on the cycle's slowest, every step gained or lost at the end is
    a step of the tail, and ``itl_p95_ms`` moves with the host's speed."""
    sizes = [(len(p), o) for p, o, _ in itertools.islice(
        traffic_gen.serving_requests(traffic, vocab, traffic["entry_seed"]),
        traffic["cycle"])]
    rng = traffic_gen.rng_of(seed)
    for p, o in itertools.cycle(sizes):
        yield rng.integers(0, vocab, p).tolist(), o, -1


def model_config(ctx):
    """The configuration the run uses: the cell's, or under ``--rehearse``
    the tiny hybrid one its file names."""
    if ctx["rehearse"]:
        return bench.load_json(bench.HERE, "configs",
                               ctx["cell"]["config"] + ".json")
    return ctx["config"]


def hybrid_config(config, fault=None):
    from paddle_tpu.models.hybrid import HybridConfig

    s = reference.Sizes(config)
    return HybridConfig(
        vocab_size=s.Vp, max_seq_len=s.max_len, hidden=s.D, ffn_hidden=s.F,
        mixer_types=s.mixers, num_heads=s.H, num_kv_heads=s.Hkv,
        head_dim=s.hd, lightning_heads=s.Hl, lightning_head_dim=s.hdl,
        rope_theta=s.theta, rms_eps=s.eps, scale_emb=s.scale_emb,
        scale_depth=float(config["scale_depth"]),
        mup_depth=int(config["assumed"]["mup_depth"]),
        logit_divisor=s.logit_divisor, kernel_size=s.kernel_size,
        kernel_stride=s.kernel_stride, block_size=s.block_size,
        topk=s.topk, init_blocks=s.init_blocks, window_size=s.window_size,
        dense_len=s.dense_len, lightning_decay=fault != "no_decay",
        dtype=config["dtype"])


class Server(_serve.Server):
    """The accepted runner's clients' loop around the hybrid model."""

    def __init__(self, ctx):
        from jax.profiler import TraceAnnotation

        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.serving import RequestState, SamplingParams
        from paddle_tpu.serving.model import HybridServed

        self.span = TraceAnnotation
        self.State, self.Sampling = RequestState, SamplingParams
        config, fault = model_config(ctx), ctx.get("fault")
        if fault not in (None,) + FAULTS:
            raise ValueError(f"fault {fault!r} is none of {FAULTS}")
        self.cfg = hybrid_config(config, fault)
        self.sizes = reference.Sizes(config)
        params = reference.weights(config, ctx["seed"], self.cfg.jdtype())
        conf = Config().enable_generation(
            HybridServed(self.cfg, dense_only=fault == "dense"), params,
            **ctx["cell"]["engine"])
        if not ctx["rehearse"]:
            conf.enable_tpu()
        self.pred = create_predictor(conf)
        self.traffic = ctx["traffic"]
        self.feed = pinned_requests(self.traffic, self.sizes.V, ctx["seed"])
        self.clients = [None] * self.traffic["clients"]
        self.done, self.failed = [], []
        self.sending, self.in_window = True, False
        self.reset_counts()

    def reset_counts(self):
        super().reset_counts()
        # over the steps' live rows: positions and compressed keys each
        # sparse layer had to read at least (roofline_hybrid.row_reads)
        self.read_rows = self.span_rows = 0

    def turn(self):
        t = super().turn()
        for s in self.clients:
            if s is not None:
                read, spans = roofline_hybrid.row_reads(
                    self.sizes, len(s.req.tokens))
                self.read_rows += read
                self.span_rows += spans
        return t

    def counters(self):
        m = self.pred.engine.metrics
        return dict(super().counters(),
                    prefill_chunks=m.prefill_chunks.value,
                    context_positions=m.attention_context.value,
                    selected_positions=m.attention_selected.value,
                    state_resets=m.state_resets.value)


def score(model, params, served, numerics_model=None):
    """As the accepted runner's: over the sampled requests, the gap in the
    reference's logits of the token that was served (or, for a control, of
    the token ``numerics_model`` puts first) below the reference's best, at
    every position the program decoded."""
    every = []
    for s in served:
        toks = np.asarray(s.tokens, np.int32)
        n_prompt = len(s.prompt)
        logits = model.forward_logits(params, toks, n_prompt)[:-1]
        if numerics_model is None:
            chosen = toks[n_prompt:]
        else:
            chosen = reference.first_token(
                numerics_model.forward_logits(params, toks, n_prompt)[:-1])
        every.append(np.asarray(reference.gap_below_best(
            logits, np.asarray(chosen, np.int32))))
    gaps = np.concatenate(every) if every else np.zeros(0)
    if not gaps.size:
        return {"logit_gap_mean": 0.0, "logit_gap": 0.0, "other_token": 0.0,
                "tokens": 0}
    return {"logit_gap_mean": float(gaps.mean()),
            "logit_gap": float(gaps.max()),
            "other_token": float((gaps > 0).mean()), "tokens": gaps.size}


def run(ctx):
    import jax
    from jax.profiler import TraceAnnotation

    traffic, cell = ctx["traffic"], ctx["cell"]
    config = model_config(ctx)
    server = Server(ctx)
    server.warm()
    log = ctx["compile_log"]

    if ctx["trace_dir"]:
        jax.profiler.start_trace(ctx["trace_dir"])
    compiles_before = log.count()
    before = server.counters()
    server.reset_counts()
    server.in_window = True
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_start"]
    t = t0
    with TraceAnnotation(WINDOW_SPAN):
        while t - t0 < ctx["seconds"]:
            t = server.turn()
    t_close = t
    after = server.counters()
    compiles = log.count() - compiles_before
    steps, rows, step_s = server.steps, server.rows, list(server.step_s)
    context_rows = server.context_rows
    read_rows, span_rows = server.read_rows, server.span_rows
    if ctx["trace_dir"]:
        jax.profiler.stop_trace()
    # the window is closed: no new request, but every request sent in it
    # is owed its first token
    server.sending = False
    waited = 0
    while any(s is not None and s.in_window and not s.times
              and s.req.state in (server.State.RUNNING, server.State.QUEUED)
              for s in server.clients):
        server.turn()
        waited += 1
    everything = server.done + [s for s in server.clients if s is not None]
    finished = [s for s in server.done
                if s.times and s.times[-1] <= t_close]
    failed = list(server.failed)

    sent = [s for s in everything + failed if s.in_window]
    ttft = [s.times[0] - s.t_add for s in sent if s.times]
    never = [s for s in sent if not s.times]
    gaps, tokens = [], 0
    for s in everything + failed:
        tokens += sum(t0 < x <= t_close for x in s.times)
        gaps += [b - a for a, b in zip(s.times, s.times[1:])
                 if t0 < b <= t_close]
    elapsed = t_close - t0
    context = mean_context(everything + failed)

    peak_bytes = None
    if not ctx["rehearse"]:
        peak_bytes = max(d.memory_stats()["peak_bytes_in_use"]
                         for d in ctx["devices"])
    picked = sample(finished, traffic["checked_requests"], ctx["seed"])
    # what the reference needs of them, before the program is let go
    for s in picked:
        s.tokens, s.req = list(s.req.tokens), None
    dtype = server.cfg.jdtype()
    server.free()
    del server
    t_ref = time.perf_counter()
    model = reference.Model(config, "float32")
    scored = score(model, reference.weights(config, ctx["seed"], dtype),
                   picked)
    reference_s = time.perf_counter() - t_ref

    detail = f"{scored['tokens']} tokens of {len(picked)} requests"
    compared = {n: (scored[n], cell["limits"][n], detail) for n in COMPARED}
    correct = (all(v <= lim for v, lim, _ in compared.values())
               and not failed and not never and compiles == 0
               and scored["tokens"] > 0)
    delta = {k: after[k] - before[k] for k in after}
    ms = lambda values, q: 1e3 * percentile(values, q) if values else None
    return {
        "correct": correct, "attempted": len(sent),
        "failed": len(failed) + len(never), "compared": compared,
        "memory_peak_bytes": peak_bytes, "sample": picked,
        "end_to_end": {
            "serve_tokens_per_s": tokens / elapsed,
            "itl_p95_ms": ms(gaps, 95),
            "setup_s": setup_s},
        "counts": {"steps": steps, "rows": rows, "elapsed_s": elapsed,
                   "step_s": step_s, "tokens_out": tokens,
                   "context_rows": context_rows,
                   "ttft_p95_ms": ms(ttft, 95),
                   "max_batch_size": cell["engine"]["max_batch_size"],
                   "chunk_len": cell["engine"]["chunk_len"],
                   "read_rows": read_rows, "span_rows": span_rows,
                   "prefill_chunks": delta["prefill_chunks"],
                   "prefill_tokens": delta["prefill"],
                   "generated_tokens": delta["generated"],
                   "prefix_hit_tokens": delta["prefix_hit_tokens"],
                   "context_positions": delta["context_positions"],
                   "selected_positions": delta["selected_positions"],
                   "state_resets": delta["state_resets"],
                   "mean_context": context},
        "notes": {"compiles_in_window": compiles, "setup_s": setup_s,
                  "reference_s": reference_s, "steps": steps,
                  "requests_sent": len(sent),
                  "requests_finished": len(finished),
                  "steps_after_close": waited, "counters": delta,
                  "not_compared": {"other_token": scored["other_token"]},
                  "ttft_p50_ms": ms(ttft, 50), "ttft_p95_ms": ms(ttft, 95),
                  "itl_p50_ms": ms(gaps, 50),
                  "step_p50_ms": ms(step_s, 50),
                  "cache_hits": len(log.hits),
                  "cache_misses": len(log.misses)},
    }


def readings(ctx, seeds, control, control_seeds, fault_seeds):
    """For ``calibrate.py``: per seed a window at the cell's own load and
    the program's gaps; for the seeds asked the control's (the token the
    reference puts first in ``control`` arithmetic, and in bfloat16, at the
    same positions of the same requests) and each planted fault's (the
    program run again with the fault, scored as a sound run is)."""
    config = model_config(ctx)

    def window(seed, fault=None):
        c = dict(ctx, seed=seed, trace_dir=None, fault=fault,
                 t_start=time.perf_counter())
        c["cell"] = dict(ctx["cell"],
                         limits=dict.fromkeys(COMPARED, float("inf")))
        result = run(c)
        line = {"kind": "fault_" + fault if fault else "program",
                "seed": seed,
                "numbers": dict(result["notes"]["not_compared"],
                                **{n: v for n, (v, _, _)
                                   in result["compared"].items()}),
                "at": {n: d for n, (_, _, d)
                       in result["compared"].items()},
                "end_to_end": result["end_to_end"],
                "notes": result["notes"]}
        return line, result

    for seed in seeds:
        line, result = window(seed)
        yield line
        if seed in control_seeds:
            model = reference.Model(config, "float32")
            params = reference.weights(
                config, seed, hybrid_config(config).jdtype())
            for numerics in (control, "bfloat16"):
                scored = score(model, params, result["sample"],
                               reference.Model(config, numerics))
                yield {"kind": "control_" + numerics, "seed": seed,
                       "numbers": scored, "at": {}}
        if seed in fault_seeds:
            for fault in FAULTS:
                yield window(seed, fault)[0]

"""Runner ``serve_ssm``: the closed loop of runner ``serve`` around a model
whose every block runs a state-space mixer beside grouped-query attention,
served through the same ``create_predictor(...).add_request / step``.

The clients' loop, the sampling of finished requests, the pinned stream and
the scoring are the accepted runners' (``serve``: ``Server.turn``, ``warm``,
``sample``, ``percentile``, ``mean_context``; ``serve_hybrid``:
``pinned_requests``, ``score``); what differs is what is built and what it
is compared with: the program's ``SSMConfig`` from the configuration file,
weights from ``reference_ssm.make_weights``, and the sampled requests'
prompt and served tokens through ``reference_ssm.Model``'s full forward
(the recurrence position by position, no cache), one request at a time.
The numbers compared are the accepted cells': the widest and the mean gap
by which a served token's logit lies below the reference's best, over the
positions the program decoded.

``--rehearse``: ``run.py`` swaps in the dense tiny configuration, which
this model cannot use; the cell file's ``rehearse`` block names the tiny
configuration of this family (``"config"``) and this runner loads it.

Planted faults (``ctx["fault"]``, for ``calibrate.py`` and the tests): the
program itself run with part of the mathematics left out —
``"fixed_decay"`` (``dt`` from its bias alone: the decay no longer depends
on the token) and ``"no_carry"`` (the convolution reads zeros before every
chunk: the window is not carried, the bug a chunked prefill invites) — must
each fail a limit, as must the control (the reference in float8 in the
program's place).
"""
from __future__ import annotations

import time

from benchmark import reference_ssm as reference
from benchmark import run as bench

_serve = bench.load_module("runners", "serve")
_hybrid = bench.load_module("runners", "serve_hybrid")
percentile, sample, mean_context = (_serve.percentile, _serve.sample,
                                    _serve.mean_context)
pinned_requests, score = _hybrid.pinned_requests, _hybrid.score
WINDOW_SPAN, SPANS, COMPARED = (_serve.WINDOW_SPAN, _serve.SPANS,
                                _serve.COMPARED)
FAULTS = ("fixed_decay", "no_carry")


def model_config(ctx):
    """The configuration the run uses: the cell's, or under ``--rehearse``
    the tiny one of this family that its file names."""
    if ctx["rehearse"]:
        return bench.load_json(bench.HERE, "configs",
                               ctx["cell"]["config"] + ".json")
    return ctx["config"]


def ssm_config(config, fault=None):
    from paddle_tpu.models.ssm import SSMConfig

    s = reference.Sizes(config)
    return SSMConfig(
        vocab_size=s.Vp, max_seq_len=s.max_len, hidden=s.D, ffn_hidden=s.F,
        num_layers=s.L, num_heads=s.H, num_kv_heads=s.Hkv, head_dim=s.hd,
        rope_theta=s.theta, ssm_heads=s.Hs, ssm_head_dim=s.P,
        ssm_state=s.N, ssm_groups=s.G, conv_width=s.K, rms_eps=s.eps,
        embedding_multiplier=s.embedding_multiplier,
        attention_in_multiplier=s.attention_in,
        attention_out_multiplier=s.attention_out,
        key_multiplier=s.key_multiplier, ssm_in_multiplier=s.ssm_in,
        ssm_multipliers=s.ssm_multipliers, ssm_out_multiplier=s.ssm_out,
        mlp_multipliers=s.mlp_multipliers,
        lm_head_multiplier=s.lm_head_multiplier,
        input_dependent_decay=fault != "fixed_decay",
        carry_conv_window=fault != "no_carry", dtype=config["dtype"])


class Server(_serve.Server):
    """The accepted runner's clients' loop around the parallel-mixer
    model."""

    def __init__(self, ctx):
        from jax.profiler import TraceAnnotation

        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.serving import RequestState, SamplingParams
        from paddle_tpu.serving.model import SSMServed

        self.span = TraceAnnotation
        self.State, self.Sampling = RequestState, SamplingParams
        config, fault = model_config(ctx), ctx.get("fault")
        if fault not in (None,) + FAULTS:
            raise ValueError(f"fault {fault!r} is none of {FAULTS}")
        self.cfg = ssm_config(config, fault)
        self.sizes = reference.Sizes(config)
        params = reference.weights(config, ctx["seed"], self.cfg.jdtype())
        conf = Config().enable_generation(SSMServed(self.cfg), params,
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

    def counters(self):
        m = self.pred.engine.metrics
        return dict(super().counters(),
                    prefill_chunks=m.prefill_chunks.value,
                    context_positions=m.attention_context.value,
                    state_resets=m.state_resets.value,
                    state_rows_chunk=m.state_row_steps_chunk.value,
                    state_rows_decode=m.state_row_steps_decode.value)


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
    delta = {k: after[k] - before[k] for k in after}
    correct = (all(v <= lim for v, lim, _ in compared.values())
               and not failed and not never and compiles == 0
               and scored["tokens"] > 0 and delta["preempted"] == 0)
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
                   "page_size": cell["engine"]["page_size"],
                   "prefill_chunks": delta["prefill_chunks"],
                   "prefill_tokens": delta["prefill"],
                   "generated_tokens": delta["generated"],
                   "prefix_hit_tokens": delta["prefix_hit_tokens"],
                   "context_positions": delta["context_positions"],
                   "state_resets": delta["state_resets"],
                   "state_rows_chunk": delta["state_rows_chunk"],
                   "state_rows_decode": delta["state_rows_decode"],
                   "mean_context": context},
        "notes": {"compiles_in_window": compiles, "setup_s": setup_s,
                  "reference_s": reference_s, "steps": steps,
                  "requests_sent": len(sent),
                  "requests_finished": len(finished),
                  "steps_after_close": waited, "counters": delta,
                  "not_compared": {"other_token": scored["other_token"]},
                  "ttft_p50_ms": ms(ttft, 50), "ttft_p95_ms": ms(ttft, 95),
                  "itl_p50_ms": ms(gaps, 50), "itl_p90_ms": ms(gaps, 90),
                  "itl_p99_ms": ms(gaps, 99),
                  "step_p50_ms": ms(step_s, 50),
                  "step_p95_ms": ms(step_s, 95),
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

    def controls(seed, served):
        # in a scope of its own: the weights go before the next window
        # builds its own (two sets do not fit the chip)
        model = reference.Model(config, "float32")
        params = reference.weights(config, seed, ssm_config(config).jdtype())
        return [{"kind": "control_" + numerics, "seed": seed, "at": {},
                 "numbers": score(model, params, served,
                                  reference.Model(config, numerics))}
                for numerics in (control, "bfloat16")]

    for seed in seeds:
        line, result = window(seed)
        yield line
        if seed in control_seeds:
            yield from controls(seed, result["sample"])
        if seed in fault_seeds:
            for fault in FAULTS:
                yield window(seed, fault)[0]

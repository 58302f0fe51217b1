"""Runner ``serve``: ``create_predictor(...).add_request / step`` under a
closed loop of clients.

Each client sends its next request when its last one has finished (callers
that wait for a reply).  Set-up builds the predictor around weights the
benchmark makes from the seed and runs the clients until every one has
finished a request: that compiles the one step program and fills the prefix
cache, so the window measures steady state.  All times are the harness's
own, taken around ``add_request`` and ``step``: a token exists when the step
that produced it has returned.

After the window has closed no new request is sent; the loop goes on until
every request sent in the window has its first token, so the tail of TTFT is
the tail of all of them.  Then the predictor is freed and the plain
reference runs once over a sample of the requests the window finished (drawn
from the seed, the longest and one served from a cached prefix in it): the
numbers compared are the widest and the mean gap by which a served token's
logit lies below the reference's best, over the positions the program decoded.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import reference, traffic_gen

WINDOW_SPAN = "bench/window"
SPANS = ("bench/add_request", "bench/step", "bench/client")
# The widest gap and the mean gap over the decoded positions of the sample:
# the control has to fail one of them (PERF.md has the readings).
COMPARED = ("logit_gap", "logit_gap_mean")
PAD_TO = 128        # the reference pads a row to a multiple: few shapes


class Served:
    """One request as the harness saw it."""

    __slots__ = ("req", "prompt", "want", "prefix", "t_add", "times",
                 "in_window", "tokens")

    def __init__(self, req, prompt, want, prefix, t_add, in_window):
        self.req, self.prompt, self.want, self.prefix = req, prompt, want, \
            prefix
        self.t_add, self.times, self.in_window = t_add, [], in_window
        self.tokens = None      # prompt + output, kept once the program goes

    def produced(self):
        return len(self.req.tokens) - len(self.prompt)


class Server:
    """The predictor and the clients' loop: one object, built in set-up
    and handed to the window."""

    def __init__(self, ctx):
        from jax.profiler import TraceAnnotation

        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.serving import RequestState, SamplingParams

        self.span = TraceAnnotation
        self.State, self.Sampling = RequestState, SamplingParams
        config, cell = ctx["config"], ctx["cell"]
        self.cfg = gpt_config(config)
        params = reference.weights(config, ctx["seed"], self.cfg.jdtype())
        conf = Config().enable_generation(self.cfg, params, **cell["engine"])
        if not ctx["rehearse"]:
            conf.enable_tpu()
        self.pred = create_predictor(conf)
        self.traffic = ctx["traffic"]
        self.feed = traffic_gen.serving_requests(
            self.traffic, reference.Sizes(config).V, ctx["seed"])
        self.clients = [None] * self.traffic["clients"]
        self.done, self.failed = [], []
        self.sending, self.in_window = True, False
        self.reset_counts()

    def reset_counts(self):
        """What the runner counts around ``step()``: steps, rows that held
        a request, each step's seconds, cached positions of live rows."""
        self.steps, self.rows, self.step_s, self.context_rows = 0, 0, [], 0

    def counters(self):
        m = self.pred.engine.metrics
        return {"prefill": m.prefill_tokens.value,
                "generated": m.tokens_generated.value,
                "prefix_hits": m.prefix_cache_hits.value,
                "prefix_hit_tokens": m.prefix_hit_tokens.value,
                "preempted": m.requests_preempted.value}

    def turn(self):
        """One turn of the loop: every idle client sends (while sending is
        on), then one step; returns the time the step returned."""
        with self.span(SPANS[2]):
            for i, s in enumerate(self.clients):
                if s is not None and s.req.state in (self.State.RUNNING,
                                                     self.State.QUEUED):
                    continue
                if s is not None:
                    (self.done if s.req.state == self.State.FINISHED
                     else self.failed).append(s)
                    self.clients[i] = None
                if self.sending:
                    prompt, want, prefix = next(self.feed)
                    t_add = time.perf_counter()
                    with self.span(SPANS[0]):
                        req = self.pred.add_request(
                            prompt, self.Sampling(max_new_tokens=want))
                    self.clients[i] = Served(req, prompt, want, prefix,
                                             t_add, self.in_window)
        live = [s for s in self.clients if s is not None]
        t0 = time.perf_counter()
        with self.span(SPANS[1]):
            self.pred.step()
        t1 = time.perf_counter()
        self.steps += 1
        self.rows += len(live)
        self.step_s.append(t1 - t0)
        for s in live:
            s.times.extend([t1] * (s.produced() - len(s.times)))
            self.context_rows += len(s.req.tokens)
        return t1

    def warm(self):
        """Until every client has finished one request."""
        finished = set()
        while len(finished) < len(self.clients):
            before = {id(s): i for i, s in enumerate(self.clients)
                      if s is not None}
            self.turn()
            for s in self.done:
                if id(s) in before:
                    finished.add(before[id(s)])
            if self.steps > 100000:
                raise SystemExit("benchmark: warm-up does not end")
        self.done.clear()

    def free(self):
        self.pred = None
        self.clients = []


def gpt_config(config):
    from paddle_tpu.models.gpt import GPTConfig

    s = reference.Sizes(config)
    return GPTConfig(vocab_size=s.Vp, max_seq_len=s.P, hidden=s.D,
                     num_layers=s.L, num_heads=s.H, ffn_hidden=s.F,
                     dropout=0.0, dtype=config["dtype"],
                     tie_embeddings=True)


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def sample(finished, n, seed):
    """``n`` of the finished requests, drawn from the seed, with the
    longest and (where there is one) one that started from a cached prefix
    among them."""
    if not finished:
        return []
    rng = traffic_gen.rng_of(seed, stream=1)
    longest = max(finished, key=lambda s: len(s.req.tokens))
    picked = [longest]
    shared = [s for s in finished if s.prefix >= 0 and s is not longest]
    if shared:
        picked.append(shared[rng.integers(len(shared))])
    rest = [s for s in finished if all(s is not p for p in picked)]
    for i in rng.permutation(len(rest))[: max(0, n - len(picked))]:
        picked.append(rest[i])
    return picked


def score(model, params, served, numerics_model=None):
    """Over the sampled requests: the gap, in the reference's logits, of
    the token that was served (or, for a control, of the token that
    ``numerics_model`` puts first) below the reference's best, at every
    position the program decoded.  Returns the gaps' mean, the widest, the
    share of positions where the two tokens differ, and their number."""
    every = []
    for s in served:
        toks = np.asarray(s.tokens, np.int32)
        n_prompt = len(s.prompt)
        padded = -(-len(toks) // PAD_TO) * PAD_TO
        row = np.zeros((1, padded), np.int32)
        row[0, : len(toks)] = toks
        logits = model.forward_logits(params, row)[0]
        if numerics_model is None:
            chosen = np.roll(row[0], -1)        # the token that was served
        else:
            chosen = reference.first_token(
                numerics_model.forward_logits(params, row)[0])
        gaps = np.asarray(reference.gap_below_best(logits, chosen))
        every.append(gaps[n_prompt - 1: len(toks) - 1])
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

    traffic, cell, config = ctx["traffic"], ctx["cell"], ctx["config"]
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
                   "prefill_tokens": delta["prefill"],
                   "generated_tokens": delta["generated"],
                   "prefix_hit_tokens": delta["prefix_hit_tokens"],
                   "mean_context": context},
        "notes": {"compiles_in_window": compiles, "setup_s": setup_s,
                  "reference_s": reference_s, "steps": steps,
                  "requests_sent": len(sent),
                  "requests_finished": len(finished),
                  "steps_after_close": waited, "counters": delta,
                  "not_compared": {"other_token": scored["other_token"]},
                  "ttft_p50_ms": ms(ttft, 50), "ttft_p95_ms": ms(ttft, 95),
                  "itl_p50_ms": ms(gaps, 50),
                  "cache_hits": len(log.hits),
                  "cache_misses": len(log.misses)},
    }


def mean_context(served):
    """Mean number of cached positions a processed token attended over,
    taken as half the final length of its request (a token at position i
    attends over i)."""
    total = sum(len(s.req.tokens) ** 2 / 2 for s in served)
    n = sum(len(s.req.tokens) for s in served)
    return total / n if n else 0.0


def readings(ctx, seeds, control, control_seeds, fault_seeds):
    """For ``calibrate.py``: per seed a short window at the cell's own load
    and the program's widest gap; for the seeds asked the control's (the
    token the reference puts first in ``control`` arithmetic, at the same
    positions of the same requests)."""
    config = ctx["config"]
    for seed in seeds:
        c = dict(ctx, seed=seed, trace_dir=None, t_start=time.perf_counter())
        c["cell"] = dict(ctx["cell"],
                         limits=dict.fromkeys(COMPARED, float("inf")))
        result = run(c)
        yield {"kind": "program", "seed": seed,
               "numbers": dict(result["notes"]["not_compared"],
                               **{n: v for n, (v, _, _)
                                  in result["compared"].items()}),
               "at": {n: d for n, (_, _, d)
                      in result["compared"].items()},
               "end_to_end": result["end_to_end"], "notes": result["notes"]}
        if seed not in control_seeds:
            continue
        model = reference.Model(config, "float32")
        params = reference.weights(config, seed,
                                   gpt_config(config).jdtype())
        for numerics in (control, "bfloat16"):
            scored = score(model, params, result["sample"],
                           reference.Model(config, numerics))
            yield {"kind": "control_" + numerics, "seed": seed,
                   "numbers": scored, "at": {}}

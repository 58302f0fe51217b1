"""Runner ``serve_mla_sparse``: the closed loop of runner ``serve`` around a
model of latent attention in every layer — full layers reading the
positions a learned indexer chooses token by token, sliding layers their
window — with sigmoid-routed experts and a shared one (latent pages and
index keys for the full layers, latent window pages for the sliding ones,
an expert layer that holds a share of the experts), served through the
same ``create_predictor(...).add_request / step``.

From the accepted runners it imports, and does not restate: the clients'
loop, the warm-up, the sampling of finished requests and the percentiles
(``serve``: ``warm``, ``sample``, ``percentile``, ``mean_context``), the
pinned stream (``serve_hybrid``: ``pinned_requests``), the loop with window
pools, the scoring and what a run keeps of the program's logits
(``serve_moe_window``: ``Server.turn``, ``score``, ``Server.logits_of`` and
``free``), and the window span's name.  What differs is what is built — the
program's ``MLASparseConfig`` from the configuration file, weights from
``reference_mla_sparse.make_weights`` — and the counters read.  The per-row
hook that keeps a request's first and last logits rows
(``Engine._sample_token``) is the one of ``serve_moe_window``, restated:
there it is made inside that runner's constructor, which builds its own
model.

What is compared is ``serve_moe_window``'s: the mean gap of a served
token's logit below the reference's best over the positions the program
decoded, and the median over the sampled requests of the logits rows'
error at the prompt's last position (``prefill_logit_err``: chunked
prefill through the latent pages, the index keys and the window pages) and
at the last decoded one (``decode_logit_err``: at the request's longest
context, every window page before it long given back); the widest gap and
the worst row are in the run's ``notes``.

``--rehearse``: ``run.py`` swaps in the dense tiny configuration, which
this model cannot use; the cell file's ``rehearse`` block names the tiny
configuration of this family (``"config"``) and this runner loads it.

Set-up ends when ``WARM_FINISHED`` clients have each finished a request,
not every client as in the accepted runners.  At this cell's steps (a chunk
step some 0.26-0.30 s) a whole turnover is some 2,000 steps, six minutes of
set-up a run, and the window after it (entry 49: steps 2,025 on) finishes
no request at all, so nothing would be compared.  After three the ramp is
past (the 16 first prompts prefilled side by side, some 650 steps), rows
decode at staggered lengths and new prompts are prefilled one at a time
beside 15 decoding rows, as in the rest of the cycle; at entry 49 the
window (steps 952 on) is 123 such chunk steps in a row, as the accepted
rule's is 137, and three requests finish in its first 71 steps.  The
schedule is in steps and the same for every seed (``pinned_requests``).

Planted faults, for ``calibrate.py``: the reference with part of the
mathematics changed (``reference_mla_sparse.Model(fault=)``) — the
indexer's selection left out (``"no_selection"``), the newest
``index_topk`` positions in place of the indexer's choice (``"newest"``),
the latents' rescale left out (``"no_rescale"``) — in the program's place
at the sound run's requests and positions, as the control (the reference
in float8) is.  The program has no fault switch.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import reference_mla_sparse as reference
from benchmark import run as bench

_serve = bench.load_module("runners", "serve")
_hybrid = bench.load_module("runners", "serve_hybrid")
_moe = bench.load_module("runners", "serve_moe_window")
percentile, sample, mean_context = (_serve.percentile, _serve.sample,
                                    _serve.mean_context)
pinned_requests, score = _hybrid.pinned_requests, _moe.score
WINDOW_SPAN, COMPARED = _serve.WINDOW_SPAN, _moe.COMPARED
# The host spans a traced run labels the device's idle gaps with: none, as
# runner ``serve_short_conv`` (``trace.reduce`` labels each gap against
# every span); the loop still emits ``serve.SPANS``.
SPANS = ()
FAULTS = reference.FAULTS
# clients that have finished a request when set-up ends (module docstring)
WARM_FINISHED = 3
# the reference pads a request to a multiple of this many positions: the
# cell's compared requests (to 32,768 tokens) are one shape, so its programs
# compile once a run (some minutes on the chip) and not once a length
REFERENCE_BLOCK = 32768


def reference_model(config, **kw):
    """``reference_mla_sparse.Model`` padding to ``REFERENCE_BLOCK``."""
    block = min(REFERENCE_BLOCK, reference.Sizes(config).max_len)
    return reference.Model(config, block=block, **kw)


class _Logits:
    """A reference model whose logits of a request are computed once, so
    that every control and fault is scored against the same rows."""

    def __init__(self, model):
        self.model, self.rows = model, {}

    def forward_logits(self, params, tokens, n_prompt):
        key = (np.asarray(tokens).tobytes(), n_prompt)
        if key not in self.rows:
            self.rows[key] = self.model.forward_logits(params, tokens,
                                                       n_prompt)
        return self.rows[key]


def model_config(ctx):
    """The configuration the run uses: the cell's, or under ``--rehearse``
    the tiny one of this family that its file names."""
    if ctx["rehearse"]:
        return bench.load_json(bench.HERE, "configs",
                               ctx["cell"]["config"] + ".json")
    return ctx["config"]


def mla_sparse_config(config):
    from paddle_tpu.models.mla_sparse import MLA, MLASparseConfig

    s = reference.Sizes(config)
    if s.routed_scale != 1 or not s.norm_topk:
        raise ValueError("the program renormalises the chosen weights and "
                         "scales them by 1: routed_scaling_factor 1 and "
                         "norm_topk_prob true")
    mla = {k: MLA(m["H"], m["Rq"], m["Rkv"], m["dn"], m["dr"], m["dv"],
                  m["theta"]) for k, m in s.mla.items()}
    return MLASparseConfig(
        vocab_size=s.Vp, max_seq_len=s.max_len, hidden=s.D,
        layer_types=s.kinds, num_dense_layers=s.count(reference.DENSE),
        full=mla[reference.FULL], sliding=mla[reference.SLIDING],
        window=s.window, index_heads=s.Hi, index_dim=s.di,
        index_topk=s.topk,
        lora_rescale=s.rescale,
        dense_ffn=s.F, expert_ffn=s.Fe, num_experts=s.E,
        experts_held=(s.first, s.held), top_k=s.top_k,
        routed_scale=s.routed_scale, rms_eps=s.eps, dtype=config["dtype"])


class Server(_moe.Server):
    """The accepted runner's clients' loop with window pools, around the
    latent-attention model."""

    def __init__(self, ctx):
        import jax
        from jax.profiler import TraceAnnotation

        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.serving import RequestState, SamplingParams
        from paddle_tpu.serving.model import MLASparseServed

        self.span = TraceAnnotation
        self.State, self.Sampling = RequestState, SamplingParams
        config = model_config(ctx)
        self.cfg = mla_sparse_config(config)
        self.sizes = reference.Sizes(config)
        params = reference.weights(config, ctx["seed"], self.cfg.jdtype())
        conf = Config().enable_generation(MLASparseServed(self.cfg), params,
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
        # serve_moe_window's hook: the logits of a request's first and
        # last sampled token, by request id and the position they were
        # computed at, a slice started on the device and read afterwards
        self.kept = {}
        take_row = jax.jit(lambda logits, i: logits[i])
        eng = self.pred.engine
        sound = eng._sample_token

        def keep(token, req):
            first = len(req.tokens) == len(req.prompt)
            last = len(req.output) + 1 >= req.sampling.max_new_tokens
            if first or last:
                row = take_row(eng.step_logits,
                               np.int32(eng._slots.index(req)))
                self.kept.setdefault(req.id, {})[len(req.tokens) - 1] = row
            return sound(token, req)

        eng._sample_token = keep

    def warm(self):
        """Until ``WARM_FINISHED`` clients have each finished a request."""
        finished = set()
        while len(finished) < WARM_FINISHED:
            before = {id(s): i for i, s in enumerate(self.clients)
                      if s is not None}
            self.turn()
            finished.update(before[id(s)] for s in self.done
                            if id(s) in before)
            if self.steps > 100000:
                raise SystemExit("benchmark: warm-up does not end")
        self.done.clear()

    def counters(self):
        m = self.pred.engine.metrics
        return dict(super().counters(),
                    latent_rows_selected=m.latent_rows_selected.value,
                    latent_rows_window=m.latent_rows_window.value,
                    latent_row_pages=m.latent_row_pages.value,
                    expert_tile_rows=m.expert_tile_rows.value)


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
    # rows of requests the warm-up finished go; a request the window will
    # finish keeps the row of its first token from before the window
    live = {s.req.id for s in server.clients if s is not None}
    server.kept = {i: r for i, r in server.kept.items() if i in live}
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
    context_rows, window_rows = server.context_rows, server.window_rows
    window_pages, full_pages = server.window_pages, server.full_pages
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
    gaps, tokens, firsts = [], 0, 0
    for s in everything + failed:
        tokens += sum(t0 < x <= t_close for x in s.times)
        firsts += bool(s.times) and t0 < s.times[0] <= t_close
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
    kept = [server.logits_of(s) for s in picked]
    for s in picked:
        s.tokens, s.req = list(s.req.tokens), None
    dtype = server.cfg.jdtype()
    server.free()
    del server
    gc.collect()
    t_ref = time.perf_counter()
    model = reference_model(config)
    scored = score(model, reference.weights(config, ctx["seed"], dtype),
                   picked, kept)
    reference_s = time.perf_counter() - t_ref

    detail = (f"{scored['tokens']} tokens and {scored['rows']} rows of "
              f"logits of {len(picked)} requests")
    compared = {n: (scored[n], cell["limits"][n], detail) for n in COMPARED}
    delta = {k: after[k] - before[k] for k in after}
    correct = (all(v <= lim for v, lim, _ in compared.values())
               and not failed and not never and compiles == 0
               and scored["tokens"] > 0 and scored["rows"] > 0
               and delta["preempted"] == 0)
    ms = lambda values, q: 1e3 * percentile(values, q) if values else None
    return {
        "correct": correct, "attempted": len(sent),
        "failed": len(failed) + len(never), "compared": compared,
        "memory_peak_bytes": peak_bytes, "sample": picked,
        "sample_logits": kept,
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
                   "first_tokens": firsts,
                   "prefix_hit_tokens": delta["prefix_hit_tokens"],
                   "context_positions": delta["context_positions"],
                   "expert_pairs": delta["expert_pairs"],
                   "experts_read": delta["experts_read"],
                   "expert_tile_rows": delta["expert_tile_rows"],
                   "selected_positions": delta["selected_positions"],
                   "latent_rows_selected": delta["latent_rows_selected"],
                   "latent_rows_window": delta["latent_rows_window"],
                   "latent_row_pages": delta["latent_row_pages"],
                   "window_rows": window_rows,
                   "window_pages_held": window_pages,
                   "full_pages_held": full_pages,
                   "window_pages_released":
                       delta["window_pages_released"],
                   "mean_context": context},
        "notes": {"compiles_in_window": compiles, "setup_s": setup_s,
                  "reference_s": reference_s, "steps": steps,
                  "requests_sent": len(sent),
                  "requests_finished": len(finished),
                  "steps_after_close": waited, "counters": delta,
                  "not_compared": {"logit_gap": scored["logit_gap"],
                                   "other_token": scored["other_token"],
                                   "worst_row_err": scored["worst_row_err"]},
                  "ttft_p50_ms": ms(ttft, 50), "ttft_p95_ms": ms(ttft, 95),
                  "itl_p50_ms": ms(gaps, 50), "itl_p90_ms": ms(gaps, 90),
                  "itl_p95_ms": ms(gaps, 95), "itl_p99_ms": ms(gaps, 99),
                  "step_p50_ms": ms(step_s, 50),
                  "step_p95_ms": ms(step_s, 95),
                  "cache_hits": len(log.hits),
                  "cache_misses": len(log.misses)},
    }


def readings(ctx, seeds, control, control_seeds, fault_seeds):
    """For ``calibrate.py``: per seed a window at the cell's own load and
    the program's numbers; for the seeds asked the control's (the reference
    in ``control`` arithmetic, and in bfloat16) and each planted fault's
    (the float32 reference with the fault), each in the program's place at
    the same positions of the same requests."""
    config = model_config(ctx)

    def window(seed):
        c = dict(ctx, seed=seed, trace_dir=None,
                 t_start=time.perf_counter())
        c["cell"] = dict(ctx["cell"],
                         limits=dict.fromkeys(COMPARED, float("inf")))
        result = run(c)
        line = {"kind": "program", "seed": seed,
                "numbers": dict(result["notes"]["not_compared"],
                                **{n: v for n, (v, _, _)
                                   in result["compared"].items()}),
                "at": {n: d for n, (_, _, d)
                       in result["compared"].items()},
                "end_to_end": result["end_to_end"],
                "memory_peak_bytes": result["memory_peak_bytes"],
                "notes": result["notes"]}
        return line, result

    def others(seed, served, kept, theirs):
        # in a scope of its own, run to its end before the next window:
        # the weights go before that window builds its own (two sets do not
        # fit the chip); a line is printed as soon as it is read
        model = _Logits(reference_model(config))
        params = reference.weights(config, seed,
                                   mla_sparse_config(config).jdtype())
        for kind, numerics in theirs:
            yield {"kind": kind, "seed": seed, "at": {},
                   "numbers": score(model, params, served, kept,
                                    reference_model(config, **numerics))}

    for seed in seeds:
        line, result = window(seed)
        yield line
        theirs = []
        if seed in control_seeds:
            theirs += [("control_" + n, {"numerics": n})
                       for n in (control, "bfloat16")]
        if seed in fault_seeds:
            theirs += [("fault_" + f, {"fault": f}) for f in FAULTS]
        if theirs:
            yield from others(seed, result["sample"],
                              result["sample_logits"], theirs)

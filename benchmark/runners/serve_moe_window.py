"""Runner ``serve_moe_window``: the closed loop of runner ``serve`` around a
sparse-expert model with sliding-window layers beside full-attention layers
(two groups of page pools, an expert layer that holds a share of the
experts), served through the same ``create_predictor(...).add_request /
step``.

From the accepted runners it imports, and does not restate: the clients'
loop, the warm-up, the sampling of finished requests and the percentiles
(``serve``: ``Server.turn``, ``warm``, ``sample``, ``percentile``,
``mean_context``), the pinned stream (``serve_hybrid``:
``pinned_requests``) and the spans' names.  What differs is what is built —
the program's ``MoEWindowConfig`` from the configuration file, weights from
``reference_moe_window.make_weights`` — and what is compared: the mean gap
of ``serve_hybrid.score`` (by how much a served token's logit lies below
the reference's best, over the positions the program decoded; the widest
gap is reported in the run's ``notes`` and is no limit here: a maximum over
8,000 tokens read 0.06 to 0.23 over eleven sound runs and 0.45 in float8,
so no limit has room on both sides), and the **logits themselves** at two
positions of every sampled request: the prompt's last
(what chunked prefill through both page tables produced) and the last
decoded one (decode through the cache at the request's longest context,
every window page before it long given back).  The program's rows are read
where the engine offers them (``Engine._sample_token``, the per-row hook,
with ``Engine.step_logits`` on the device): a slice is started there and
read after the window, so the window waits for nothing.  A row's error is
the root mean square of its difference from the reference's row over the
deviation of the reference's row, and the number compared is the median
over the rows: a router's near-tie that rounding decides the other way
moves one row by several per cent (the tenth expert of a token changes), a
fault moves every row.

``--rehearse``: ``run.py`` swaps in the dense tiny configuration, which
this model cannot use; the cell file's ``rehearse`` block names the tiny
configuration of this family (``"config"``) and this runner loads it.

Planted faults (``ctx["fault"]``, for ``calibrate.py`` and the tests): the
program itself run with part of the mathematics changed — ``"window_511"``
(the sliding layers read 511 positions, one short) and ``"drop_pair"``
(each token's tenth choice is left out: what a capacity would do to it) —
and the control (the reference in float8 in the program's place).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import reference_moe_window as reference
from benchmark import run as bench

_serve = bench.load_module("runners", "serve")
_hybrid = bench.load_module("runners", "serve_hybrid")
percentile, sample, mean_context = (_serve.percentile, _serve.sample,
                                    _serve.mean_context)
pinned_requests = _hybrid.pinned_requests
WINDOW_SPAN, SPANS = _serve.WINDOW_SPAN, _serve.SPANS
COMPARED = ("logit_gap_mean", "prefill_logit_err", "decode_logit_err")
FAULTS = ("window_511", "drop_pair")


def model_config(ctx):
    """The configuration the run uses: the cell's, or under ``--rehearse``
    the tiny one of this family that its file names."""
    if ctx["rehearse"]:
        return bench.load_json(bench.HERE, "configs",
                               ctx["cell"]["config"] + ".json")
    return ctx["config"]


def moe_window_config(config, fault=None):
    from paddle_tpu.models.moe_window import MoEWindowConfig

    s = reference.Sizes(config)
    full, sliding = s.rope[reference.FULL], s.rope[reference.SLIDING]
    return MoEWindowConfig(
        vocab_size=s.Vp, max_seq_len=s.max_len, hidden=s.D,
        layer_types=s.kinds, mlp_types=s.mlps, heads_per_layer=s.heads,
        num_kv_heads=s.Hkv, head_dim=s.hd,
        window=s.window - (fault == "window_511"), dense_ffn=s.F,
        expert_ffn=s.Fe, shared_ffn=s.Fs, num_experts=s.E,
        experts_held=(s.first, s.held), top_k=s.top_k,
        routed_scale=s.routed_scale, norm_topk=s.norm_topk,
        full_theta=full["rope_theta"],
        full_rotary=full["partial_rotary_factor"],
        yarn_factor=full["factor"],
        yarn_original_max=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        yarn_attention_factor=full["attention_factor"],
        sliding_theta=sliding["rope_theta"],
        sliding_rotary=sliding["partial_rotary_factor"], rms_eps=s.eps,
        drop_last_choice=fault == "drop_pair", dtype=config["dtype"])


class Server(_serve.Server):
    """The accepted runner's clients' loop around the sparse-expert model
    with window layers."""

    def __init__(self, ctx):
        import jax
        from jax.profiler import TraceAnnotation

        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.serving import RequestState, SamplingParams
        from paddle_tpu.serving.model import MoEWindowServed

        self.span = TraceAnnotation
        self.State, self.Sampling = RequestState, SamplingParams
        config, fault = model_config(ctx), ctx.get("fault")
        if fault not in (None,) + FAULTS:
            raise ValueError(f"fault {fault!r} is none of {FAULTS}")
        self.cfg = moe_window_config(config, fault)
        self.sizes = reference.Sizes(config)
        params = reference.weights(config, ctx["seed"], self.cfg.jdtype())
        conf = Config().enable_generation(MoEWindowServed(self.cfg), params,
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
        # the logits of a request's first and last sampled token, by
        # request id and the position they were computed at; a slice of
        # the step's logits is started on the device and read afterwards
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

    def reset_counts(self):
        super().reset_counts()
        # over the steps' live rows: positions a window layer had to read
        # at least, and the pages the two pools held
        self.window_rows = self.window_pages = self.full_pages = 0

    def turn(self):
        t = super().turn()
        m = self.pred.engine.metrics
        self.window_pages += m.pages_in_use_window.value
        self.full_pages += m.pages_in_use_full.value
        for s in self.clients:
            if s is not None:
                self.window_rows += min(len(s.req.tokens),
                                        self.sizes.window)
        return t

    def counters(self):
        m = self.pred.engine.metrics
        return dict(super().counters(),
                    prefill_chunks=m.prefill_chunks.value,
                    context_positions=m.attention_context.value,
                    selected_positions=m.attention_selected.value,
                    expert_pairs=m.expert_pairs.value,
                    experts_read=m.expert_weight_reads.value,
                    window_pages_released=m.window_pages_released.value)

    def free(self):
        # the hook holds the engine and the engine the hook: without this
        # the program's 12 GB outlive `del server` until a collection, and
        # the reference's weights found 0.9 GB free (my chip run, PR 35)
        self.pred.engine._sample_token = None
        self.kept.clear()
        super().free()

    def logits_of(self, served):
        """{position: float32 row} the program computed for ``served``."""
        return {pos: np.asarray(row, np.float32)
                for pos, row in self.kept.get(served.req.id, {}).items()}


def score(model, params, served, kept, numerics_model=None):
    """Over the sampled requests, against ``model``'s float32 logits: the
    two gaps of the accepted cells (of the token that was served or, for a
    control, of the token ``numerics_model`` puts first, below the
    reference's best, at every position the program decoded; the mean is
    compared, the widest reported), and the
    median error of the logits themselves, a row's root-mean-square
    difference over the deviation of the reference's row, at the prompt's
    last position (``prefill_logit_err``) and past it
    (``decode_logit_err``):
    the program's kept rows (``kept``, per request ``{position: row}``),
    or for a control ``numerics_model``'s at the same positions."""
    every, errs = [], {"prefill": [], "decode": []}
    for s, rows in zip(served, kept, strict=True):
        toks = np.asarray(s.tokens, np.int32)
        n_prompt = len(s.prompt)
        logits = model.forward_logits(params, toks, n_prompt)[:-1]
        theirs = None if numerics_model is None else \
            numerics_model.forward_logits(params, toks, n_prompt)[:-1]
        chosen = toks[n_prompt:] if theirs is None \
            else reference.first_token(theirs)
        every.append(np.asarray(reference.gap_below_best(
            logits, np.asarray(chosen, np.int32))))
        for pos, row in rows.items():
            at = pos - (n_prompt - 1)
            ref = np.asarray(logits[at])
            got = row if theirs is None else np.asarray(theirs[at])
            errs["prefill" if at == 0 else "decode"].append(
                float(np.sqrt(np.mean((got - ref) ** 2)) / ref.std()))
    gaps = np.concatenate(every) if every else np.zeros(0)
    rows = sum(len(v) for v in errs.values())
    middle = lambda v: float(np.median(v)) if v else 0.0
    if not gaps.size:
        return dict.fromkeys(COMPARED, 0.0) | {
            "logit_gap": 0.0, "other_token": 0.0, "worst_row_err": 0.0,
            "tokens": 0, "rows": rows}
    return {"logit_gap_mean": float(gaps.mean()),
            "logit_gap": float(gaps.max()),
            "prefill_logit_err": middle(errs["prefill"]),
            "decode_logit_err": middle(errs["decode"]),
            "worst_row_err": max(errs["prefill"] + errs["decode"] + [0.0]),
            "other_token": float((gaps > 0).mean()), "tokens": gaps.size,
            "rows": rows}


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
    # finish keeps the row of its first token from before the window (a
    # median over the 7 of 16 sampled requests that began inside the
    # window read 0.0147 once where 16 rows read 0.0069: my chip runs)
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
    model = reference.Model(config, "float32")
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
                   "window_rows": window_rows,
                   "window_pages_held": window_pages,
                   "full_pages_held": full_pages,
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
                   "selected_positions": delta["selected_positions"],
                   "expert_pairs": delta["expert_pairs"],
                   "experts_read": delta["experts_read"],
                   "window_pages_released": delta["window_pages_released"],
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
    in ``control`` arithmetic, and in bfloat16, in the program's place at
    the same positions of the same requests) and each planted fault's (the
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

    def controls(seed, served, kept):
        # in a scope of its own: the weights go before the next window
        # builds its own (two sets do not fit the chip)
        model = reference.Model(config, "float32")
        params = reference.weights(config, seed,
                                   moe_window_config(config).jdtype())
        return [{"kind": "control_" + numerics, "seed": seed, "at": {},
                 "numbers": score(model, params, served, kept,
                                  reference.Model(config, numerics))}
                for numerics in (control, "bfloat16")]

    for seed in seeds:
        line, result = window(seed)
        yield line
        if seed in control_seeds:
            yield from controls(seed, result["sample"],
                                result["sample_logits"])
        if seed in fault_seeds:
            for fault in FAULTS:
                yield window(seed, fault)[0]

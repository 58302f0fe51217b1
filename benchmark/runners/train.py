"""Runner ``train``: ``HybridEngine.step`` in a timed loop.

Set-up builds ONE object — the engine with its compiled step and its state —
from weights the benchmark makes from the seed, drives it through its first
three steps on fresh rows (which compiles the step and is what the reference
follows), and hands the same object to the window.  The window calls the
same ``step`` on a fresh batch each time and waits for every loss, as a
trainer that logs it does.  After the window the program's state is freed
and the plain reference follows the first steps from the same seed.

The cell's file gives ``engine`` (what ``EngineConfig`` takes, and
``remat``), ``devices`` and ``limits``; the traffic file gives ``batch``,
``seq`` and how ids are drawn.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark import reference, traffic_gen

WINDOW_SPAN = "bench/window"
SPANS = ("bench/feed", "bench/step", "bench/wait")
FOLLOWED_STEPS = 2      # updates the reference follows; it scores step 3
# loss1 is read and printed, and not compared: see PERF.md (no control and
# no fault reads three times what sound runs do)
COMPARED = ("loss2", "loss3", "grad1_norm", "grad1_sample", "change_norm")


def gpt_config(config, cell):
    """The program's model configuration from the configuration file."""
    from paddle_tpu.models.gpt import GPTConfig

    s = reference.Sizes(config)
    return GPTConfig(vocab_size=s.Vp, max_seq_len=s.P, hidden=s.D,
                     num_layers=s.L, num_heads=s.H, ffn_hidden=s.F,
                     dropout=0.0, dtype=config["dtype"], use_flash=True,
                     remat=cell["engine"]["remat"], tie_embeddings=True)


def recipe_of(config, cell, ec):
    """What the reference needs to know of the optimizer: read from the
    engine's own settings, so a default the cell does not name is the
    program's default on both sides."""
    return {"lr": ec.lr, "beta1": ec.beta1, "beta2": ec.beta2,
            "eps": ec.eps, "weight_decay": ec.weight_decay,
            "grad_clip": ec.grad_clip, "param_dtype": config["dtype"],
            "opt_dtype": ec.opt_dtype}


class Trainer:
    """The engine, its compiled step and its state: one object, built in
    set-up and handed to the window."""

    def __init__(self, ctx):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from paddle_tpu.distributed.engine import EngineConfig, HybridEngine

        self.jax = jax
        config, cell = ctx["config"], ctx["cell"]
        engine = dict(cell["engine"])
        engine.pop("remat")
        self.ec = EngineConfig(**engine)
        self.cfg = gpt_config(config, cell)
        self.eng = HybridEngine(self.cfg, devices=ctx["devices"][:1],
                                engine_cfg=self.ec)
        self.recipe = recipe_of(config, cell, self.ec)
        self.config = config
        shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.eng.mesh, spec),
            self.eng.param_specs(),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        dtype = self.cfg.jdtype()
        self._make = jax.jit(
            lambda key: reference.make_weights(config, key, dtype),
            out_shardings=shardings)
        sizes = reference.Sizes(config)
        shapes = {n: shape for n, (shape, _, _)
                  in reference.leaf_table(sizes).items()}

        def moment_sq(slots):
            """The first moments, out of the engine's padded flat slots
            and back in the parameters' shapes: their squares by part and
            an evenly strided sample of each leaf."""
            flat = {n[:-2]: a.reshape(-1)[: math.prod(shapes[n[:-2]])]
                    .reshape(shapes[n[:-2]])
                    for n, a in reference.flatten(slots).items()
                    if n.endswith("/m")}
            return (reference.sq_parts(sizes, flat),
                    {n: reference.sample(a) for n, a in flat.items()})

        self._moment_sq = jax.jit(moment_sq)
        self._change_sq = jax.jit(lambda p, p0: reference.sq_parts(
            sizes, reference.flatten(p), reference.flatten(p0)))
        self.params = self.opt = None

    def load(self, seed):
        """Weights from the seed (one program, on the device, in the type
        they are trained in) and fresh optimizer state."""
        self.seed = seed
        self.params = self._make(reference.seed_key(seed))
        want = self.jax.eval_shape(self.eng.model.init,
                                   self.jax.random.key(0))
        got = self.jax.tree_util.tree_map(
            lambda a: (a.shape, str(a.dtype)), self.params)
        if got != self.jax.tree_util.tree_map(
                lambda a: (a.shape, str(a.dtype)), want):
            raise SystemExit("benchmark: the reference's parameter tree "
                             "is not the program's")
        # the engine has no public way to build its state around given
        # parameters; this is the second half of its own init()
        self.opt = self.eng._init_opt(self.params)

    def step(self, tokens, labels):
        """One optimizer step through the program's own entry; the loss is
        returned as the device gives it (wait on it to time the step)."""
        self.params, self.opt, loss = self.eng.step(
            self.params, self.opt, tokens, labels)
        return loss

    def first_steps(self, batches):
        """The first three steps, and the numbers the reference follows:
        each loss, the norm of every leaf of the first gradient as the
        optimizer got it (from the first moment after one step: it is
        ``(1 - beta1) * g``), and every leaf's change over the first
        ``FOLLOWED_STEPS`` updates."""
        out = {"losses": []}
        for k, (tokens, labels) in enumerate(batches):
            out["losses"].append(float(self.step(tokens, labels)))
            if k == 0:
                sq, sampled = self._moment_sq(self.opt["slots"])
                out["grad1_sample"] = {
                    n: np.asarray(v) / (1 - self.recipe["beta1"])
                    for n, v in sampled.items()}
                out["grad1"] = {
                    n: math.sqrt(float(v)) / (1 - self.recipe["beta1"])
                    for n, v in sq.items()}
            if k + 1 == FOLLOWED_STEPS:
                p0 = self._make(reference.seed_key(self.seed))
                sq = self._change_sq(self.params, p0)
                del p0
                out["change"] = {n: math.sqrt(float(v))
                                 for n, v in sq.items()}
        return out

    def free(self):
        self.params = self.opt = None


def compare(prog, ref, limits):
    """``(compared, read)``: name -> (number, limit, detail) for the
    numbers that decide ``correct``, and name -> number for those that are
    only read.  A compared number without a limit in the cell's file is an
    error, not a pass."""
    numbers = reference.training_numbers(prog, ref)
    missing = [n for n in COMPARED if n not in limits]
    if missing:
        raise SystemExit(f"benchmark: the cell's file sets no limit for "
                         f"{missing}")
    compared = {n: (numbers[n][0], limits[n], numbers[n][1])
                for n in COMPARED}
    return compared, {n: v for n, (v, _) in numbers.items()
                      if n not in COMPARED}


def run(ctx):
    import jax
    from jax.profiler import TraceAnnotation

    traffic, cell = ctx["traffic"], ctx["cell"]
    s = reference.Sizes(ctx["config"])
    feed = traffic_gen.training_batches(traffic, s.V, ctx["seed"])
    marks = {"start_to_runner_s": time.perf_counter() - ctx["t_start"]}
    t = time.perf_counter()
    trainer = Trainer(ctx)
    trainer.load(ctx["seed"])
    jax.block_until_ready(trainer.opt)
    marks["engine_and_state_s"] = time.perf_counter() - t
    t = time.perf_counter()
    first = [next(feed) for _ in range(FOLLOWED_STEPS + 1)]
    prog = trainer.first_steps(first)
    marks["first_steps_s"] = time.perf_counter() - t
    log = ctx["compile_log"]
    tokens_per_step = traffic["batch"] * traffic["seq"]

    if ctx["trace_dir"]:
        jax.profiler.start_trace(ctx["trace_dir"])
    compiles_before = log.count()
    losses = []
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_start"]
    t_end = t0
    with TraceAnnotation(WINDOW_SPAN):
        while t_end - t0 < ctx["seconds"]:
            with TraceAnnotation(SPANS[0]):
                tokens, labels = next(feed)
            with TraceAnnotation(SPANS[1]):
                loss = trainer.step(tokens, labels)
            with TraceAnnotation(SPANS[2]):
                losses.append(float(loss))
            t_end = time.perf_counter()
    compiles = log.count() - compiles_before
    if ctx["trace_dir"]:
        jax.profiler.stop_trace()
    elapsed = t_end - t0

    peak_bytes = None
    if not ctx["rehearse"]:
        peak_bytes = max(d.memory_stats()["peak_bytes_in_use"]
                         for d in ctx["devices"])
    recipe, config = trainer.recipe, trainer.config
    trainer.free()
    del trainer
    t_ref = time.perf_counter()
    ref = reference.follow_training(
        config, recipe, ctx["seed"], first,
        rows=traffic.get("reference_rows", 1), steps=FOLLOWED_STEPS)
    reference_s = time.perf_counter() - t_ref
    compared, read_only = compare(prog, ref, cell["limits"])

    failed = sum(not math.isfinite(x) for x in losses)
    correct = (all(v <= lim for v, lim, _ in compared.values())
               and failed == 0 and compiles == 0 and len(losses) > 0)
    return {
        "correct": correct, "attempted": len(losses), "failed": failed,
        "compared": compared, "memory_peak_bytes": peak_bytes,
        "end_to_end": {
            "train_tokens_per_s": len(losses) * tokens_per_step / elapsed,
            "setup_s": setup_s},
        "counts": {"steps": len(losses), "elapsed_s": elapsed,
                   "tokens_per_step": tokens_per_step,
                   "seq": traffic["seq"], "batch": traffic["batch"],
                   "micro_batches": int(cell["engine"].get("accum_steps",
                                                             1))},
        "notes": {"compiles_in_window": compiles, "setup_s": setup_s,
                  "setup_marks": marks, "not_compared": read_only,
                  "reference_s": reference_s, "steps": len(losses),
                  "first_losses": prog["losses"],
                  "last_loss": losses[-1] if losses else None,
                  "cache_hits": len(log.hits),
                  "cache_misses": len(log.misses)},
    }


def readings(ctx, seeds, control, control_seeds, fault_seeds):
    """For ``calibrate.py``: per seed the program's numbers against the
    float32 reference; for the seeds asked, the control's (the reference in
    ``control`` arithmetic) and the planted fault's (half of every batch
    left out, in bf16 arithmetic)."""
    traffic = ctx["traffic"]
    s = reference.Sizes(ctx["config"])
    trainer = Trainer(ctx)
    rows = traffic.get("reference_rows", 1)
    for seed in seeds:
        feed = traffic_gen.training_batches(traffic, s.V, seed)
        first = [next(feed) for _ in range(FOLLOWED_STEPS + 1)]
        t0 = time.perf_counter()
        trainer.load(seed)
        prog = trainer.first_steps(first)
        trainer.free()
        t1 = time.perf_counter()
        follow = lambda **kw: reference.follow_training(
            trainer.config, trainer.recipe, seed, first, rows=rows,
            steps=FOLLOWED_STEPS, **kw)
        ref = follow()
        t2 = time.perf_counter()
        def line(kind, got):
            numbers = reference.training_numbers(got, ref)
            return {"kind": kind, "seed": seed,
                    "numbers": {n: v for n, (v, _) in numbers.items()},
                    "at": {n: d for n, (_, d) in numbers.items()}}

        yield dict(line("program", prog), program_s=t1 - t0,
                   reference_s=t2 - t1,
                   grad1_global_norm=ref["grad1_global_norm"],
                   ref_grad1=ref["grad1"], ref_change=ref["change"])
        if seed in control_seeds:
            yield line("control_" + control, follow(numerics=control))
            yield line("control_bfloat16", follow(numerics="bfloat16"))
        if seed in fault_seeds:
            yield line("fault_half_batch",
                       follow(numerics="bfloat16", half_batch=True))
            yield line("fault_state_unchanged",
                       follow(numerics="bfloat16", frozen=True))

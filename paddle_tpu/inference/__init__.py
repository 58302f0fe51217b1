"""Inference API — Config / create_predictor (the AnalysisPredictor tail).

Reference parity: paddle/fluid/inference/api/analysis_predictor.cc
(AnalysisPredictor — load program+params, run the IR analysis pipeline,
execute), paddle_infer::Config (analysis_config.cc — device / precision /
optimization knobs), and the int8 path of
inference/api/mkldnn_quantizer.cc (calibration scales → quantized kernels).

TPU-native split of those jobs:
- the ~150-pass IR analysis pipeline IS XLA: the saved jax.export artifact
  (jit.save) is already an optimized, versioned program, so Config's
  ir_optim/memory_optim knobs are accepted no-ops (documented per knob);
- device/precision selection happens at predictor BUILD: the serialized
  program has baked dtypes, so precision overrides (bf16 / int8) rebuild
  the executable from the model Layer + weights — exactly the role of the
  reference's analysis passes rewriting the program;
- int8 uses the PTQ/QAT scales from contrib.quant: weights quantize
  per-output-channel to REAL int8 arrays, activations to int8 by the
  calibrated scale, and the matmul runs int8xint8→int32 on the MXU via
  lax.dot_general(preferred_element_type=int32) — not fake-quant.
"""
from __future__ import annotations

import json
import os

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..observability.compile_watchdog import watch

__all__ = ["Config", "PrecisionType", "create_predictor", "Predictor",
           "GenerationPredictor"]


class PrecisionType:
    Float32 = "float32"
    Bfloat16 = "bfloat16"
    Half = "bfloat16"          # fp16 requests map to bf16 (TPU native)
    Int8 = "int8"


class Config:
    """paddle_infer.Config parity."""

    def __init__(self, prog_file=None, params_file=None):
        # jit.save artifact prefix (…pdmodel/.pdiparams.npz live beside it)
        self.prog_file = prog_file
        self.params_file = params_file
        self.device = None        # None = jax's default backend
        self.precision = PrecisionType.Float32
        self.model_layer = None
        self.quant_scales = None
        self.generation = None
        self._ir_optim = True

    # ---- device selection (Config::EnableUseGpu analog) ----
    def enable_tpu(self):
        """Require a TPU: create_predictor raises when jax's default
        backend is anything else, instead of serving on the CPU.  (The
        CPU is selected outside the program, with JAX_PLATFORMS=cpu.)"""
        self.device = "tpu"
        return self

    # ---- precision ----
    def set_precision(self, precision):
        if precision not in (PrecisionType.Float32, PrecisionType.Bfloat16,
                             PrecisionType.Int8):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        return self

    def enable_int8(self, scales=None):
        """Int8 inference using PTQ/QAT calibration scales — a dict
        {layer_name: {"weight": s, "activation": s}} (contrib.quant
        quant_scales/PTQ.scales) or a path to a JSON of the same."""
        self.precision = PrecisionType.Int8
        if isinstance(scales, (str, os.PathLike)):
            with open(scales) as f:
                scales = json.load(f)
        self.quant_scales = scales
        return self

    # ---- autoregressive generation (serving engine) ----
    def enable_generation(self, model_config, params=None, *, page_size=16,
                          num_pages=256, max_batch_size=4, chunk_len=None,
                          prefix_cache=None, num_window_pages=None):
        """Switch create_predictor to a GenerationPredictor: a
        continuous-batching, paged-cache generation engine
        (paddle_tpu.serving) over the given model — a served-model
        object (``paddle_tpu.serving.model``) or a config it can wrap
        (``GPTConfig``, ``HybridConfig``).  params defaults to the
        model's own fresh weights; page_size/num_pages size the page
        pools, max_batch_size the in-flight batch.  chunk_len bounds the
        prompt tokens any request contributes to one unified step
        (chunked prefill — prompts of any admissible length are split
        into chunk_len-token rows scheduled next to decode rows).
        prefix_cache enables radix prefix reuse: a prompt sharing a
        cached prefix skips that prefill entirely, token-identically.  It
        defaults to
        on, except for a model with recurrent layers, which is served
        cold and for which ``True`` is refused.  num_window_pages sizes
        the pools of a model's sliding-window layers (default: what
        max_batch_size rows can hold at most)."""
        self.generation = {
            "config": model_config, "params": params,
            "knobs": {"page_size": page_size, "num_pages": num_pages,
                      "max_batch_size": max_batch_size,
                      "chunk_len": chunk_len, "prefix_cache": prefix_cache},
        }
        if num_window_pages is not None:
            self.generation["knobs"]["num_window_pages"] = num_window_pages
        return self

    # ---- model source for rebuild-precision paths ----
    def set_model(self, layer, params_path=None):
        """A Layer instance to rebuild the executable from (required for
        precision != as-saved; the serialized program has baked dtypes)."""
        self.model_layer = layer
        if params_path:
            self.prog_file = params_path
        return self

    # ---- accepted no-ops, each with the owning TPU mechanism ----
    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag      # XLA always optimizes; kept for parity
        return self

    def enable_memory_optim(self):
        return self                # XLA buffer assignment owns memory

    def set_cpu_math_library_num_threads(self, n):
        return self                # XLA threadpool owns CPU parallelism


class _Int8Linear:
    """Inference-only int8 Linear: per-output-channel int8 weights,
    activation quantized by the calibrated scale, int8×int8→int32 MXU
    matmul, fused dequant (+bias)."""

    def __init__(self, linear, act_scale):
        w = np.asarray(linear.weight.data, np.float32)      # [in, out]
        w_absmax = np.maximum(np.abs(w).max(axis=0), 1e-8)  # per out-chan
        self.w_scale = jnp.asarray(w_absmax / 127.0, jnp.float32)
        self.w_q = jnp.asarray(
            np.clip(np.round(w / (w_absmax / 127.0)), -127, 127), jnp.int8)
        self.a_scale = float(act_scale) / 127.0
        self.bias = (jnp.asarray(linear.bias.data, jnp.float32)
                     if linear.bias is not None else None)

    def __call__(self, x):
        xq = jnp.clip(jnp.round(x / self.a_scale), -127, 127).astype(
            jnp.int8)
        acc = jax.lax.dot_general(
            xq, self.w_q, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * (self.a_scale * self.w_scale)
        if self.bias is not None:
            out = out + self.bias
        return out


class Predictor:
    """create_predictor result: __call__/run on numpy/Tensor inputs.

    Native-precision path executes the serialized jax.export program
    (jit.Predictor); precision-override paths jit the model Layer with
    transformed weights.  Per-input-shape executables are cached by
    jax.jit — the batched-serving behavior of AnalysisPredictor's
    shape-bucketed engines.
    """

    def __init__(self, config: Config):
        self.config = config
        self._impl = None
        self._mode = None
        self._build()

    def _build(self):
        cfg = self.config
        if cfg.precision == PrecisionType.Float32 and cfg.model_layer is None:
            from ..jit import Predictor as _SavedPredictor

            self._impl = _SavedPredictor(cfg.prog_file)
            self._mode = "saved-program"
            return
        if cfg.model_layer is None:
            raise ValueError(
                f"precision={cfg.precision!r} rebuilds the executable and "
                "needs the model Layer: call config.set_model(layer) "
                "(the serialized program's dtypes are baked)")
        layer = cfg.model_layer
        if cfg.prog_file:
            from ..jit import load as jit_load

            jit_load(cfg.prog_file, layer=layer)   # restore weights
        if cfg.precision == PrecisionType.Int8:
            self._impl = self._build_int8(layer)
            self._mode = "int8"
        else:
            self._impl = self._build_cast(layer, cfg.precision)
            self._mode = cfg.precision

    # ---- precision rebuilds ------------------------------------------
    def _build_cast(self, layer, precision):
        dt = jnp.bfloat16 if precision == PrecisionType.Bfloat16 \
            else jnp.float32
        params, buffers = layer.raw_state()
        params = jax.tree_util.tree_map(lambda a: a.astype(dt), params)

        def pure(params, buffers, *inputs):
            with layer.swap_state(params, buffers):
                out = layer.forward(*[Tensor(x.astype(dt)) for x in inputs])
            return jax.tree_util.tree_map(
                lambda t: t.data if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))

        jfn = watch(jax.jit(pure),
                    name=f"inference::predictor[{precision}]")
        return lambda *arrs: jfn(params, buffers, *arrs)

    def _build_int8(self, layer):
        from ..nn.layer.common import Linear

        scales = self.config.quant_scales or {}
        quantized = {}
        # include_self: the model may itself BE a Linear (ADVICE r4) —
        # the root is keyed by its empty-prefix name, matching PTQ scales
        for name, sub in layer.named_sublayers(include_self=True):
            if isinstance(sub, Linear):
                entry = scales.get(name)
                act = (entry or {}).get("activation")
                if act is None:
                    raise ValueError(
                        f"int8 predictor: no activation scale for layer "
                        f"{name!r} — calibrate with contrib.quant.PTQ and "
                        f"pass its scales to enable_int8()")
                quantized[id(sub)] = _Int8Linear(sub, act)
        if not quantized:
            raise ValueError("int8 predictor: model has no Linear layers")

        import contextlib

        @contextlib.contextmanager
        def patched():
            """Dispatch quantized Linears to their int8 twins ONLY for the
            duration of a predictor call/trace — the user's model keeps
            its fp32 behavior outside."""
            subs = [s for _, s in layer.named_sublayers()
                    if id(s) in quantized]
            saved = [s.forward for s in subs]
            try:
                for s in subs:
                    q = quantized[id(s)]
                    # lint-ok: trace-purity intentional trace-time
                    # dispatch patch; restored in finally before the
                    # trace ends, so no state leaks across traces
                    s.forward = (lambda x, _q=q:
                                 Tensor(_q(x.data if isinstance(x, Tensor)
                                           else x)))
                yield
            finally:
                for s, f in zip(subs, saved):
                    # lint-ok: trace-purity restores the pre-patch
                    # forward (see the paired patch above)
                    s.forward = f

        # fp32 weights of quantized Linears would otherwise ride along as
        # jit operands (the int8 twin owns the real data): swap dummies in
        params, buffers = layer.raw_state()
        quantized_prefixes = tuple(
            name + "." for name, sub in layer.named_sublayers()
            if id(sub) in quantized)
        params = {k: (jnp.zeros((1,), jnp.float32)
                      if k.startswith(quantized_prefixes) else v)
                  for k, v in params.items()}

        def pure(params, buffers, *inputs):
            with patched(), layer.swap_state(params, buffers):
                out = layer.forward(*[Tensor(jnp.asarray(x, jnp.float32))
                                      for x in inputs])
            return jax.tree_util.tree_map(
                lambda t: t.data if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))

        jfn = watch(jax.jit(pure), name="inference::predictor[int8]")
        return lambda *arrs: jfn(params, buffers, *arrs)

    # ---- serving entry ------------------------------------------------
    def run(self, *inputs):
        arrs = tuple(np.asarray(a.data if isinstance(a, Tensor) else a)
                     for a in inputs)
        if self._mode == "saved-program":
            return self._impl(*arrs)
        out = self._impl(*arrs)
        return jax.tree_util.tree_map(Tensor, out)

    __call__ = run


class GenerationPredictor:
    """create_predictor result when Config.enable_generation was called:
    autoregressive serving over the continuous-batching engine.

    ``generate(prompts, sampling)`` is the batch entry (token-id lists in,
    generated token-id lists out); ``add_request``/``step`` expose the
    engine's incremental scheduler for streaming callers; ``metrics()``
    snapshots the serving counters/histograms (TTFT, queue wait,
    per-token decode time, page-pool occupancy)."""

    def __init__(self, config: Config):
        from ..serving import Engine

        gen = config.generation
        self.config = config
        self.engine = Engine(gen["config"], gen["params"], **gen["knobs"])

    def generate(self, prompts, sampling=None):
        return self.engine.generate(prompts, sampling)

    def add_request(self, prompt, sampling=None):
        return self.engine.add_request(prompt, sampling)

    def step(self):
        return self.engine.step()

    def metrics(self):
        return self.engine.metrics.snapshot()


def create_predictor(config: Config):
    """paddle_infer.create_predictor parity; generation-enabled configs
    build the serving-engine predictor instead."""
    if config.device is not None and jax.default_backend() != config.device:
        raise RuntimeError(
            f"Config asks for device {config.device!r} but jax's default "
            f"backend is {jax.default_backend()!r} "
            f"(devices: {jax.devices()})")
    if config.generation is not None:
        return GenerationPredictor(config)
    return Predictor(config)

"""A named kernel's share of its roofline, from the reduced trace.

``trace.short`` keeps an operation's instruction name and marks a Mosaic
custom call ``[mosaic]``.  XLA names such a call after the innermost jax
scope it was traced in, and a ``pallas_call`` with a ``name=`` is traced
inside a scope of that name: ``flash_fwd.16[mosaic]``,
``flash_bwd_dq.9[mosaic]``.  A program whose kernels have no name gives
``closed_call.3[mosaic]`` and the like, and nothing here matches.
"""
from __future__ import annotations

from benchmark import reference, roofline


def mosaic_seconds(ops, kernels):
    """Device seconds of the Mosaic calls whose name holds one of
    ``kernels``."""
    return sum(sec for name, sec in ops.items()
               if "[mosaic]" in name and any(k in name for k in kernels))


def causal_attention_share(run, kernels, part):
    """Per cent: the least time for the ``part`` (``"forward"`` or
    ``"backward"``) of the causal attention calls the traced steps require,
    one per layer and micro-batch, over the device time of ``kernels``.
    ``None`` without a trace or without such kernels in it."""
    tr, c = run.get("trace"), run["counts"]
    if not tr:
        return None
    spent = mosaic_seconds(tr["ops"], kernels)
    if spent <= 0:
        return None
    s = reference.Sizes(run["config"])
    micro = c["micro_batches"]
    ops, nbytes = roofline.causal_attention_call(
        c["batch"] // micro, s.H, c["seq"], s.hd)[part]
    least, _ = roofline.least_seconds(ops, nbytes, run["peak"])
    # steps the trace saw in full: those the window completed
    return 100.0 * c["steps"] * micro * s.L * least / spent

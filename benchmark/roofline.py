"""The table of peaks, and the operations and bytes that the mathematics of a
step or of a kernel call requires, computed from shapes.

A device kind that the table does not list is an error: there is no default
peak and no CPU peak.  Recomputed operations (activation checkpointing, the
flash kernels' second pass over the scores) are never counted: a share is of
what the model requires, so taking recomputation out raises it.
"""
from __future__ import annotations

# Published peaks of one chip (Google Cloud documentation, "TPU v5e": 197
# TFLOP/s bf16, 16 GB of HBM at 819 GB/s).  jax names the v5e "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: device kind {device_kind!r} is not in "
            f"benchmark/roofline.py's table of peaks {sorted(PEAKS)}: no "
            f"share of a peak can be reported for it") from None


def matmul_params(s):
    """Parameters that take part in a matrix product for every token: the
    blocks' four matrices and the (tied) output head.  The embedding lookup
    and the positions are reads, not products."""
    return s.L * (4 * s.D * s.D + 2 * s.D * s.F) + s.Vp * s.D


def train_flops_per_token(s, seq):
    """Forward and backward: 6 per matrix parameter, and causal attention's
    two products over half the square, 6 * layers * seq * D."""
    return 6 * matmul_params(s) + 6 * s.L * seq * s.D


def forward_flops_per_token(s, context):
    """One token of a forward pass that attends over ``context`` cached
    positions: 2 per matrix parameter, and 4 * layers * context * D."""
    return 2 * matmul_params(s) + 4 * s.L * context * s.D


def causal_attention_call(batch, heads, seq, head_size, itemsize=2):
    """(operations, bytes) that one causal attention over ``[batch, heads,
    seq, head_size]`` requires, forward and backward each.

    Forward: the scores and the weighted values over the lower triangle,
    2 products of seq^2 / 2 * head_size multiply-adds; q, k, v read and the
    output written.  Backward: dV, dP, dQ, dK — 4 such products; q, k, v,
    the output and its gradient read, three gradients written."""
    unit = 2 * batch * heads * (seq * seq // 2) * head_size
    array = batch * heads * seq * head_size * itemsize
    return {"forward": (2 * unit, 4 * array),
            "backward": (4 * unit, 8 * array)}


def least_seconds(ops, nbytes, peak):
    """The least time the chip could take, and which peak bounds it."""
    t_ops, t_bytes = ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return max(t_ops, t_bytes), ("operations" if t_ops >= t_bytes
                                 else "bytes")

"""``flash_attention_roofline``: the least time the chip could take for the
causal attention calls the traced steps require (forward and backward of
every layer and micro-batch, from shapes: ``roofline.causal_attention_call``)
over the device time the trace gives the flash kernels.  Under activation
recomputation the forward kernel runs twice a step and is required once, so
the share falls; that is what it is for."""
from benchmark import reference, roofline

# The trace names a Pallas call after the jax scope it was traced in
# ("closed_call.14", "checkpoint.19"), not after its kernel; what it does
# say is that the instruction is a Mosaic custom call, and the train step
# has no Mosaic kernel but the flash forward and its two backward kernels.
KERNELS = ("[mosaic]",)


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr:
        return None
    spent = sum(sec for name, sec in tr["ops"].items()
                if any(k in name for k in KERNELS))
    if spent <= 0:
        return None
    s = reference.Sizes(run["config"])
    micro = c["micro_batches"]
    call = roofline.causal_attention_call(c["batch"] // micro, s.H,
                                          c["seq"], s.hd)
    least = sum(roofline.least_seconds(ops, nbytes, run["peak"])[0]
                for ops, nbytes in call.values())
    # steps the trace saw in full: those the window completed
    calls = c["steps"] * micro * s.L
    return 100.0 * calls * least / spent

"""``flash_fwd_roofline``: the least time the chip could take for the
causal attention forward calls the traced steps require (one per layer and
micro-batch: 2 products, ``roofline.causal_attention_call``'s forward part)
over the device time the trace gives the Mosaic calls named ``flash_fwd``.
Under activation recomputation the forward kernel runs twice a step and is
required once, so the share falls.  The trace carries a kernel's name only
where the program gives its ``pallas_call`` one; without it nothing is
read."""
from benchmark import kernel_share


def read(run):
    return kernel_share.causal_attention_share(run, ("flash_fwd",),
                                               "forward")

"""``flash_bwd_roofline``: the least time the chip could take for the
causal attention backward calls the traced steps require (one per layer and
micro-batch: 4 products, ``roofline.causal_attention_call``'s backward
part) over the device time the trace gives the two Mosaic calls that share
them, ``flash_bwd_dkdv`` and ``flash_bwd_dq`` (each computes the scores
again, which is not counted).  Without the kernels' names in the trace
nothing is read."""
from benchmark import kernel_share


def read(run):
    return kernel_share.causal_attention_share(
        run, ("flash_bwd_dkdv", "flash_bwd_dq"), "backward")

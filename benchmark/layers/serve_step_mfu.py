"""``serve_step_mfu``: the whole serving step's share of the chip's peak —
the forward operations the processed tokens require (2 per matrix parameter,
and attention over the mean cached context) per second of the window, over
the peak."""
from benchmark import reference, roofline


def read(run):
    c = run["counts"]
    tokens = c["prefill_tokens"] + c["generated_tokens"]
    if tokens <= 0:
        return None
    s = reference.Sizes(run["config"])
    per_s = (roofline.forward_flops_per_token(s, c["mean_context"])
             * tokens / c["elapsed_s"])
    return 100.0 * per_s / (run["chips"] * run["peak"]["flops_per_s"])

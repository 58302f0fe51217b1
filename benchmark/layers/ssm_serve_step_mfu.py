"""``ssm_serve_step_mfu``: the whole serving step's share of the chip's peak
for a model of state-space mixers beside attention — the operations the
window's tokens require (``roofline_ssm.step_flops``: 2 per matrix
parameter of the blocks for every processed token, 2 per parameter of the
head only for the rows owed a token, dense attention over the counted
context positions, the scan's state products and chunk squares) per second
of the window, over the peak."""
from benchmark import reference_ssm, roofline_ssm


def read(run):
    c = run["counts"]
    if "state_rows_chunk" not in c:
        return None
    tokens, pairs = roofline_ssm.processed(c)
    if tokens <= 0:
        return None
    s = reference_ssm.Sizes(run["config"])
    per_s = roofline_ssm.step_flops(
        s, tokens, c["generated_tokens"], c["context_positions"],
        pairs) / c["elapsed_s"]
    return 100.0 * per_s / (run["chips"] * run["peak"]["flops_per_s"])

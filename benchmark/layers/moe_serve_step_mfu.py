"""``moe_serve_step_mfu``: the whole serving step's share of the chip's peak
for a sparse-expert model with window layers — the operations the window's
tokens require (``roofline_moe_window.step_flops``: 2 per *active* matrix
parameter for every processed token, 2 per expert parameter for every pair
the held experts computed, the head only for the rows owed a token,
attention over the positions each layer kind reads) per second of the
window, over the peak."""
from benchmark import reference_moe_window, roofline_moe_window


def read(run):
    c = run["counts"]
    if "expert_pairs" not in c:
        return None
    tokens = roofline_moe_window.processed(c)
    if tokens <= 0:
        return None
    s = reference_moe_window.Sizes(run["config"])
    per_s = roofline_moe_window.step_flops(
        s, tokens, c["generated_tokens"], c["context_positions"],
        c["selected_positions"], c["expert_pairs"]) / c["elapsed_s"]
    return 100.0 * per_s / (run["chips"] * run["peak"]["flops_per_s"])

"""``hybrid_serve_step_mfu``: the whole serving step's share of the chip's
peak for a model of sparse and lightning layers — the operations the
processed tokens require (``roofline_hybrid.step_flops``: 2 per matrix
parameter, gates included, attention over the positions *selected*, the
state products) per second of the window, over the peak."""
from benchmark import reference_hybrid, roofline_hybrid


def read(run):
    c = run["counts"]
    if "selected_positions" not in c:
        return None
    tokens = c["prefill_tokens"] + c["generated_tokens"]
    if tokens <= 0:
        return None
    s = reference_hybrid.Sizes(run["config"])
    pairs = roofline_hybrid.chunk_pairs(
        c["prefill_tokens"], c["prefill_chunks"], c["generated_tokens"])
    per_s = roofline_hybrid.step_flops(
        s, tokens, c["selected_positions"], pairs) / c["elapsed_s"]
    return 100.0 * per_s / (run["chips"] * run["peak"]["flops_per_s"])

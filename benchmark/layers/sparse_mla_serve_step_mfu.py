"""``sparse_mla_serve_step_mfu``: the whole serving step's share of the
chip's peak for the latent-attention decoder with a learned indexer — the
operations the window's tokens require (``roofline_mla_sparse.step_flops``:
2 per *active* matrix parameter for every processed token by layer kind, 2
per expert parameter for every pair the held experts computed, the head
only for the rows owed a token, the absorbed attention over the rows the
full layers' indexer chose and the sliding layers' windows hold, the
indexer's ``2 * 64 * 128`` a scored position) per second of the window,
over the peak."""
from benchmark import reference_mla_sparse, roofline_mla_sparse


def read(run):
    c = run["counts"]
    if not roofline_mla_sparse.counted(c):
        return None
    tokens = roofline_mla_sparse.processed(c)
    if tokens <= 0:
        return None
    s = reference_mla_sparse.Sizes(run["config"])
    per_s = roofline_mla_sparse.step_flops(
        s, tokens, c["generated_tokens"], c["context_positions"],
        c["latent_rows_selected"], c["latent_rows_window"],
        c["expert_pairs"]) / c["elapsed_s"]
    return 100.0 * per_s / (run["chips"] * run["peak"]["flops_per_s"])

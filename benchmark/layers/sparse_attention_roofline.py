"""``sparse_attention_roofline``: the least time the chip could take for
the sparse layers' attention in the window's steps — reading each live
row's selected key and value blocks and its compressed keys in each sparse
layer and step, and the products over the selected positions — over the
device time of the Mosaic calls named ``ragged_paged_attention``."""
from benchmark import kernel_share, reference_hybrid, roofline, \
    roofline_hybrid


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or "read_rows" not in c:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"],
                                        ("ragged_paged_attention",))
    if spent <= 0:
        return None
    s = reference_hybrid.Sizes(run["config"])
    layers = s.count(roofline_hybrid.SPARSE)
    ops = layers * roofline_hybrid.sparse_attention_ops(
        s, c["selected_positions"])
    nbytes = layers * roofline_hybrid.sparse_attention_bytes(
        s, c["read_rows"], c["span_rows"])
    least, _ = roofline.least_seconds(ops, nbytes, run["peak"])
    return 100.0 * least / spent

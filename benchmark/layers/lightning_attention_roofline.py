"""``lightning_attention_roofline``: the least time the chip could take for
the lightning layers in the window's steps — reading and writing each live
row's float32 state in each lightning layer and step, and the products with
the state and inside each chunk — over the device time of the Mosaic calls
named ``lightning_attention``."""
from benchmark import kernel_share, reference_hybrid, roofline, \
    roofline_hybrid


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or "prefill_chunks" not in c:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"], ("lightning_attention",))
    if spent <= 0:
        return None
    s = reference_hybrid.Sizes(run["config"])
    layers = s.count(roofline_hybrid.LIGHTNING)
    tokens = c["prefill_tokens"] + c["generated_tokens"]
    pairs = roofline_hybrid.chunk_pairs(
        c["prefill_tokens"], c["prefill_chunks"], c["generated_tokens"])
    ops = layers * roofline_hybrid.lightning_ops(s, tokens, pairs)
    nbytes = layers * roofline_hybrid.lightning_bytes(s, c["rows"])
    least, _ = roofline.least_seconds(ops, nbytes, run["peak"])
    return 100.0 * least / spent

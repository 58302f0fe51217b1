"""``latent_attention_roofline``: the least time the chip could take for
the latent attention of the window's steps — reading, once for each query,
the cached rows it reads (the indexer's choice in a full layer, the window
in a sliding one: ``serving_latent_rows_total``) at the model's width and
type, its absorbed query and its latent output, and the two absorbed
products a row — over the device time of the Mosaic calls named
``latent_attention``."""
from benchmark import (kernel_share, reference_mla_sparse, roofline,
                       roofline_mla_sparse)


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or not roofline_mla_sparse.counted(c):
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"], ("latent_attention",))
    if spent <= 0:
        return None
    s = reference_mla_sparse.Sizes(run["config"])
    full, window = c["latent_rows_selected"], c["latent_rows_window"]
    least, _ = roofline.least_seconds(
        roofline_mla_sparse.attention_ops(s, full, window),
        roofline_mla_sparse.latent_attention_bytes(
            s, roofline_mla_sparse.processed(c), full, window),
        run["peak"])
    return 100.0 * least / spent

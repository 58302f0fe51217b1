"""``expert_matmul_roofline``: the least time the chip could take for the
expert layers of the window's steps — reading once the matrices of every
held expert that got a pair (the program's
``serving_expert_weight_reads_total``) and each pair's rows, and the pairs'
products (``serving_expert_pairs_total``) — over the device time of the
Mosaic calls named ``expert_matmul``."""
from benchmark import (kernel_share, reference_moe_window, roofline,
                       roofline_moe_window)


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or "expert_pairs" not in c:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"], ("expert_matmul",))
    if spent <= 0:
        return None
    s = reference_moe_window.Sizes(run["config"])
    least, _ = roofline.least_seconds(
        roofline_moe_window.expert_ops(s, c["expert_pairs"]),
        roofline_moe_window.expert_bytes(s, c["expert_pairs"],
                                         c["experts_read"]),
        run["peak"])
    return 100.0 * least / spent

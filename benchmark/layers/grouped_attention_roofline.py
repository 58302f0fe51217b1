"""``grouped_attention_roofline``: the least time the chip could take for
the attention branches of the window's steps — reading each live row's keys
and values (dense, the key/value heads only) in each layer and step, and
the two products over the counted context positions — over the device time
of the Mosaic calls named ``ragged_paged_attention``: the grouped-heads mode
at short contexts and many rows."""
from benchmark import kernel_share, reference_ssm, roofline, roofline_ssm


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or "state_rows_chunk" not in c:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"],
                                        ("ragged_paged_attention",))
    if spent <= 0:
        return None
    s = reference_ssm.Sizes(run["config"])
    ops = s.L * roofline_ssm.attention_ops(s, c["context_positions"])
    nbytes = s.L * roofline_ssm.attention_bytes(s, c["context_rows"])
    least, _ = roofline.least_seconds(ops, nbytes, run["peak"])
    return 100.0 * least / spent

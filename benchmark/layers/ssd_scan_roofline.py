"""``ssd_scan_roofline``: the least time the chip could take for the
selective scans of the window's steps — reading and writing each advanced
row's float32 state in each layer (the rows the program counted in
``serving_state_row_steps_total``) and the scan's products — over the
device time of the Mosaic calls named ``ssd_scan``."""
from benchmark import kernel_share, reference_ssm, roofline, roofline_ssm


def read(run):
    tr, c = run.get("trace"), run["counts"]
    if not tr or "state_rows_chunk" not in c:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"], ("ssd_scan",))
    if spent <= 0:
        return None
    s = reference_ssm.Sizes(run["config"])
    tokens, pairs = roofline_ssm.processed(c)
    ops = s.L * roofline_ssm.scan_ops(s, tokens, pairs)
    nbytes = s.L * roofline_ssm.scan_bytes(
        s, c["state_rows_chunk"] + c["state_rows_decode"])
    least, _ = roofline.least_seconds(ops, nbytes, run["peak"])
    return 100.0 * least / spent

"""``expert_matmul_device_share``: the share of the device's busy time in
the traced window that the Mosaic calls named ``expert_matmul`` took: how
much of the step the expert layer's products are."""
from benchmark import kernel_share


def read(run):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    spent = kernel_share.mosaic_seconds(tr["ops"], ("expert_matmul",))
    if spent <= 0:
        return None
    return 100.0 * spent / tr["busy_s"]

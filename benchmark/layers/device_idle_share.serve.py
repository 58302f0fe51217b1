"""``device_idle_share.serve``: the share of the traced window in which no
operation ran on the device (1 - union of the device's operation intervals
over the window), from the profiler's trace."""


def read(run):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

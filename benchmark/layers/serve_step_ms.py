"""``serve_step_ms``: the median wall time of the window's ``step()`` calls,
on the runner's clock around the call."""
import statistics


def read(run):
    step_s = run["counts"].get("step_s")
    if not step_s:
        return None
    return 1e3 * statistics.median(step_s)

"""``serve_batch_occupancy``: rows that held a request when a step was
called, over the rows the engine has, mean over the window's steps (counted
by the runner around ``step()``)."""


def read(run):
    c = run["counts"]
    if not c.get("steps"):
        return None
    return 100.0 * c["rows"] / (c["steps"] * c["max_batch_size"])

"""``ttft_p95_ms``: the 95th percentile, over all requests sent in the
window, of ``add_request`` to the first output token on the runner's clock.
In a closed loop that keeps every row busy it is set by how many prompts
share the step's token budget when a request arrives, which swings with the
order of the mix: it stands here, beside the scheduler's counts, and not
among the bounded end-to-end metrics."""


def read(run):
    return run["counts"].get("ttft_p95_ms")

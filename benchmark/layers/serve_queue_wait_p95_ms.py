"""``serve_queue_wait_p95_ms``: the 95th percentile of submit to admission
as the program stamps it (``serving_queue_wait_seconds``, observed once per
admission in admission order, which is first in, first out), over the
requests sent in the window: the newest ``notes["requests_sent"]`` samples,
since nothing is sent after the window and every request sent in it is
admitted before the run ends.  A preempted request is admitted and observed
a second time, so with any preemption in the window the samples no longer
count requests and nothing is read."""
import numpy as np

from benchmark import program_series


def read(run):
    notes = run["notes"]
    sent = notes.get("requests_sent")
    if not sent or notes.get("counters", {}).get("preempted", 1) != 0:
        return None
    waits = program_series.samples("serving_queue_wait_seconds")
    if waits is None or len(waits) < sent:
        return None
    return 1e3 * float(np.percentile(waits[-sent:], 95))

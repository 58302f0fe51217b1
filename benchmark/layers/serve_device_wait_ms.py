"""``serve_device_wait_ms``: the median, over the window's steps, of the
``device_wait`` phase of ``serving_step_phase_seconds{phase}`` — the
program's own time around ``logits.block_until_ready()``, from the end of
the dispatch to the step program's end on the device."""
import statistics

from benchmark import program_series


def read(run):
    phases = program_series.window_phases(run)
    if phases is None:
        return None
    return 1e3 * statistics.median(phases["device_wait"])

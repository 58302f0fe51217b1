"""``serve_host_share``: the share of the window's ``Engine.step()`` time
that the host spent on anything but waiting for the device — over the
window's steps, the sum of every phase of
``serving_step_phase_seconds{phase}`` but ``device_wait`` over the sum of
all of them.  The phases are durations the program timed itself
(``time.perf_counter_ns`` around each, ``RecordEvent``'s clock).  The loop
is synchronous, so this should read what ``device_idle_share.serve`` reads
of the same run, less whatever ``dispatch`` overlaps with the device."""
from benchmark import program_series


def read(run):
    phases = program_series.window_phases(run)
    if phases is None:
        return None
    total = sum(sum(s) for s in phases.values())
    if total <= 0:
        return None
    return 100.0 * (total - sum(phases["device_wait"])) / total

"""``serve_fetch_sample_ms``: the median, over the window's steps, of
``fetch + sample`` of ``serving_step_phase_seconds{phase}`` — the copy of
the ``[rows, vocabulary]`` logits to the host and the host's sampling of
every row that is owed a token: what sampling on the device would take off
the step."""
import statistics

from benchmark import program_series


def read(run):
    phases = program_series.window_phases(run)
    if phases is None:
        return None
    return 1e3 * statistics.median(
        f + s for f, s in zip(phases["fetch"], phases["sample"]))

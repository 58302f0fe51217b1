"""What the program records about itself, read after a run: the series of
``paddle_tpu.observability``'s default registry, which outlive the engine
that wrote them.

``serving_step_phase_seconds{phase}`` holds one observation per phase per
``Engine.step()`` call (0 for a phase the call did not reach), so the n-th
sample of every phase belongs to the n-th call, and the serving runner makes
one ``step()`` per turn.  A window is therefore cut by count, not by clock:
its samples are the ``counts["steps"]`` that come before the last
``notes["steps_after_close"]``.  A series keeps its newest 2048 samples; a
window that no longer fits (about two minutes of 68 ms steps) reads nothing.

Every function returns ``None`` rather than raise when the program has no
such series or method, as a program from before these series has not.
"""
from __future__ import annotations

# the phases of one ``Engine.step()`` call, in order; together they cover it
PHASES = ("admit", "plan", "pack", "dispatch", "device_wait", "fetch",
          "sample", "commit")
PHASE_SERIES = "serving_step_phase_seconds"


def samples(name, **labels):
    """The kept samples of a histogram of the program, oldest first."""
    try:
        from paddle_tpu.observability.metrics import default_registry
    except ImportError:
        return None
    series = default_registry().get(name)
    if series is not None and labels:
        if tuple(sorted(labels)) != tuple(sorted(series.labelnames)):
            return None
        series = series.labels(**labels)
    read = getattr(series, "samples", None)
    return read() if read is not None else None


def window_phases(run):
    """``{phase: [seconds, one per step of the window]}``, or ``None``
    when a phase holds fewer samples than the window and the steps after
    it, or the phases' sample counts differ."""
    steps = run["counts"].get("steps")
    after = run["notes"].get("steps_after_close")
    if not steps or after is None:
        return None
    every = {p: samples(PHASE_SERIES, phase=p) for p in PHASES}
    if any(s is None for s in every.values()):
        return None
    sizes = {len(s) for s in every.values()}
    if len(sizes) != 1 or sizes.pop() < steps + after:
        return None
    return {p: s[len(s) - after - steps: len(s) - after]
            for p, s in every.items()}

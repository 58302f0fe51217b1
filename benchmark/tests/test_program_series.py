"""CPU tests of the readers that take their numbers from the program's own
series (``program_series.py``) and from the kernels' names in the reduced
trace (``kernel_share.py``), on hand-built runs and a hand-built registry.

    python -m pytest benchmark/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import kernel_share, program_series, roofline  # noqa: E402
from benchmark import run as bench  # noqa: E402
from paddle_tpu.observability.metrics import (Histogram,  # noqa: E402
                                              default_registry)

PHASES = program_series.PHASES
READERS = {name: bench.load_module("layers", name).read for name in (
    "serve_host_share", "serve_device_wait_ms", "serve_fetch_sample_ms",
    "serve_queue_wait_p95_ms", "flash_fwd_roofline", "flash_bwd_roofline")}


@pytest.fixture
def registry():
    """The process's registry with the two series these readers take
    swapped for empty ones, and put back after."""
    reg = default_registry()
    names = (program_series.PHASE_SERIES, "serving_queue_wait_seconds")
    before = {n: reg.get(n) for n in names}
    reg.register(Histogram(names[0], labelnames=("phase",)), replace=True)
    reg.register(Histogram(names[1]), replace=True)
    yield reg
    for n, old in before.items():
        reg.unregister(n)
        if old is not None:
            reg.register(old)


def _observe_steps(reg, steps):
    """``steps``: one ``{phase: seconds}`` per ``Engine.step()`` call."""
    family = reg.get(program_series.PHASE_SERIES)
    for step in steps:
        for p in PHASES:
            family.labels(phase=p).observe(step.get(p, 0.0))


def _run(steps, after, sent=0, preempted=0):
    return {"counts": {"steps": steps},
            "notes": {"steps_after_close": after, "requests_sent": sent,
                      "counters": {"preempted": preempted}}}


def _step(wait, other=0.001, fetch=0.0, sample=0.0):
    step = dict.fromkeys(PHASES, other)
    step.update(device_wait=wait, fetch=fetch, sample=sample)
    return step


# ------------------------------------------------------------ the window


def test_the_window_is_cut_by_steps_and_steps_after_close(registry):
    warm = [_step(9.0)] * 3
    window = [_step(0.050), _step(0.060), _step(0.070), _step(0.080)]
    after = [_step(7.0)] * 2
    _observe_steps(registry, warm + window + after)
    cut = program_series.window_phases(_run(4, 2))
    assert cut["device_wait"] == [0.050, 0.060, 0.070, 0.080]
    assert set(cut) == set(PHASES)
    assert all(len(v) == 4 for v in cut.values())
    # nothing after the window: its steps are the newest
    assert program_series.window_phases(_run(2, 0))["device_wait"] == [
        7.0, 7.0]


def test_a_short_reservoir_reads_nothing(registry):
    _observe_steps(registry, [_step(0.05)] * 5)
    assert program_series.window_phases(_run(4, 1)) is not None
    assert program_series.window_phases(_run(4, 2)) is None
    assert program_series.window_phases(_run(0, 0)) is None
    for name in ("serve_host_share", "serve_device_wait_ms",
                 "serve_fetch_sample_ms"):
        assert READERS[name](_run(4, 2)) is None


def test_unequal_phase_counts_read_nothing(registry):
    _observe_steps(registry, [_step(0.05)] * 6)
    registry.get(program_series.PHASE_SERIES).labels(
        phase="commit").observe(0.001)
    assert program_series.window_phases(_run(4, 1)) is None
    assert READERS["serve_host_share"](_run(4, 1)) is None


def test_a_program_without_the_series_reads_nothing(registry):
    registry.unregister(program_series.PHASE_SERIES)
    registry.unregister("serving_queue_wait_seconds")
    assert program_series.samples(program_series.PHASE_SERIES,
                                  phase="admit") is None
    for name in ("serve_host_share", "serve_device_wait_ms",
                 "serve_fetch_sample_ms", "serve_queue_wait_p95_ms"):
        assert READERS[name](_run(4, 1, sent=3)) is None
    # a histogram from before ``samples()`` existed
    class Old:
        labelnames = ()
        name = "serving_queue_wait_seconds"
    registry.register(Old())
    assert READERS["serve_queue_wait_p95_ms"](_run(4, 1, sent=3)) is None


# ------------------------------------------------------------ the readers


def test_host_share_is_everything_but_the_wait_over_everything(registry):
    _observe_steps(registry, [_step(9.0)] + [_step(0.060, other=0.001,
                                                   fetch=0.002,
                                                   sample=0.001)] * 4)
    # per step: 5 phases of 1 ms + fetch 2 + sample 1 = 8 ms of host, 60 wait
    assert READERS["serve_host_share"](_run(4, 0)) == pytest.approx(
        100.0 * 8 / 68)
    assert READERS["serve_device_wait_ms"](_run(4, 0)) == pytest.approx(60.0)


def test_fetch_sample_is_the_median_of_the_per_step_sum(registry):
    steps = [_step(0.06, fetch=f, sample=s) for f, s in
             ((0.001, 0.004), (0.002, 0.001), (0.005, 0.005))]
    _observe_steps(registry, steps + [_step(0.06, fetch=1.0, sample=1.0)])
    assert READERS["serve_fetch_sample_ms"](_run(3, 1)) == pytest.approx(5.0)


def test_queue_wait_takes_the_newest_requests_sent(registry):
    series = registry.get("serving_queue_wait_seconds")
    for v in [5.0] * 10 + [0.001 * i for i in range(1, 21)]:
        series.observe(v)
    assert READERS["serve_queue_wait_p95_ms"](
        _run(4, 1, sent=20)) == pytest.approx(19.05)
    assert READERS["serve_queue_wait_p95_ms"](_run(4, 1, sent=31)) is None
    assert READERS["serve_queue_wait_p95_ms"](_run(4, 1, sent=0)) is None


def test_queue_wait_reads_nothing_after_a_preemption(registry):
    series = registry.get("serving_queue_wait_seconds")
    for _ in range(8):
        series.observe(0.001)
    assert READERS["serve_queue_wait_p95_ms"](_run(4, 1, sent=8)) == \
        pytest.approx(1.0)
    assert READERS["serve_queue_wait_p95_ms"](
        _run(4, 1, sent=8, preempted=1)) is None
    run = _run(4, 1, sent=8)
    del run["notes"]["counters"]
    assert READERS["serve_queue_wait_p95_ms"](run) is None


# ------------------------------------------------------- kernels by name


def _train_run(ops):
    config = bench.load_json(bench.HERE, "configs", "tiny.json")
    return {"trace": {"ops": ops}, "config": config,
            "peak": roofline.peaks("TPU v5 lite"),
            "counts": {"steps": 3, "micro_batches": 2, "batch": 4,
                       "seq": 64}}


def test_mosaic_seconds_takes_named_mosaic_calls_only():
    ops = {"flash_fwd.16[mosaic]": 1.0, "flash_fwd.17[mosaic]": 2.0,
           "flash_bwd_dkdv.9[mosaic]": 4.0, "flash_bwd_dq.9[mosaic]": 8.0,
           "flash_fwd_fusion.3": 16.0, "closed_call.3[mosaic]": 32.0}
    assert kernel_share.mosaic_seconds(ops, ("flash_fwd",)) == 3.0
    assert kernel_share.mosaic_seconds(
        ops, ("flash_bwd_dkdv", "flash_bwd_dq")) == 12.0
    assert kernel_share.mosaic_seconds(ops, ("ragged",)) == 0.0


def test_flash_shares_split_the_required_calls_by_kernel():
    from benchmark import reference

    ops = {"flash_fwd.16[mosaic]": 1e-3, "flash_fwd.17[mosaic]": 1e-3,
           "flash_bwd_dkdv.9[mosaic]": 3e-3, "flash_bwd_dq.9[mosaic]": 1e-3,
           "fusion.1": 5.0}
    run = _train_run(ops)
    s = reference.Sizes(run["config"])
    call = roofline.causal_attention_call(2, s.H, 64, s.hd)
    calls = 3 * 2 * s.L
    fwd = roofline.least_seconds(*call["forward"], run["peak"])[0]
    bwd = roofline.least_seconds(*call["backward"], run["peak"])[0]
    assert READERS["flash_fwd_roofline"](run) == pytest.approx(
        100 * calls * fwd / 2e-3)
    assert READERS["flash_bwd_roofline"](run) == pytest.approx(
        100 * calls * bwd / 4e-3)


def test_flash_shares_read_nothing_from_unnamed_kernels_or_no_trace():
    run = _train_run({"closed_call.3[mosaic]": 1.0,
                      "checkpoint.19[mosaic]": 1.0})
    assert READERS["flash_fwd_roofline"](run) is None
    assert READERS["flash_bwd_roofline"](run) is None
    run["trace"] = None
    assert READERS["flash_fwd_roofline"](run) is None

"""Unified observability layer tests: MetricsRegistry snapshot /
Prometheus exposition, thread-safety, the JIT compile watchdog (the
ragged-shape regression detector), the step-aware Profiler scheduler,
chrome-trace export with step instants + counter tracks, and the
Benchmark timer warmup-boundary regression."""
import json
import logging
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observability import (CompileWatchdog, Counter, Gauge,
                                      Histogram, MetricsRegistry,
                                      default_registry, default_watchdog,
                                      watchdog_enabled)
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 make_scheduler)


@pytest.fixture(autouse=True)
def _clean_watchdog():
    wd = default_watchdog()
    prev = wd.enabled
    wd.reset()
    yield
    wd.enabled = prev
    wd.reset()


@pytest.fixture
def obs_caplog(caplog):
    """caplog wired to the observability logger: the framework's
    'paddle_tpu' parent logger sets propagate=False (per-rank handler),
    so records never reach caplog's root handler on their own."""
    log = logging.getLogger("paddle_tpu.observability")
    log.addHandler(caplog.handler)
    try:
        yield caplog
    finally:
        log.removeHandler(caplog.handler)


# ---------------------------------------------------------------- registry
class TestMetricsRegistry:
    def test_counter_gauge_histogram_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("reqs").inc(3)
        g = reg.gauge("occ")
        g.set(0.8)
        g.set(0.5)
        h = reg.histogram("lat")
        for v in (0.01, 0.02, 0.04):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["reqs"] == {"type": "counter", "value": 3}
        assert snap["occ"]["value"] == {"current": 0.5, "peak": 0.8}
        assert snap["lat"]["value"]["count"] == 3
        assert snap["lat"]["value"]["p50"] == 0.02
        json.dumps(snap)                     # JSON-able end to end

    def test_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("compiles", labelnames=("fn",))
        c.labels(fn="prefill").inc(2)
        c.labels(fn="decode").inc()
        c.labels(fn="prefill").inc()         # same child
        snap = reg.snapshot()["compiles"]
        series = {s["labels"]["fn"]: s["value"] for s in snap["series"]}
        assert series == {"prefill": 3, "decode": 1}
        with pytest.raises(ValueError):
            c.inc()                          # family needs .labels()
        with pytest.raises(ValueError):
            reg.gauge("compiles")            # kind mismatch

    def test_get_or_create_and_replace(self):
        reg = MetricsRegistry()
        a = reg.counter("x")
        assert reg.counter("x") is a
        a.inc(5)
        fresh = Counter("x")
        reg.register(fresh, replace=True)    # the reset idiom
        assert reg.snapshot()["x"]["value"] == 0
        with pytest.raises(ValueError):
            reg.register(Counter("x"))       # no silent replacement

    def test_prometheus_round_trip(self):
        """Every sample line in the exposition must be parseable and
        must agree with the snapshot."""
        reg = MetricsRegistry()
        reg.counter("reqs_total", labelnames=("code",)) \
            .labels(code=200).inc(7)
        reg.gauge("occ").set(0.25)
        h = reg.histogram("lat_s")
        for v in (0.0001, 0.01, 5.0):
            h.observe(v)
        text = reg.expose_prometheus()
        line = re.compile(
            r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$')
        samples = {}
        for ln in text.splitlines():
            if ln.startswith("#"):
                assert ln.startswith(("# HELP ", "# TYPE "))
                continue
            m = line.match(ln)
            assert m, f"unparseable exposition line: {ln!r}"
            samples[m.group(1) + (m.group(2) or "")] = float(m.group(3))
        assert samples['reqs_total{code="200"}'] == 7
        assert samples["occ"] == 0.25
        assert samples["lat_s_count"] == 3
        assert abs(samples["lat_s_sum"] - 5.0101) < 1e-9
        assert samples['lat_s_bucket{le="+Inf"}'] == 3
        # buckets are cumulative and monotone
        buckets = [(float(k.split('le="')[1].rstrip('"}')), v)
                   for k, v in samples.items()
                   if k.startswith("lat_s_bucket") and "+Inf" not in k]
        vals = [v for _, v in sorted(buckets)]
        assert vals == sorted(vals)
        assert vals[-1] <= 3

    def test_histogram_thread_safety(self):
        """observe() from worker threads while the main thread snapshots:
        the old list-mutation-during-sort race crashed here."""
        h = Histogram("lat")
        stop = threading.Event()
        errs = []

        def hammer():
            i = 0
            while not stop.is_set():
                h.observe(i % 100 * 1e-3)
                i += 1

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                s = h.summary()
                # before the first observe lands, stats are None-filled
                assert s["count"] == 0 or s["p99"] >= s["p50"]
                h.percentile(95)
        except Exception as e:              # pragma: no cover
            errs.append(e)
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errs

    def test_summary_sorts_reservoir_once(self, monkeypatch):
        import paddle_tpu.observability.metrics as om

        calls = {"n": 0}
        real_sorted = sorted

        def counting_sorted(*a, **k):
            calls["n"] += 1
            return real_sorted(*a, **k)

        # shadow the builtin in the module's global namespace
        monkeypatch.setattr(om, "sorted", counting_sorted, raising=False)
        h = Histogram("lat")
        for v in (3.0, 1.0, 2.0):
            h.observe(v)
        s = h.summary()
        assert calls["n"] == 1               # one sort for p50+p95+p99
        assert (s["p50"], s["p95"], s["p99"]) == (2.0, 3.0, 3.0)


# ---------------------------------------------------------------- watchdog
class TestCompileWatchdog:
    def _watched_step(self, wd):
        def step(x, y):
            return (x * y).sum()

        return wd.watch(jax.jit(step), name="test::step")

    def test_recompile_flagged_once_with_shape_diff(self, obs_caplog):
        """The acceptance scenario: same-shape calls log nothing; ONE
        changed-shape call logs exactly one WARNING carrying the
        per-argument shape diff."""
        wd = CompileWatchdog(registry=MetricsRegistry())
        wd.enable()
        f = self._watched_step(wd)
        x4 = jnp.ones((4, 2))
        with obs_caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.observability"):
            for _ in range(3):
                f(x4, x4)                    # warmup + cache hits
            assert obs_caplog.records == []
            f(jnp.ones((8, 2)), jnp.ones((8, 2)))   # ragged batch
        warnings = [r for r in obs_caplog.records
                    if r.levelno == logging.WARNING]
        assert len(warnings) == 1
        msg = warnings[0].getMessage()
        assert "test::step" in msg
        assert "f32[4,2] -> f32[8,2]" in msg
        rep = wd.report()["test::step"]
        assert rep["calls"] == 4
        assert rep["compiles"] == 2
        assert rep["recompiles"] == 1
        assert rep["compile_time_s"] > 0

    def test_silent_when_disabled_and_counters_in_registry(self, obs_caplog):
        reg = MetricsRegistry()
        wd = CompileWatchdog(registry=reg)
        f = self._watched_step(wd)           # disabled: pure pass-through
        with obs_caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.observability"):
            f(jnp.ones((2, 2)), jnp.ones((2, 2)))
            f(jnp.ones((5, 2)), jnp.ones((5, 2)))
        assert obs_caplog.records == []
        assert wd.report() == {}

        wd.enable()
        f(jnp.ones((3, 2)), jnp.ones((3, 2)))
        f(jnp.ones((6, 2)), jnp.ones((6, 2)))
        snap = reg.snapshot()
        series = {s["labels"]["fn"]: s["value"]
                  for s in snap["jit_compiles_total"]["series"]}
        assert series["test::step"] == 2
        recs = {s["labels"]["fn"]: s["value"]
                for s in snap["jit_recompiles_total"]["series"]}
        assert recs["test::step"] == 1

    def test_proxy_forwards_jit_attrs(self):
        wd = CompileWatchdog(registry=MetricsRegistry())
        f = wd.watch(jax.jit(lambda x: x + 1), name="fwd")
        lowered = f.lower(jnp.ones((2,)))    # AOT surface intact
        assert "stablehlo" in lowered.as_text() or lowered.as_text()
        assert callable(f.__wrapped__)

    def test_serving_engine_compiles_each_program_once(self, obs_caplog):
        """The engine's 'ONE statically-shaped program compiles exactly
        once' contract — prompt chunks and decode rows share the unified
        step — watched live across ragged prompts, a prompt long enough
        to span several chunks, and mid-flight admission."""
        from paddle_tpu.models.gpt import GPT_CONFIGS
        from paddle_tpu.serving import Engine, SamplingParams

        with obs_caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.observability"), \
                watchdog_enabled() as wd:
            eng = Engine(GPT_CONFIGS["tiny"], page_size=4, num_pages=64,
                         max_batch_size=2, chunk_len=16)
            eng.generate([[1, 2, 3], [4, 5], list(range(40))],
                         SamplingParams(max_new_tokens=3))
            rep = wd.report()
        assert rep["serving::unified_step"]["compiles"] == 1
        assert rep["serving::unified_step"]["calls"] > 1
        assert not [r for r in obs_caplog.records
                    if r.levelno >= logging.WARNING]


# ---------------------------------------------------------------- profiler
class TestScheduler:
    def test_states_on_right_steps(self):
        s = make_scheduler(wait=1, warmup=2, active=3, repeat=0)
        want = [ProfilerState.CLOSED, ProfilerState.READY,
                ProfilerState.READY, ProfilerState.RECORD,
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN]
        assert [s(i) for i in range(6)] == want
        assert [s(i) for i in range(6, 12)] == want     # cycles

    def test_repeat_and_skip_first(self):
        s = make_scheduler(closed=0, ready=0, record=2, repeat=1,
                           skip_first=2)
        assert [s(i) for i in range(6)] == [
            ProfilerState.CLOSED, ProfilerState.CLOSED,
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN,
            ProfilerState.CLOSED, ProfilerState.CLOSED]

    def test_record_required(self):
        with pytest.raises(ValueError):
            make_scheduler(wait=1, warmup=1, active=0)

    def test_profiler_records_only_active_window(self):
        fired = []
        p = Profiler(scheduler=(1, 1, 2, 1), with_device=False,
                     on_trace_ready=lambda pr: fired.append(pr.step_num))
        p.start()
        for i in range(6):
            with RecordEvent(f"step{i}"):
                pass
            p.step()
        p.stop()
        names = {ev[1] for ev in p._events if ev[0] == "X"}
        assert names == {"step2", "step3"}   # active steps only
        assert fired[0] == 3                 # window closed after step 3

    def test_step_without_scheduler_marks_instants(self):
        p = Profiler(with_device=False)
        p.start()
        for _ in range(3):
            p.step()
        p.stop()
        instants = [ev for ev in p._events if ev[0] == "i"]
        assert len(instants) == 4            # start + 3 steps


class TestChromeExport:
    def test_instants_counters_and_track_metadata(self, tmp_path):
        default_registry().gauge("test_occupancy").set(0.75)
        p = Profiler(with_device=False)
        p.start()
        with RecordEvent("span_a"):
            pass
        p.step()
        p.stop()
        out = tmp_path / "trace.json"
        p.export(str(out))
        evs = json.loads(out.read_text())["traceEvents"]
        by_ph = {}
        for e in evs:
            by_ph.setdefault(e["ph"], []).append(e)
        assert any(e["name"] == "span_a" for e in by_ph["X"])
        assert any(e["name"].startswith("ProfilerStep#")
                   for e in by_ph["i"])
        counters = [e for e in by_ph["C"]
                    if e["name"] == "test_occupancy"]
        assert counters and counters[-1]["args"]["test_occupancy"] == 0.75
        meta_names = {e["name"] for e in by_ph["M"]}
        assert {"process_name", "thread_name"} <= meta_names

    def test_record_event_decorator(self):
        @RecordEvent("decorated")
        def work(a, b=1):
            return a + b

        p = Profiler(with_device=False)
        p.start()
        assert work(1, b=2) == 3
        p.stop()
        assert "decorated" in p.summary()


# ------------------------------------------------------------------- timer
class _FakeTime:
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


class TestBenchmarkWarmupBoundary:
    def test_reader_and_batch_skip_the_same_steps(self, monkeypatch):
        """Regression: the boundary step must contribute reader cost IFF
        it contributes batch cost (the old pre/post-increment convention
        split let them diverge)."""
        import paddle_tpu.profiler.timer as timer_mod

        clk = _FakeTime()
        monkeypatch.setattr(timer_mod, "time", clk)
        from paddle_tpu.profiler.timer import Benchmark

        bm = Benchmark(warmup_steps=1)
        for _ in range(3):
            bm.before_reader()
            clk.t += 0.5                     # reader: 0.5s/step
            bm.after_reader()
            bm.step_start()
            clk.t += 1.0                     # batch: 1.0s/step
            bm.step_end(num_samples=2)
        info = bm.step_info()
        assert info["steps"] == 2            # 3 steps - 1 warmup
        assert info["avg_batch_cost"] == pytest.approx(1.0)
        # reader cost averaged over the SAME 2 counted steps
        assert info["reader_cost"] == pytest.approx(0.5)
        assert info["ips"] == pytest.approx(4 / 2.0)

    def test_dangling_reader_fetch_not_counted(self, monkeypatch):
        """A tail batch fetched but never stepped (loop break) must not
        inflate reader cost."""
        import paddle_tpu.profiler.timer as timer_mod

        clk = _FakeTime()
        monkeypatch.setattr(timer_mod, "time", clk)
        from paddle_tpu.profiler.timer import Benchmark

        bm = Benchmark(warmup_steps=0)
        bm.before_reader()
        clk.t += 0.2
        bm.after_reader()
        bm.step_start()
        clk.t += 1.0
        bm.step_end()
        bm.before_reader()
        clk.t += 99.0                        # fetched, then loop breaks
        bm.after_reader()
        assert bm.step_info()["reader_cost"] == pytest.approx(0.2)


# --------------------------------------------------------- serving client
class TestServingMetricsThinClient:
    def test_registers_into_default_registry(self):
        from paddle_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.requests_submitted.inc(2)
        m.ttft.observe(0.1)
        snap = default_registry().snapshot()
        assert snap["serving_requests_submitted_total"]["value"] == 2
        assert snap["serving_ttft_seconds"]["value"]["count"] == 1
        # rebuild = reset: fresh series replace the old ones globally
        m2 = ServingMetrics()
        assert default_registry().snapshot()[
            "serving_requests_submitted_total"]["value"] == 0
        assert m2.snapshot()["requests"]["submitted"] == 0

    def test_isolated_registry(self):
        from paddle_tpu.serving.metrics import STEP_PHASES, ServingMetrics

        reg = MetricsRegistry()
        m = ServingMetrics(registry=reg)
        m.tokens_generated.inc(5)
        assert reg.snapshot()[
            "serving_tokens_generated_total"]["value"] == 5
        snap = m.snapshot()
        assert snap["tokens"]["generated"] == 5
        assert set(snap) == {"requests", "tokens", "prefix_cache",
                             "queue_wait_s", "ttft_s", "decode_token_s",
                             "step_phase_s", "page_occupancy",
                             "engine_healthy", "queue_depth",
                             "estimated_drain_s"}
        assert tuple(snap["step_phase_s"]) == STEP_PHASES


# ----------------------------------------------------------------- hapi
class TestProfilerCallback:
    def test_fit_traces_batches_and_steps(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import ProfilerCallback
        from paddle_tpu.io import Dataset

        class Toy(Dataset):
            def __init__(self, n=16):
                rng = np.random.RandomState(0)
                self.x = rng.randn(n, 4).astype(np.float32)
                self.y = rng.randint(0, 2, (n,)).astype(np.int64)

            def __len__(self):
                return len(self.x)

            def __getitem__(self, i):
                return self.x[i], self.y[i]

        model = paddle.Model(nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                           nn.Linear(8, 2)))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        model.prepare(opt, nn.CrossEntropyLoss())
        cb = ProfilerCallback(scheduler=(0, 1, 3, 0), with_device=False)
        model.fit(Toy(), batch_size=4, epochs=1, verbose=0, callbacks=[cb])
        summ = cb.profiler.summary()
        assert "hapi::train_batch" in summ
        assert "hapi::train_step" in summ    # the jitted step span
        instants = [ev for ev in cb.profiler._events if ev[0] == "i"]
        assert instants                      # step boundaries in trace

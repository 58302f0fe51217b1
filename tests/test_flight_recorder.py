"""Flight recorder tests: Span/Tracer model, request-lifecycle tracing
through the serving engine (chrome round-trip incl. evicted + shed),
the retry-after drain estimate, the telemetry HTTP endpoints scraped
over a real localhost socket, the resource sampler, import purity
(no side-effect threads/sockets), empty-histogram None semantics, and
the metric-naming lint."""
import dataclasses
import json
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
from paddle_tpu.observability import (Histogram, MetricsRegistry,
                                      ResourceSampler, Tracer,
                                      default_tracer,
                                      start_telemetry_server)
from paddle_tpu.serving import (Engine, RequestState, SamplingParams,
                                ServingMetrics)


class ManualClock:
    """Deterministic seconds source; ``auto`` advances a fixed dt per
    read so spans get nonzero, reproducible durations without sleeps."""

    def __init__(self, auto=0.0):
        self.t = 0.0
        self.auto = auto

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        self.t += self.auto
        return self.t


def _tiny_engine(clock=None, **kw):
    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")
    params = gpt_init(cfg, jax.random.key(0), dtype=jnp.float32)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("chunk_len", 32)
    return Engine(cfg, params, clock=clock, **kw)


# ----------------------------------------------------------------- tracer


class TestTracer:
    def test_span_tree_ids_and_ring(self):
        clk = ManualClock(auto=0.5)
        tr = Tracer(clock=clk, max_traces=3)
        root = tr.start_trace("op", attributes={"k": 1})
        child = tr.start_span("phase", root)
        grand = tr.start_span("inner", child)
        assert child.trace_id == root.trace_id == grand.trace_id
        assert child.parent_id == root.span_id
        assert grand.parent_id == child.span_id
        grand.end()
        child.end()
        assert tr.traces() == []             # root still open
        root.end()
        (done,) = tr.traces()
        assert done["name"] == "op"
        assert [s["name"] for s in done["spans"]] == ["op", "phase",
                                                      "inner"]
        assert done["duration_s"] > 0
        # ring keeps only the newest max_traces
        for i in range(5):
            tr.start_trace(f"t{i}").end()
        names = [t["name"] for t in tr.traces()]
        assert names == ["t2", "t3", "t4"]
        assert tr.summary()["completed"] == 6   # lifetime, not buffered

    def test_open_children_force_ended_with_root(self):
        tr = Tracer(clock=ManualClock(auto=1.0))
        root = tr.start_trace("op")
        tr.start_span("never_ended", root)
        root.end()
        (done,) = tr.traces()
        child = done["spans"][1]
        assert child["attributes"]["unfinished"] is True
        assert child["end_s"] == done["end_s"]

    def test_trace_context_manager_records_errors(self):
        tr = Tracer(clock=ManualClock(auto=1.0))
        with pytest.raises(ValueError):
            with tr.trace("boom"):
                raise ValueError("nope")
        (done,) = tr.traces()
        assert "ValueError" in done["spans"][0]["attributes"]["error"]

    def test_injectable_clock_stamps_exactly(self):
        clk = ManualClock()
        tr = Tracer(clock=clk)
        clk.advance(10.0)
        root = tr.start_trace("op")
        clk.advance(2.5)
        root.end()
        (done,) = tr.traces()
        assert done["start_s"] == 10.0 and done["end_s"] == 12.5


# -------------------------------------------------- engine request traces


class TestEngineRequestTracing:
    def test_request_span_tree_nests_chunk_and_decode(self):
        """Acceptance: a request traced through generate() yields a
        chrome-exportable span tree whose chunk/decode spans nest
        under the request root — injectable clock, no sleeps."""
        eng = _tiny_engine(clock=ManualClock(auto=0.001))
        eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3))
        (tr,) = eng.tracer.traces()
        spans = {s["name"]: s for s in tr["spans"]}
        root = spans["request#0"]
        assert root["parent_id"] is None
        assert root["attributes"]["state"] == "finished"
        assert root["attributes"]["batch_slot"] == 0
        assert {"queued", "chunk[0]", "decode[1]", "decode[2]"} <= set(spans)
        for name, s in spans.items():
            if name == "request#0":
                continue
            assert s["parent_id"] == root["span_id"]
            assert root["start_s"] <= s["start_s"]
            assert s["end_s"] <= root["end_s"]
        # lifecycle order: queued → chunk[i] → decode[i].  A span runs
        # from its program's dispatch to the read of its ids, and the
        # next program is dispatched while this one runs: consecutive
        # spans follow one another by their starts and by their ends
        assert spans["queued"]["end_s"] <= spans["chunk[0]"]["start_s"]
        for a, b in (("chunk[0]", "decode[1]"), ("decode[1]", "decode[2]")):
            assert spans[a]["start_s"] < spans[b]["start_s"]
            assert spans[b]["start_s"] < spans[a]["end_s"] \
                < spans[b]["end_s"]
        # occupancy rides on the decode spans
        assert spans["decode[1]"]["attributes"]["page_occupancy"] > 0

    def test_chrome_round_trip_with_evicted_and_shed(self, tmp_path):
        clk = ManualClock()
        eng = _tiny_engine(clock=clk, shed_queue_high=2, max_batch_size=1)
        ok = eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=2))
        doomed = eng.add_request([4, 5], SamplingParams(max_new_tokens=2,
                                                       ttl_s=0.5))
        shed = eng.add_request([6], SamplingParams(max_new_tokens=2))
        assert shed.state == RequestState.RETRY_AFTER
        clk.advance(0.01)
        eng.step()                       # admits+prefills ok
        clk.advance(1.0)                 # doomed's TTL passes while queued
        while eng.has_work():
            clk.advance(0.01)
            eng.step()
        assert ok.state == RequestState.FINISHED
        assert doomed.state == RequestState.EVICTED

        path = str(tmp_path / "flight.json")
        eng.tracer.export_chrome(path)
        with open(path) as f:
            trace = json.load(f)
        evs = trace["traceEvents"]
        # one labelled track per request
        labels = {e["tid"]: e["args"]["name"] for e in evs
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        assert sorted(labels.values()) == ["request#0", "request#1",
                                           "request#2"]
        by_track = {}
        for e in evs:
            if e["ph"] == "X":
                by_track.setdefault(labels[e["tid"]], []).append(e)
        # finished request: full lifecycle nested inside the root X event
        req0 = {e["name"]: e for e in by_track["request#0"]}
        root = req0["request#0"]
        for name, e in req0.items():
            assert e["ts"] >= root["ts"]
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-6
        assert "chunk[0]" in req0 and "queued" in req0
        # evicted and shed requests still produce tracks
        assert any(e["name"] == "request#1" for e in by_track["request#1"])
        assert any(e["name"] == "request#2" for e in by_track["request#2"])

    def test_trace_states_for_terminal_paths(self):
        clk = ManualClock()
        eng = _tiny_engine(clock=clk, shed_queue_high=1, max_batch_size=1)
        rej = eng.add_request([], SamplingParams())     # infeasible
        q = eng.add_request([1, 2], SamplingParams(max_new_tokens=2,
                                                   ttl_s=0.1))
        shed = eng.add_request([3], SamplingParams())
        clk.advance(1.0)
        eng.step()                                      # evicts q
        states = {t["name"]: t["spans"][0]["attributes"]["state"]
                  for t in eng.tracer.traces()}
        assert states[f"request#{rej.id}"] == "rejected"
        assert states[f"request#{q.id}"] == "evicted"
        assert states[f"request#{shed.id}"] == "retry_after"
        shed_tr = [t for t in eng.tracer.traces()
                   if t["name"] == f"request#{shed.id}"][0]
        assert shed_tr["spans"][0]["attributes"]["retry_after_s"] > 0


# ------------------------------------------------------ retry-after hint


class TestRetryAfterHint:
    def test_shed_request_carries_finite_drain_estimate(self):
        """Acceptance: retry_after_s is finite, > 0, and derived from
        live queue depth ÷ the measured decode rate."""
        clk = ManualClock(auto=0.001)    # 1ms per clock read
        eng = _tiny_engine(clock=clk, shed_queue_high=3, shed_queue_low=0,
                           max_batch_size=1)
        for _ in range(3):
            eng.add_request([1, 2], SamplingParams(max_new_tokens=4))
        eng.step()                       # prefill, dispatched
        assert eng.decode_rate() is None     # nothing read yet
        eng.step()                       # its token committed → EWMA rate
        assert eng.decode_rate() is not None and eng.decode_rate() > 0
        shed = eng.add_request([3, 4], SamplingParams(max_new_tokens=4))
        assert shed.state == RequestState.RETRY_AFTER
        assert shed.retry_after_s is not None
        assert 0 < shed.retry_after_s < float("inf")
        expected = eng.pending_decode_tokens() / eng.decode_rate()
        assert shed.retry_after_s == pytest.approx(expected, rel=1e-6)
        assert "retry in" in shed.finish_reason

    def test_drain_estimate_floored_before_decode_sample(self):
        eng = _tiny_engine(clock=ManualClock(auto=0.001),
                           shed_queue_high=1)
        # cold start: no EWMA sample yet — the conservative floor, not
        # a hammer-inviting 0 (the fleet router would otherwise dump
        # the whole backlog on a freshly restarted replica)
        assert eng.estimated_drain_s() == eng.drain_floor_s > 0
        assert eng.decode_rate() is None
        eng.add_request([1, 2], SamplingParams(max_new_tokens=8))
        # small backlog, still cold → the floor dominates the
        # ASSUMED_DECODE_RATE fallback (0.08s here)
        est = eng.estimated_drain_s()
        assert est == max(8 / Engine.ASSUMED_DECODE_RATE,
                          eng.drain_floor_s)
        shed = eng.add_request([3], SamplingParams(max_new_tokens=8))
        assert shed.state == RequestState.RETRY_AFTER
        assert shed.retry_after_s >= eng.drain_floor_s

    def test_health_and_gauges_publish_drain(self):
        clk = ManualClock(auto=0.001)
        # low watermark 0: hysteresis keeps the engine degraded until
        # the queue fully drains, so the post-step state is deterministic
        eng = _tiny_engine(clock=clk, shed_queue_high=2, shed_queue_low=0,
                           max_batch_size=1)
        eng.metrics = ServingMetrics(registry=MetricsRegistry())
        for _ in range(2):
            eng.add_request([1, 2], SamplingParams(max_new_tokens=4))
        eng.step()
        h = eng.health()
        assert h["healthy"] is False     # queue watermark crossed
        assert h["estimated_drain_s"] > 0
        assert h["queue_depth"] == 1
        snap = eng.metrics.registry.snapshot()
        assert snap["serving_estimated_drain_seconds"]["value"]["current"] > 0
        assert snap["serving_queue_depth"]["value"]["current"] == 1


# ----------------------------------------------------------- hapi spans


class TestHapiStepSpans:
    def test_fit_opens_per_step_spans(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.io import Dataset

        class Toy(Dataset):
            def __init__(self, n=8):
                rng = np.random.RandomState(0)
                self.x = rng.randn(n, 4).astype(np.float32)
                self.y = rng.randint(0, 2, (n,)).astype(np.int64)

            def __len__(self):
                return len(self.x)

            def __getitem__(self, i):
                return self.x[i], self.y[i]

        default_tracer().reset()
        model = paddle.Model(nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                           nn.Linear(8, 2)))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        model.prepare(opt, nn.CrossEntropyLoss())
        model.fit(Toy(), batch_size=4, epochs=1, verbose=0)
        steps = [t for t in default_tracer().traces()
                 if t["name"] == "hapi::step"]
        assert len(steps) == 2
        attrs = [t["spans"][0]["attributes"] for t in steps]
        assert [a["step"] for a in attrs] == [0, 1]
        assert all(a["epoch"] == 0 for a in attrs)
        assert all(isinstance(a["loss"], float) for a in attrs)


# ------------------------------------------------------- resource sampler


class TestResourceSampler:
    def test_sample_once_populates_gauges(self):
        reg = MetricsRegistry()
        s = ResourceSampler(registry=reg)
        sample = s.sample_once()
        assert sample["rss_bytes"] is None or sample["rss_bytes"] > 0
        snap = reg.snapshot()
        if sample["rss_bytes"] is not None:
            assert snap["process_rss_bytes"]["value"]["current"] > 0
        if sample["open_fds"] is not None:
            assert snap["process_open_fds"]["value"]["current"] > 0
        # jax is imported in this process → live buffers are measurable
        assert sample["jax_live_buffer_bytes"] is not None
        assert "0" in sample["gc_collections"]
        json.dumps(sample)

    def test_thread_start_stop(self):
        import threading

        reg = MetricsRegistry()
        before = {t.name for t in threading.enumerate()}
        with ResourceSampler(interval_s=0.01, registry=reg) as s:
            for _ in range(200):
                if s.last_sample is not None:
                    break
                threading.Event().wait(0.01)
            assert s.last_sample is not None
        assert {t.name for t in threading.enumerate()} == before


# ----------------------------------------------- telemetry endpoints e2e


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.headers.get("Content-Type", ""), \
                r.read().decode()
    except urllib.error.HTTPError as e:      # non-2xx still has a body
        return e.code, e.headers.get("Content-Type", ""), \
            e.read().decode()


class TestTelemetryServerE2E:
    """End-to-end over a real localhost socket: scrape /metrics,
    /healthz, /varz and /traces during a generate() run."""

    def test_scrape_all_endpoints_during_generation(self):
        # private tracer: the process-wide one carries traces from other
        # tests, and this test counts exactly its own two requests
        eng = _tiny_engine(tracer=Tracer())
        # and a private registry: /healthz folds process-wide gauges
        # (training_healthy, slo_page_active, hang_watchdog_active,
        # integrity_divergence_active) on top of the engine's health, and
        # one that another test file left raised in this worker's default
        # registry turned this probe to 503 (the one failure of the
        # driver's whole run at PR 34: order, not timing)
        reg = MetricsRegistry()
        eng.metrics = ServingMetrics(registry=reg)
        with start_telemetry_server(port=0, engine=eng,
                                    registry=reg) as srv:
            assert srv.port > 0
            eng.generate([[1, 2, 3], [4, 5]],
                         SamplingParams(max_new_tokens=3))

            code, ctype, body = _get(srv.url + "/metrics")
            assert code == 200 and ctype.startswith("text/plain")
            assert "# TYPE serving_requests_submitted_total counter" \
                in body
            assert "serving_requests_submitted_total 2" in body
            assert "serving_ttft_seconds_bucket" in body

            code, ctype, body = _get(srv.url + "/healthz")
            health = json.loads(body)
            assert code == 200 and health["healthy"] is True
            assert set(health) >= {"queue_depth", "page_occupancy",
                                   "estimated_drain_s",
                                   "decode_rate_tok_s"}

            code, _, body = _get(srv.url + "/varz")
            varz = json.loads(body)
            assert "serving_requests_finished_total" in varz["metrics"]
            assert "jit" in varz and "pid" in varz

            code, _, body = _get(srv.url + "/traces")
            traces = json.loads(body)["traces"]
            assert len(traces) == 2
            for t in traces:
                names = [s["name"] for s in t["spans"]]
                assert names[0].startswith("request#")
                assert "chunk[0]" in names

            code, _, body = _get(srv.url + "/traces?limit=1")
            assert len(json.loads(body)["traces"]) == 1

            code, _, _ = _get(srv.url + "/nope")
            assert code == 404

    def test_healthz_503_while_shedding(self):
        eng = _tiny_engine(shed_queue_high=1)
        with start_telemetry_server(port=0, engine=eng) as srv:
            eng.add_request([1, 2], SamplingParams(max_new_tokens=4))
            assert eng._update_shedding()
            code, _, body = _get(srv.url + "/healthz")
            assert code == 503
            assert json.loads(body)["healthy"] is False

    def test_registry_fallback_without_engine(self):
        reg = MetricsRegistry()
        reg.gauge("serving_engine_healthy").set(1)
        reg.gauge("serving_queue_depth").set(7)
        with start_telemetry_server(port=0, registry=reg) as srv:
            code, _, body = _get(srv.url + "/healthz")
            health = json.loads(body)
            assert code == 200
            assert health["queue_depth"] == 7


# --------------------------------------------------------- import purity


class TestImportPurity:
    def test_import_paddle_tpu_spawns_no_threads_or_sockets(self):
        """Exporter and sampler are strictly opt-in: a bare import must
        not start a thread or open a listening socket (tier-1: a fleet
        binary embedding the framework owns its own ports)."""
        script = (
            "import json, os, threading\n"
            "def socket_fds():\n"
            "    out = []\n"
            "    for fd in os.listdir('/proc/self/fd'):\n"
            "        try:\n"
            "            t = os.readlink(f'/proc/self/fd/{fd}')\n"
            "        except OSError:\n"
            "            continue\n"
            "        if t.startswith('socket:'):\n"
            "            out.append(fd)\n"
            "    return out\n"
            "before_t = {t.name for t in threading.enumerate()}\n"
            "before_s = socket_fds()\n"
            "import paddle_tpu\n"
            "import paddle_tpu.observability.exporter\n"
            "after_t = {t.name for t in threading.enumerate()}\n"
            "after_s = socket_fds()\n"
            "print(json.dumps({'new_threads': sorted(after_t - before_t),"
            " 'new_sockets': sorted(set(after_s) - set(before_s))}))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", script], capture_output=True,
            text=True, timeout=300,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        diff = json.loads(out.stdout.strip().splitlines()[-1])
        assert diff["new_threads"] == [], diff
        assert diff["new_sockets"] == [], diff


# ------------------------------------------------------ empty histograms


class TestEmptyHistogram:
    def test_percentile_and_summary_none_filled(self):
        h = Histogram("lat")
        assert h.percentile(50) is None
        s = h.summary()
        assert s == {"count": 0, "mean": None, "p50": None, "p95": None,
                     "p99": None}
        json.dumps(s)                    # JSON null, not a crash
        h.observe(0.5)
        assert h.percentile(50) == 0.5
        assert h.summary()["mean"] == 0.5

    def test_fresh_process_exposition_does_not_raise(self):
        reg = MetricsRegistry()
        reg.histogram("cold_series")
        text = reg.expose_prometheus()
        assert "cold_series_count 0" in text
        snap = reg.snapshot()
        assert snap["cold_series"]["value"]["p50"] is None

    def test_serving_summary_renders_empty_series(self):
        m = ServingMetrics(registry=MetricsRegistry())
        text = m.summary()               # nothing observed anywhere
        assert "queue_wait_s" in text and "-" in text


# ------------------------------------------------------ metric-name lint


class TestMetricNamesLint:
    def _tool(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "tools", "check_metric_names.py")
        spec = importlib.util.spec_from_file_location(
            "check_metric_names", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # the repo-wide sweep now runs ONCE in the consolidated suite:
    # tests/test_static_analysis.py::TestTier1Suite

    def test_lint_catches_planted_violations(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "from paddle_tpu.observability import Counter, Gauge\n"
            "a = Counter('requests_served')\n"          # no _total
            "b = Gauge('CamelCaseName')\n"              # not snake_case
            "c = Counter(\n    'foo_total')\n"          # multi-line: seen
            "d = Gauge('foo_total')\n"                  # kind mismatch
            "# Counter('commented_out')\n")             # comment: ignored
        violations = self._tool().check(root=str(tmp_path))
        text = "\n".join(violations)
        assert "requests_served" in text and "_total" in text
        assert "CamelCaseName" in text
        assert "foo_total" in text and "one name, one type" in text
        assert "commented_out" not in text
        assert len(violations) == 3


# --------------------------------------------------- tracing overhead smoke


def _trace_lifecycle_s(tracer, n=300, reps=3):
    """Seconds one request-shaped trace lifecycle costs under ``tracer``:
    a root and queued/dispatch/decode child spans with attributes, all
    ended; median over ``reps`` windows of ``n``.  Host clock on the CPU."""
    import time

    def per_request():
        t0 = time.perf_counter()
        for i in range(n):
            now = float(i)
            root = tracer.start_trace("request#bench", start_s=now,
                                      attributes={"prompt_len": 32})
            for name in ("queued", "router::dispatch", "decode"):
                sp = tracer.start_span(name, root, start_s=now)
                sp.set_attribute("outcome", "ok")
                sp.end(now + 0.001)
            root.end(now + 0.002)
        return (time.perf_counter() - t0) / n

    per_request()                            # warm-up
    return float(np.median([per_request() for _ in range(reps)]))


class TestTracingOverheadSmoke:
    def test_implied_request_overhead_under_bound(self):
        """Acceptance: a full request-shaped trace lifecycle with tail
        retention at full sampling (the default posture) costs under 1%
        of 50 ms.  A ratio of host times on the CPU against a model
        request of 50 ms; not measured on the chip."""
        import time

        from paddle_tpu.observability.tracing import TailRetention

        full = _trace_lifecycle_s(
            Tracer(clock=time.perf_counter, max_traces=256))
        sampled_tracer = Tracer(clock=time.perf_counter, max_traces=256,
                                retention=TailRetention(sample_rate=0.01))
        _trace_lifecycle_s(sampled_tracer)
        disabled = _trace_lifecycle_s(
            Tracer(clock=time.perf_counter, enabled=False))
        assert full / 0.05 < 0.01, full
        # absolute sanity: tens of microseconds per request, not ms
        assert full * 1e6 < 1000, full
        # the disabled posture must be dramatically cheaper (null span)
        assert disabled < full, (disabled, full)
        # and sampled retention must actually shed boring traces
        assert sampled_tracer.summary()["dropped"] > 0

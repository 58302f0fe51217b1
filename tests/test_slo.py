"""SLO engine matrix — burn-rate alerts, budgets, endpoints, and the
autoscaler coupling, all on a manual clock.

The alert state machine is driven beat by beat through a scripted
traffic history: a 100%-bad storm fires the fast-burn page exactly once
(fire-once/sticky), the alert stays active while the storm holds, does
NOT clear before ``clear_after_seconds`` of continuously-healthy short
window, then clears exactly once — and every transition lands in the
metrics, the ``/slo`` payload, and a tail-retained ``slo::<name>``
span.  The autoscaler acceptance: a firing TTFT fast-burn page scales
the fleet up under pressure the hysteresis band alone would ignore,
and a degraded error budget blocks scale-down.
"""
import json
import urllib.error
import urllib.request

import pytest

from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.slo import (SEVERITIES, SLO, BurnRateAlert,
                                          SLOEngine)
from paddle_tpu.observability.timeseries import TimeSeriesStore
from paddle_tpu.observability.tracing import Tracer
from paddle_tpu.observability.exporter import start_telemetry_server
from paddle_tpu.serving import Autoscaler, FleetRouter, RequestState


class _ManualClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _page_alert(**kw):
    spec = dict(burn_rate_threshold=5.0, long_window_seconds=4.0,
                short_window_seconds=1.0, clear_after_seconds=1.0)
    spec.update(kw)
    return BurnRateAlert("page", **spec)


def _availability_engine(clock, *, tracer=None, registry=None):
    """req/bad counters + one availability SLO with a tight page."""
    reg = registry or MetricsRegistry()
    req = reg.counter("req_total")
    bad = reg.counter("bad_total")
    store = TimeSeriesStore(registry=reg, clock=clock)
    slo = SLO("availability", target=0.9, bad="bad_total",
              total="req_total", alerts=(_page_alert(),),
              budget_window_seconds=60.0)
    engine = SLOEngine(store, [slo], registry=reg, tracer=tracer,
                       clock=clock)
    return reg, req, bad, store, engine


def _beat(clock, store, engine, req, bad, n_req, n_bad, dt=0.5):
    clock.advance(dt)
    req.inc(n_req)
    bad.inc(n_bad)
    store.scrape_once()
    return engine.evaluate()


# --------------------------------------------------------- declarations


class TestDeclarations:
    def test_severity_enum_is_fixed(self):
        assert SEVERITIES == ("page", "ticket")
        with pytest.raises(ValueError):
            BurnRateAlert("warning", burn_rate_threshold=1.0,
                          long_window_seconds=60.0,
                          short_window_seconds=5.0)

    def test_short_window_must_be_shorter(self):
        with pytest.raises(ValueError):
            BurnRateAlert("page", burn_rate_threshold=1.0,
                          long_window_seconds=5.0,
                          short_window_seconds=5.0)

    def test_slo_name_must_be_snake_case(self):
        with pytest.raises(ValueError):
            SLO("TTFT-p99", target=0.99, bad="b_total", total="t_total")

    def test_target_bounds(self):
        for target in (0.0, 1.0, 1.5):
            with pytest.raises(ValueError):
                SLO("ttft", target=target, bad="b_total",
                    total="t_total")

    def test_exactly_one_form(self):
        with pytest.raises(ValueError):
            SLO("x", target=0.9)                      # no form at all
        with pytest.raises(ValueError):
            SLO("x", target=0.9, good="g_total", bad="b_total",
                total="t_total")                      # two forms
        with pytest.raises(ValueError):
            SLO("x", target=0.9, histogram="lat_seconds")  # no threshold

    def test_duplicate_slo_names_rejected(self):
        clock = _ManualClock()
        store = TimeSeriesStore(registry=MetricsRegistry(), clock=clock)
        slos = [SLO("a", target=0.9, bad="b_total", total="t_total"),
                SLO("a", target=0.5, bad="b_total", total="t_total")]
        with pytest.raises(ValueError):
            SLOEngine(store, slos, registry=MetricsRegistry())

    def test_default_alert_pair_is_workbook_shaped(self):
        slo = SLO("avail", target=0.999, bad="b_total", total="t_total")
        sevs = [a.severity for a in slo.alerts]
        assert sevs == ["page", "ticket"]
        page, ticket = slo.alerts
        assert page.burn_rate_threshold > ticket.burn_rate_threshold
        assert page.long_window_seconds < ticket.long_window_seconds


# ------------------------------------------------- alert state machine


class TestAlertStateMachine:
    def test_fire_once_sticky_hysteresis_clear(self):
        clock = _ManualClock()
        tracer = Tracer()
        reg, req, bad, store, engine = _availability_engine(
            clock, tracer=tracer)
        # healthy traffic: no alert ever
        for _ in range(10):
            assert _beat(clock, store, engine, req, bad, 10, 0) == []
        assert engine.alerts_active() == []
        assert engine.page_active() is False

        # 100%-bad storm: burn 10x on both windows once the long
        # window is majority-bad -> exactly ONE fire event
        fires = []
        for _ in range(12):                        # 6 s of storm
            fires += _beat(clock, store, engine, req, bad, 10, 10)
        assert [t["transition"] for t in fires] == ["fire"]
        assert fires[0]["slo"] == "availability"
        assert fires[0]["severity"] == "page"
        assert engine.page_active() is True
        assert engine.alerts_active() == [("availability", "page")]

        # storm ends; the short window drains within 1 s, but the
        # clear must wait out clear_after_seconds of continuously
        # healthy short window — no flap
        clears = []
        beats_to_clear = 0
        for _ in range(20):
            tr = _beat(clock, store, engine, req, bad, 10, 0)
            beats_to_clear += 1
            if tr:
                clears += tr
                break
        assert [t["transition"] for t in clears] == ["clear"]
        # >= short window (1 s) to drain + 1 s hysteresis at 0.5 s
        # beats: never clears on the first beats after the storm
        assert beats_to_clear >= 4
        assert engine.page_active() is False
        # sticky bookkeeping: one onset, one fire
        st = engine.status()["slos"]["availability"]["alerts"][0]
        assert st["fired"] == 1 and st["active"] is False

        # every transition became a tail-retained slo:: span
        spans = [t for t in tracer.traces()
                 if t["name"] == "slo::availability"]
        assert len(spans) == 2
        assert all(t["retained"] == "flagged" for t in spans)
        kinds = [t["spans"][0]["attributes"]["transition"]
                 for t in spans]
        assert kinds == ["fire", "clear"]

    def test_refire_after_second_onset(self):
        clock = _ManualClock()
        reg, req, bad, store, engine = _availability_engine(clock)
        for _ in range(4):
            _beat(clock, store, engine, req, bad, 10, 0)
        for storm in range(2):
            for _ in range(12):
                _beat(clock, store, engine, req, bad, 10, 10)
            for _ in range(20):
                if _beat(clock, store, engine, req, bad, 10, 0):
                    break
        st = engine.status()["slos"]["availability"]["alerts"][0]
        assert st["fired"] == 2
        kinds = [t["transition"]
                 for t in engine.status()["transitions"]]
        assert kinds == ["fire", "clear", "fire", "clear"]

    def test_long_window_vetoes_blip(self):
        """A single bad beat spikes the short window but not the
        4 s long window: no page — sustained damage is required."""
        clock = _ManualClock()
        reg, req, bad, store, engine = _availability_engine(clock)
        for _ in range(10):
            _beat(clock, store, engine, req, bad, 10, 0)
        assert _beat(clock, store, engine, req, bad, 10, 10) == []
        for _ in range(3):
            assert _beat(clock, store, engine, req, bad, 10, 0) == []
        assert engine.alerts_active() == []

    def test_no_traffic_is_not_an_outage(self):
        clock = _ManualClock()
        reg, req, bad, store, engine = _availability_engine(clock)
        for _ in range(10):
            clock.advance(0.5)
            store.scrape_once()
            assert engine.evaluate() == []
        assert engine.page_active() is False
        assert engine.min_budget_ratio() == 1.0

    def test_metrics_published_on_evaluate(self):
        clock = _ManualClock()
        reg, req, bad, store, engine = _availability_engine(clock)
        for _ in range(12):
            _beat(clock, store, engine, req, bad, 10, 10)
        fired = reg.counter(
            "slo_alerts_total",
            labelnames=("slo", "severity")).labels(
                slo="availability", severity="page").value
        assert fired == 1
        active = reg.gauge(
            "slo_alert_active",
            labelnames=("slo", "severity")).labels(
                slo="availability", severity="page").value
        assert active == 1.0
        assert reg.gauge("slo_page_active").value == 1.0
        burn = reg.gauge(
            "slo_burn_rate", labelnames=("slo", "window")).labels(
                slo="availability", window="1s").value
        assert burn == pytest.approx(10.0)
        budget = reg.gauge(
            "slo_error_budget_ratio", labelnames=("slo",)).labels(
                slo="availability").value
        assert budget < 1.0

    def test_budget_drains_with_bad_fraction(self):
        clock = _ManualClock()
        reg, req, bad, store, engine = _availability_engine(clock)
        for _ in range(4):
            _beat(clock, store, engine, req, bad, 10, 0)
        healthy = engine.min_budget_ratio()
        assert healthy == 1.0
        for _ in range(12):
            _beat(clock, store, engine, req, bad, 10, 10)
        assert engine.min_budget_ratio() < healthy
        assert engine.min_budget_ratio() == 0.0   # 10x overspend


# --------------------------------------------------- histogram-form SLO


class TestLatencySLO:
    def test_ttft_threshold_objective_fires_on_slow_tail(self):
        clock = _ManualClock()
        reg = MetricsRegistry()
        # bucket upper bounds 0.05, 0.1, 0.2, 0.4
        ttft = reg.histogram("serving_ttft_seconds", start=0.05,
                             factor=2.0, count=4)
        store = TimeSeriesStore(registry=reg, clock=clock)
        slo = SLO("ttft_fast", target=0.9,
                  histogram="serving_ttft_seconds",
                  threshold_seconds=0.1, alerts=(_page_alert(),),
                  budget_window_seconds=60.0)
        engine = SLOEngine(store, [slo], registry=reg, clock=clock)
        for _ in range(6):                         # fast: all good
            clock.advance(0.5)
            ttft.observe(0.06)
            store.scrape_once()
            assert engine.evaluate() == []
        fires = []
        for _ in range(12):                        # slow tail storm
            clock.advance(0.5)
            ttft.observe(0.35)
            store.scrape_once()
            fires += engine.evaluate()
        assert [t["transition"] for t in fires] == ["fire"]
        assert engine.page_active() is True


# ------------------------------------------------------------ endpoints


class TestEndpoints:
    def test_slo_timeseries_and_healthz_fold(self):
        clock = _ManualClock()
        reg, req, bad, store, engine = _availability_engine(clock)
        srv = start_telemetry_server(port=0, registry=reg,
                                     tracer=Tracer(), slo=engine,
                                     timeseries=store)
        try:
            for _ in range(4):
                _beat(clock, store, engine, req, bad, 10, 0)
            code, body = _get(srv.url + "/slo")
            assert code == 200
            payload = json.loads(body)
            assert payload["page_active"] is False
            assert payload["slos"]["availability"]["target"] == 0.9
            code, body = _get(srv.url + "/healthz")
            assert code == 200
            assert json.loads(body)["slo_page_active"] is False

            code, body = _get(srv.url + "/timeseries")
            assert code == 200
            assert json.loads(body)["series"] >= 2
            code, body = _get(
                srv.url + "/timeseries?name=req_total&window_seconds=4")
            assert code == 200
            q = json.loads(body)
            assert q["kind"] == "counter" and q["delta"] == 30.0

            for _ in range(12):                   # storm -> page
                _beat(clock, store, engine, req, bad, 10, 10)
            code, body = _get(srv.url + "/healthz")
            health = json.loads(body)
            assert code == 503
            assert health["healthy"] is False
            assert health["slo_page_active"] is True
            code, body = _get(srv.url + "/slo")
            payload = json.loads(body)
            assert payload["page_active"] is True
            assert [t["transition"]
                    for t in payload["transitions"]] == ["fire"]

            for _ in range(20):                   # recover -> clear
                if _beat(clock, store, engine, req, bad, 10, 0):
                    break
            code, _ = _get(srv.url + "/healthz")
            assert code == 200
        finally:
            srv.stop()

    def test_healthz_gauge_fallback_without_engine(self):
        reg = MetricsRegistry()
        reg.gauge("slo_page_active").set(1)
        srv = start_telemetry_server(port=0, registry=reg,
                                     tracer=Tracer())
        try:
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            assert json.loads(body)["slo_page_active"] is True
        finally:
            srv.stop()

    def test_endpoints_404_when_not_attached(self):
        srv = start_telemetry_server(port=0, registry=MetricsRegistry(),
                                     tracer=Tracer())
        try:
            assert _get(srv.url + "/slo")[0] == 404
            assert _get(srv.url + "/timeseries")[0] == 404
        finally:
            srv.stop()


# -------------------------------------------------- autoscaler coupling


class _StubEngine:
    """Router-facing engine stub (mirrors test_autoscaler's)."""

    def __init__(self, rate=120.0, drain=0.0):
        self.rate = rate
        self.drain = drain
        self.reqs = []

    def health(self):
        return {"healthy": True, "queue_depth": 0,
                "running": len(self.reqs), "page_occupancy": 0.0,
                "estimated_drain_s": self.drain,
                "decode_rate_tok_s": self.rate,
                "prefix_cache": {"enabled": True}}

    def add_request(self, prompt, sampling, trace_context=None):
        raise AssertionError("no traffic in these tests")

    def has_work(self):
        return False

    def step(self):
        pass

    def evacuate(self):
        self.reqs = []

    def prefix_summary(self, max_entries=32):
        return {"page_size": 8, "enabled": True, "entries": {},
                "stats": {}}

    def warmup(self):
        return self


class _StubSLO:
    """SLOEngine-shaped stub: the autoscaler only reads
    ``alerts_active()`` and ``min_budget_ratio()``."""

    def __init__(self, alerts=(), budget=1.0):
        self.alerts = list(alerts)
        self.budget = budget

    def alerts_active(self):
        return list(self.alerts)

    def min_budget_ratio(self):
        return self.budget


def _fleet(engines, clock, *, registry=None, scaler_kw=None):
    registry = registry or MetricsRegistry()
    router = FleetRouter(engines, clock=clock, registry=registry)
    kw = dict(min_replicas=1, max_replicas=4, up_pressure_s=2.0,
              down_pressure_s=0.25, up_pending_depth=6,
              scale_up_cooldown_s=5.0, scale_down_cooldown_s=10.0,
              spawn_backoff_base_s=0.001, spawn_backoff_cap_s=0.002)
    kw.update(scaler_kw or {})
    scaler = Autoscaler(router, lambda: _StubEngine(),
                        clock=clock, registry=registry, **kw)
    return router, scaler


class TestAutoscalerSLOCoupling:
    def test_firing_ttft_page_escalates_scale_up(self):
        """THE acceptance scenario: pressure sits inside the
        hysteresis band (no up on its own), but a real TTFT fast-burn
        page is firing — the autoscaler scales up with reason
        ``slo_fast_burn``."""
        clock = _ManualClock()
        reg = MetricsRegistry()
        ttft = reg.histogram("serving_ttft_seconds", start=0.05,
                             factor=2.0, count=4)
        store = TimeSeriesStore(registry=reg, clock=clock)
        slo = SLO("ttft_fast", target=0.9,
                  histogram="serving_ttft_seconds",
                  threshold_seconds=0.1, alerts=(_page_alert(),),
                  budget_window_seconds=60.0)
        slo_engine = SLOEngine(store, [slo], registry=reg, clock=clock)
        stub = _StubEngine(drain=1.0)              # inside the band
        router, scaler = _fleet([stub], clock, registry=reg,
                                scaler_kw={"slo": slo_engine})
        # control first: same pressure, page not yet firing -> no act
        clock.advance(1.0)
        assert scaler.tick() is None
        for _ in range(12):                        # slow-TTFT storm
            clock.advance(0.5)
            ttft.observe(0.35)
            store.scrape_once()
            slo_engine.evaluate()
        assert slo_engine.page_active() is True
        clock.advance(5.0)                         # up cooldown clear
        assert scaler.tick() == ("up", "slo_fast_burn")
        assert len(router.replicas) == 2
        sig = scaler.status()["last_signals"]
        assert sig["slo_page"] is True
        assert sig["pressure_s"] < scaler.up_pressure_s

    def test_pressure_alone_would_not_have_acted(self):
        """The identical fleet WITHOUT the SLO engine stays put under
        the same pressure — the page was the only reason to scale."""
        clock = _ManualClock()
        stub = _StubEngine(drain=1.0)
        router, scaler = _fleet([stub], clock)
        clock.advance(10.0)
        assert scaler.tick() is None
        assert len(router.replicas) == 1

    def test_active_alert_blocks_scale_down(self):
        clock = _ManualClock()
        stubs = [_StubEngine(drain=0.0), _StubEngine(drain=0.0)]
        slo = _StubSLO(alerts=[("availability", "ticket")])
        router, scaler = _fleet(stubs, clock,
                                scaler_kw={"slo": slo})
        clock.advance(30.0)
        assert scaler.tick() is None               # even a ticket vetoes
        slo.alerts = []
        clock.advance(30.0)
        assert scaler.tick() == ("down", "idle")

    def test_thin_budget_blocks_scale_down_until_it_refills(self):
        clock = _ManualClock()
        stubs = [_StubEngine(drain=0.0), _StubEngine(drain=0.0)]
        slo = _StubSLO(budget=0.1)                 # below the 0.25 floor
        router, scaler = _fleet(stubs, clock,
                                scaler_kw={"slo": slo})
        clock.advance(30.0)
        assert scaler.tick() is None
        assert scaler.status()["last_signals"]["slo_min_budget"] == 0.1
        slo.budget = 0.9
        clock.advance(30.0)
        assert scaler.tick() == ("down", "idle")

    def test_windowed_shed_signal_replaces_adhoc_delta(self):
        """With a store attached the shed signal is a
        ``signal_window_s`` delta: a shed burst triggers up, and once
        the burst ages out of the window the signal reads zero again
        regardless of tick cadence."""
        clock = _ManualClock()
        reg = MetricsRegistry()
        stub = _StubEngine(drain=0.0)
        store = TimeSeriesStore(registry=reg, clock=clock)
        router, scaler = _fleet(
            [stub], clock, registry=reg,
            scaler_kw={"timeseries": store, "signal_window_s": 2.0,
                       "scale_down_cooldown_s": 10_000.0})
        shed = reg.counter("router_backpressure_retries_total",
                           labelnames=("replica",))
        # the replica-0 child series is born on its first inc; the
        # windowed delta needs two points of THAT series
        shed.labels(replica="0").inc()
        clock.advance(1.0)
        store.scrape_once()
        shed.labels(replica="0").inc()
        clock.advance(0.5)
        store.scrape_once()
        assert scaler.tick() == ("up", "shed")
        assert scaler.status()["last_signals"]["shed_delta"] == 1.0
        # the burst ages out of the 2 s window -> no more up events
        clock.advance(10.0)
        store.scrape_once()
        clock.advance(0.5)
        store.scrape_once()
        assert scaler.tick() is None
        assert scaler.status()["last_signals"]["shed_delta"] == 0.0


# ------------------------------------------------------- overhead smoke


def _scrape_evaluate_cycle(n=80, reps=5):
    """One full scrape+evaluate cycle — the TimeSeriesStore walking a
    serving-sized metric population (the real ServingMetrics /
    RouterMetrics / AutoscalerMetrics facades, three replicas' label
    children, live TTFT histograms) and the SLOEngine re-computing burn
    rates, budgets and alert state for availability + goodput + TTFT
    latency, each with the page+ticket alert pair.  Each cycle is timed
    alone and a window reports its fastest one (the minimum is the
    intrinsic cost; slower cycles measure preemption by unrelated
    threads); the result is the median of ``reps`` window minima.  Host
    clock on the CPU."""
    import time

    import numpy as np

    from paddle_tpu.serving.metrics import (AutoscalerMetrics,
                                            RouterMetrics, ServingMetrics)

    reg = MetricsRegistry()
    serving = ServingMetrics(registry=reg)
    router = RouterMetrics(registry=reg)
    AutoscalerMetrics(registry=reg)
    rng = np.random.default_rng(7)

    def traffic_beat(i):
        # the population a real fleet scrape sees: per-replica label
        # children plus live histograms
        for rep in range(3):
            router.dispatches.labels(replica=rep).inc()
            if i % 7 == rep:
                router.backpressure_retries.labels(replica=rep).inc()
        router.finished.inc(3)
        serving.requests_submitted.inc(3)
        ttft = float(0.02 + 0.08 * rng.random())
        serving.ttft.observe(ttft)
        router.ttft.observe(ttft)

    alerts = (BurnRateAlert("page", burn_rate_threshold=14.4,
                            long_window_seconds=2.0,
                            short_window_seconds=0.5),
              BurnRateAlert("ticket", burn_rate_threshold=3.0,
                            long_window_seconds=8.0,
                            short_window_seconds=1.0))
    slos = (
        SLO("availability", target=0.999,
            bad=("serving_requests_shed_total",
                 "router_requests_lost_total"),
            total=("serving_requests_submitted_total",),
            alerts=alerts, budget_window_seconds=30.0),
        SLO("goodput", target=0.95,
            good=("router_requests_finished_total",),
            total=("router_dispatches_total",),
            alerts=alerts, budget_window_seconds=30.0),
        SLO("ttft_fast", target=0.99,
            histogram="serving_ttft_seconds", threshold_seconds=0.2,
            alerts=alerts, budget_window_seconds=30.0),
    )
    store = TimeSeriesStore(reg, max_points=256)
    engine = SLOEngine(store, slos, registry=reg)

    def fastest_cycle():
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            store.scrape_once()
            engine.evaluate()
            best = min(best, time.perf_counter() - t0)
        return best

    for i in range(200):            # warm population + ring
        traffic_beat(i)
    fastest_cycle()                 # warm-up
    windows = []
    for w in range(reps):
        for i in range(20):
            traffic_beat(w * 20 + i)
        windows.append(fastest_cycle())
    return {"per_cycle_s": float(np.median(windows)),
            "page_active": engine.page_active()}


class TestSLOOverheadSmoke:
    def test_scrape_evaluate_cycle_under_bound(self, in_fresh_process):
        """Acceptance: a full store-scrape + 3-objective evaluate cycle
        over a serving-shaped registry costs under 1% of 50 ms even if a
        cycle ran per request (it runs per poll interval).  A ratio of
        host times on the CPU against a model request of 50 ms; not
        measured on the chip.  Runs in a fresh subprocess: a mid-suite
        interpreter carries daemon threads from earlier test modules
        whose GIL share uniformly inflates every cycle ~2x — that
        measures the test session, not the engine."""
        out = in_fresh_process(__file__, "_scrape_evaluate_cycle")
        assert out["per_cycle_s"] / 0.05 < 0.01, out
        # absolute sanity: sub-millisecond per cycle
        assert out["per_cycle_s"] * 1e6 < 5000, out
        # the fleet is healthy: no page firing at the end
        assert out["page_active"] is False, out

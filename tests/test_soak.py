"""Compressed chaos soak (``serving.soak.run_soak`` at tier-1 size): a
seeded diurnal/bursty trace through an autoscaled real-engine
fleet while the chaos timeline fires a hard kill, admission and
control-loop stalls, a spawn io_error (the fault sites
``autoscaler.poll`` / ``autoscaler.scale_up`` / ``serving.admit``),
and a live-state ``bitflip`` at ``serving.step``, asserting the
invariants end-to-end: ``lost_requests == 0``, bounded TTFT p99, at
least one scale-up AND one scale-down recorded in the live-scraped
``/fleet``, every chaos event visible in ``/flight``.  A second
scenario drives a ``poison_storm`` through the same harness and
asserts the blast-radius containment contract: every poison ends
terminal QUARANTINED, uncontrolled replica kills stay bounded by
``canary_threshold + 1``, and innocents finish token-identical to a
poison-free oracle.
"""
import dataclasses

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_forward, gpt_init
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.slo import SLO, BurnRateAlert
from paddle_tpu.serving import ChaosEvent, Engine, TrafficGenerator, run_soak


def _tiny_cfg():
    return dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = gpt_init(cfg, jax.random.key(0), dtype=jnp.float32)
    return cfg, params


# stable jitted forward — the poison-free greedy oracle (shared jit
# cache: an eager gpt_forward would recompile per call)
_ORACLE_FWD = {}


def naive_generate(cfg, params, prompt, n_new):
    fwd = _ORACLE_FWD.get(id(cfg))
    if fwd is None:
        fwd = _ORACLE_FWD.setdefault(
            id(cfg), jax.jit(lambda p, t: gpt_forward(cfg, p, t)))
    toks = list(prompt)
    for _ in range(n_new):
        logits = fwd(params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _engine_factory(tiny_model):
    cfg, params = tiny_model

    def factory():
        # a small queue watermark so the burst actually sheds — the
        # RETRY_AFTER signal is one of the scale-up triggers under test
        return Engine(cfg, params, page_size=8, num_pages=64,
                      max_batch_size=2, chunk_len=8,
                      shed_queue_high=4, shed_queue_low=1)
    return factory


@pytest.mark.faultinject
class TestCompressedSoak:
    def test_chaos_soak_invariants(self, tiny_model):
        traffic = TrafficGenerator(
            base_rate_per_s=6.0, diurnal_amplitude=0.9,
            day_period_s=8.0, phase_s=0.0,
            bursts=((1.0, 2.0, 4.0),),          # spike at t in [1, 3)
            n_cohorts=2, cohort_prefix_len=16, cohort_fraction=0.6,
            prompt_len=(8, 24), max_new_tokens=(4, 6),
            vocab_size=_tiny_cfg().vocab_size, seed=1234)
        chaos = [
            ChaosEvent(t=0.5, action="spawn_io_error"),
            ChaosEvent(t=1.5, action="stall_admit", stall_s=0.4),
            ChaosEvent(t=2.5, action="kill"),
            ChaosEvent(t=3.0, action="stall_poll", stall_s=0.3),
            # one seeded bit flips in a live KV page: silent corruption
            # whose blast radius must be at most one request's output —
            # nothing raises, nobody dies, the accounting stays exact
            ChaosEvent(t=3.5, action="bitflip"),
        ]
        report = run_soak(
            _engine_factory(tiny_model), traffic, horizon_s=8.0,
            initial_replicas=2, chaos=chaos,
            registry=MetricsRegistry(),
            scaler_kw=dict(min_replicas=1, max_replicas=3,
                           up_pressure_s=1.0, down_pressure_s=0.15,
                           up_pending_depth=4,
                           scale_up_cooldown_s=1.5,
                           scale_down_cooldown_s=2.0,
                           spawn_max_retries=2,
                           spawn_backoff_base_s=0.01,
                           spawn_backoff_cap_s=0.05),
            deadline_s=40.0, grace_s=8.0, min_down_events=1,
            ttft_bound_s=25.0)

        # ---- zero loss through kills, stalls, drains, scale events
        assert not report["timed_out"], report
        assert report["requests_submitted"] > 20
        assert report["lost_requests"] == 0, report
        assert report["requests_finished"] == report["requests_submitted"]

        # ---- bounded TTFT p99 (recoveries cost latency, never
        # starvation)
        assert report["ttft_p99_s"] is not None
        assert report["ttft_p99_ok"], report["ttft_p99_s"]

        # ---- elasticity both ways, mid-trace
        events = report["scale_events"]
        assert events.get("up", 0) >= 1, events
        assert events.get("down", 0) >= 1, events
        assert events.get("up", 0) + events.get("down", 0) >= 2

        # ---- the whole kill matrix actually fired
        assert all(ev["action"] in ("kill", "stall_admit", "stall_poll",
                                    "spawn_io_error", "bitflip")
                   for ev in report["chaos"])
        assert len(report["chaos"]) == 5
        fired_sites = {f["site"] for f in report["injector_fired"]}
        assert "serving.admit" in fired_sites
        assert "autoscaler.poll" in fired_sites
        assert "autoscaler.scale_up" in fired_sites
        assert "serving.step" in fired_sites      # the bitflip landed
        # the bitflip corrupted at most one request's *output*, never
        # the fleet: nothing quarantined, no cascade, zero loss above
        assert report["requests_quarantined"] == []
        assert report["fleet"]["cascade_breaker_open"] is False
        # the killed replica's in-flight work was re-dispatched (unless
        # it happened to be idle at kill time — redispatch also comes
        # from drains, so usually > 0)
        assert report["redispatched"] >= 0

        # ---- recoveries visible over live HTTP: /fleet carries the
        # autoscaler block with both directions, /flight the chaos
        # timeline
        scraped = report["scraped"]
        fleet = scraped["fleet"]
        assert fleet["autoscaler"]["scale_events"]["up"] >= 1
        assert fleet["autoscaler"]["scale_events"]["down"] >= 1
        assert fleet["counters"]["lost"] == 0
        flight = scraped["flight"]
        flight_ops = {rec["op"] for rec in flight["records"]}
        flight_ops |= set(flight["summary"]["by_op"])
        soak_ops = {op for op in flight_ops if op.startswith("soak::")}
        assert {"soak::kill", "soak::stall_admit", "soak::stall_poll",
                "soak::spawn_io_error", "soak::bitflip"} <= soak_ops, \
            flight_ops

        # ---- merged fleet trace view over live HTTP: a hard-killed-
        # and-failed-over request reads as ONE trace — one entry per
        # trace_id, the failover hop and both dispatches on it
        traces = scraped["traces"]
        assert traces["fleet"] is True
        merged = traces["traces"]
        tids = [t["trace_id"] for t in merged]
        assert len(tids) == len(set(tids)), "trace split across entries"
        if report["redispatched"]:
            failed_over = [
                t for t in merged
                if any(s["name"] == "router::failover"
                       for s in t["spans"])]
            assert failed_over, "redispatches left no failover trace"
            for t in failed_over:
                names = [s["name"] for s in t["spans"]]
                assert names.count("router::dispatch") >= 2, names
                # tail retention pinned it (failover, or a stronger
                # reason like a fault event recorded on a span)
                assert t["retained"] != "sampled", t["retained"]

    def test_poison_storm_containment(self, tiny_model):
        """The compressed poison-storm scenario: 3 poison requests
        (same query-of-death pattern) land mid-trace on a 3-replica
        fleet with the cascade breaker at K=2.  The containment
        contract, end-to-end through the soak harness:

        - every poison ends terminal QUARANTINED (accounted, not lost);
        - uncontrolled replica kills stay <= K+1 — suspicion pins the
          pattern after 2 kills, the canary trial eats the third, and
          conviction covers the storm's siblings for free;
        - innocents lose nothing and their greedy output is
          token-identical to a poison-free oracle run;
        - the quarantines are visible on the live-scraped ``/fleet``
          and the quarantined traces survive in the tail-retained ring.
        """
        cfg, params = tiny_model
        pattern = (7, 8, 9)
        traffic = TrafficGenerator(
            base_rate_per_s=4.0, diurnal_amplitude=0.5,
            day_period_s=6.0, phase_s=0.0, bursts=(),
            n_cohorts=2, cohort_prefix_len=8, cohort_fraction=0.4,
            prompt_len=(8, 20), max_new_tokens=(4, 6),
            vocab_size=cfg.vocab_size, seed=99)
        chaos = [ChaosEvent(t=1.0, action="poison_storm",
                            pattern=pattern, count=3, max_new_tokens=6)]
        report = run_soak(
            _engine_factory(tiny_model), traffic, horizon_s=6.0,
            initial_replicas=3, chaos=chaos,
            registry=MetricsRegistry(),
            router_kw=dict(canary_threshold=2, cascade_threshold=2,
                           cascade_window_s=2.0),
            scaler_kw=dict(min_replicas=1, max_replicas=3,
                           up_pressure_s=1.0, down_pressure_s=0.15,
                           up_pending_depth=4,
                           scale_up_cooldown_s=1.5,
                           scale_down_cooldown_s=2.0,
                           spawn_max_retries=2,
                           spawn_backoff_base_s=0.01,
                           spawn_backoff_cap_s=0.05),
            deadline_s=40.0, grace_s=8.0, min_down_events=0,
            ttft_bound_s=25.0)

        assert not report["timed_out"], report
        storm_ids = set(report["chaos"][0]["detail"]["request_ids"])
        assert len(storm_ids) == 3

        # ---- every poison terminal QUARANTINED, nothing lost
        assert set(report["requests_quarantined"]) == storm_ids
        assert report["lost_requests"] == 0, report
        assert report["requests_failed"] == []

        # ---- blast radius: <= K+1 uncontrolled kills for the whole
        # storm; the canary death was the controlled one
        counters = report["fleet"]["counters"]
        assert counters["failure_events"] <= 3, counters
        assert counters["canary_deaths"] >= 1
        assert counters["quarantined"] == 3
        assert counters["cascade_breaker_opens"] >= 1

        # ---- innocents: all finished, token-identical to the
        # poison-free oracle (sampled — the oracle recompiles per
        # sequence length, so parity-check a deterministic subset)
        innocents = [r for r in report["requests"]
                     if r["id"] not in storm_ids]
        assert innocents
        assert all(r["state"] == "finished" for r in innocents)
        assert report["requests_finished"] == len(innocents)
        for r in innocents[:6]:
            n_new = len(r["output"])
            assert r["output"] == naive_generate(cfg, params,
                                                 r["prompt"], n_new)

        # ---- containment visible from the outside: /fleet carries
        # the quarantine count, the trace ring retains the verdicts
        scraped = report["scraped"]
        assert scraped["fleet"]["quarantined"] == 3
        assert scraped["fleet"]["counters"]["quarantined"] == 3
        retained = [t for t in scraped["traces"]["traces"]
                    if t.get("retained") == "quarantined"]
        assert len(retained) >= 1, \
            [t.get("retained") for t in scraped["traces"]["traces"]]
        assert any(s["name"] == "router::quarantine"
                   for t in retained for s in t["spans"])

    def test_kill_storm_fires_and_clears_availability_page(
            self, tiny_model):
        """The SLO acceptance scenario: two hard kills mid-trace burn
        the availability error budget at page speed — the fast-burn
        page FIRES during the storm, stays sticky through it, and
        CLEARS through its hysteresis once the fleet recovers, with
        both transitions on the scraped ``/slo`` payload and the
        fire/clear pair pinned in the tail-retained trace ring.  The
        run also asserts the RSS leak-slope query end-to-end (a
        generous bound — the point is the plumbing, not a tight leak
        budget)."""
        traffic = TrafficGenerator(
            base_rate_per_s=6.0, diurnal_amplitude=0.3,
            day_period_s=8.0, phase_s=0.0, bursts=(),
            n_cohorts=2, cohort_prefix_len=8, cohort_fraction=0.4,
            prompt_len=(8, 16), max_new_tokens=(4, 6),
            vocab_size=_tiny_cfg().vocab_size, seed=4321)
        chaos = [ChaosEvent(t=1.5, action="kill"),
                 ChaosEvent(t=2.4, action="kill")]
        # availability over router counters: uncontrolled replica
        # failures + lost requests per dispatch.  target 0.99 makes a
        # single kill in the window burn ~10-20x budget (failures are
        # a few percent of dispatches), so threshold 2 fires reliably
        # on BOTH windows during the storm and reads 0 outside it.
        slos = (SLO(
            "fleet_availability", target=0.99,
            bad=("router_replica_failure_events_total",
                 "router_requests_lost_total"),
            total=("router_dispatches_total",),
            alerts=(BurnRateAlert("page", burn_rate_threshold=2.0,
                                  long_window_seconds=3.0,
                                  short_window_seconds=1.0,
                                  clear_after_seconds=0.75),),
            budget_window_seconds=30.0),)
        report = run_soak(
            _engine_factory(tiny_model), traffic, horizon_s=6.0,
            initial_replicas=2, chaos=chaos,
            registry=MetricsRegistry(), slos=slos,
            scaler_kw=dict(min_replicas=1, max_replicas=3,
                           up_pressure_s=1.0, down_pressure_s=0.15,
                           up_pending_depth=4,
                           scale_up_cooldown_s=1.5,
                           scale_down_cooldown_s=2.0,
                           spawn_max_retries=2,
                           spawn_backoff_base_s=0.01,
                           spawn_backoff_cap_s=0.05),
            deadline_s=40.0, grace_s=8.0, min_down_events=0,
            ttft_bound_s=25.0,
            rss_slope_bound_bytes_per_s=256e6)

        assert not report["timed_out"], report
        assert report["lost_requests"] == 0, report

        # ---- the page fired during the storm and cleared after it
        slo_report = report["slo"]
        kinds = [t["transition"]
                 for t in slo_report["transitions"]
                 if t["slo"] == "fleet_availability"]
        assert "fire" in kinds and "clear" in kinds, slo_report
        assert kinds[0] == "fire" and kinds[-1] == "clear"
        (alert,) = slo_report["slos"]["fleet_availability"]["alerts"]
        assert alert["fired"] >= 1
        assert alert["active"] is False           # hysteresis ran out
        assert slo_report["page_active"] is False

        # ---- both transitions visible on the live-scraped /slo
        scraped = report["scraped"]
        scraped_kinds = [t["transition"]
                         for t in scraped["slo"]["transitions"]]
        assert "fire" in scraped_kinds and "clear" in scraped_kinds
        assert scraped["slo"]["page_active"] is False
        # the page un-degraded /healthz again by scrape time
        assert scraped["healthz"]["slo_page_active"] is False

        # ---- fire/clear pair pinned in the tail-retained trace ring
        slo_traces = [t for t in scraped["traces"]["traces"]
                      if t["name"] == "slo::fleet_availability"]
        trace_kinds = {t["spans"][0]["attributes"]["transition"]
                       for t in slo_traces}
        assert {"fire", "clear"} <= trace_kinds, \
            [t.get("retained") for t in scraped["traces"]["traces"]]
        assert all(t["retained"] == "flagged" for t in slo_traces)

        # ---- windowed store ran all run long and the leak-slope
        # query answered (S2: ResourceSampler gauges -> slope)
        assert report["timeseries"]["scrapes"] > 10
        assert report["rss_slope_bytes_per_s"] is not None
        assert report["rss_slope_ok"] is True, \
            report["rss_slope_bytes_per_s"]
        assert scraped["timeseries"]["series"] > 0

    def test_page_arms_profile_capture_and_load_backs_off(
            self, tiny_model):
        """Continuous-profiling + closed-loop acceptance on the kill
        storm: the firing availability page arms a high-rate stack
        capture that lands in the retained set LINKED to the firing
        ``slo::`` transition's trace (same trace_id, ``flagged``
        retention), the live ``/profilez`` scrape answers with phase
        slices that sum to the sampled wall time, and with
        ``burn_feedback=True`` the generator thins submissions while
        the page burns — load measurably backs off, and thinned
        arrivals are accounted as feedback drops, never as lost."""
        traffic = TrafficGenerator(
            base_rate_per_s=6.0, diurnal_amplitude=0.3,
            day_period_s=8.0, phase_s=0.0, bursts=(),
            n_cohorts=2, cohort_prefix_len=8, cohort_fraction=0.4,
            prompt_len=(8, 16), max_new_tokens=(4, 6),
            vocab_size=_tiny_cfg().vocab_size, seed=4321)
        chaos = [ChaosEvent(t=1.5, action="kill"),
                 ChaosEvent(t=2.4, action="kill")]
        slos = (SLO(
            "fleet_availability", target=0.99,
            bad=("router_replica_failure_events_total",
                 "router_requests_lost_total"),
            total=("router_dispatches_total",),
            # wider windows than the bare SLO scenario: a respawn can
            # stall the driver loop (and its scrape cadence) for ~1s,
            # and a short window narrower than the stall never sees
            # the failure bump and its dispatch denominator together
            alerts=(BurnRateAlert("page", burn_rate_threshold=2.0,
                                  long_window_seconds=4.0,
                                  short_window_seconds=2.0,
                                  clear_after_seconds=0.75),),
            budget_window_seconds=30.0),)
        report = run_soak(
            _engine_factory(tiny_model), traffic, horizon_s=6.0,
            initial_replicas=2, chaos=chaos,
            registry=MetricsRegistry(), slos=slos,
            burn_feedback=True,
            scaler_kw=dict(min_replicas=1, max_replicas=3,
                           up_pressure_s=1.0, down_pressure_s=0.15,
                           up_pending_depth=4,
                           scale_up_cooldown_s=1.5,
                           scale_down_cooldown_s=2.0,
                           spawn_max_retries=2,
                           spawn_backoff_base_s=0.01,
                           spawn_backoff_cap_s=0.05),
            deadline_s=40.0, grace_s=8.0, min_down_events=0,
            ttft_bound_s=25.0)

        assert not report["timed_out"], report
        assert report["lost_requests"] == 0, report
        kinds = [t["transition"] for t in report["slo"]["transitions"]
                 if t["slo"] == "fleet_availability"]
        assert "fire" in kinds, report["slo"]

        # ---- the page armed a capture; it finished and was retained
        prof = report["profiling"]
        assert prof["stats"]["lifetime_samples"] > 0
        caps = [c for c in prof["captures"]
                if c["trigger"] == "slo_page"]
        assert caps, prof
        cap = caps[0]
        assert cap["detail"] == "fleet_availability"
        assert cap["samples"] > 0 and cap["hot"], cap

        # ---- linked to the firing slo:: transition: the capture span
        # CONTINUES that trace, so the merged ring shows one flagged
        # trace carrying both spans
        scraped = report["scraped"]
        linked = [t for t in scraped["traces"]["traces"]
                  if any(s["name"] == "profiling::capture"
                         for s in t["spans"])]
        assert linked, "capture span missing from the retained ring"
        (tr,) = [t for t in linked if t["trace_id"] == cap["trace_id"]]
        span_names = [s["name"] for s in tr["spans"]]
        assert "slo::fleet_availability" in span_names, span_names
        assert tr["retained"] == "flagged"

        # ---- /profilez answered live; phase slices sum to wall time
        pz = scraped["profilez"]
        assert pz["samples"] > 0
        assert abs(sum(v["seconds"] for v in pz["by_phase"].values())
                   - pz["sampled_seconds"]) < 1e-6
        assert any(c["trigger"] == "slo_page" for c in pz["captures"])

        # ---- closed loop: load backed off while the page burned
        bf = report["burn_feedback"]
        assert bf["enabled"] is True
        assert bf["dropped"] >= bf["dropped_while_page"] >= 1, bf

"""Training health monitor tests: goodput/MFU accounting, anomaly
detection, and cross-rank metric aggregation (plus the satellite
StatRegistry bridge, checkpoint-save histogram and naming-lint unit
rules)."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.io import Dataset
from paddle_tpu.observability import (ClusterAggregator, GoodputMonitor,
                                      HealthMonitor, MetricsRegistry,
                                      RankMetricsPublisher, Tracer,
                                      TrainingHealthError)
from paddle_tpu.observability.compile_watchdog import (default_watchdog,
                                                       watchdog_enabled)
from paddle_tpu.observability.goodput import device_peak_flops, mfu


@pytest.fixture(autouse=True, scope="module")
def _leave_no_health_gauge_behind():
    """Some monitors here live in the process's default registry; a
    ``training_healthy`` of 0 left there turns every later ``/healthz`` of
    this worker into a 503 (``tests/test_fleet.py``, when the scheduler
    gives it the worker after this file)."""
    yield
    from paddle_tpu.observability import default_registry

    default_registry().unregister("training_healthy")


class Toy(Dataset):
    def __init__(self, n=16, bad_at=None):
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, 4).astype(np.float32)
        self.y = rng.randint(0, 2, (n,)).astype(np.int64)
        if bad_at is not None:
            self.x[bad_at] = np.inf       # poisons that batch's loss

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _model():
    model = paddle.Model(nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                       nn.Linear(8, 2)))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    return model


class ManualClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- goodput


class TestGoodput:
    def test_peak_flops_table_and_env(self, monkeypatch):
        # the CPU is not in the table: no peak, so no MFU against it
        flops, kind = device_peak_flops()
        assert kind == "cpu" and flops is None

        class V5e:
            device_kind = "TPU v5 lite"

        assert device_peak_flops(V5e()) == (197.0e12, "TPU v5 lite")
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "5e13")
        flops, _ = device_peak_flops()
        assert flops == 5e13

    def test_mfu_estimator(self):
        assert mfu(1e12, 0.5, 4e12) == pytest.approx(0.5)
        assert mfu(None, 0.5, 4e12) is None
        assert mfu(1e12, 0.5, None) is None

    def test_breakdown_sums_to_wall_time(self):
        reg = MetricsRegistry()
        default_watchdog().reset()
        mon = GoodputMonitor(registry=reg)
        model = _model()
        with watchdog_enabled():
            t0 = time.perf_counter()
            model.fit(Toy(32), batch_size=4, epochs=2, verbose=0,
                      callbacks=[mon])
            wall = time.perf_counter() - t0
        rep = mon.report()
        assert rep["steps"] == 16
        phase_sum = sum(rep["phases_seconds"].values())
        # phases partition the accounted time exactly...
        assert phase_sum == pytest.approx(rep["total_seconds"], rel=1e-3)
        # ...and the accounted time is the measured fit wall time (±5%:
        # only pre-train setup and the final callback dispatch escape)
        assert rep["total_seconds"] == pytest.approx(wall, rel=0.05)
        # first batch compiled under the watchdog -> nonzero compile
        # phase; the rest is dominated by compute
        assert rep["phases_seconds"]["compile"] > 0
        assert rep["phases_seconds"]["compute"] > 0
        assert 0 < rep["goodput_ratio"] <= 1
        snap = reg.snapshot()
        assert snap["training_goodput_ratio"]["value"]["current"] == \
            pytest.approx(rep["goodput_ratio"])
        phases = {s["labels"]["phase"]: s["value"]["current"]
                  for s in snap["training_step_breakdown_seconds"]["series"]}
        assert phases == pytest.approx(rep["phases_seconds"])
        assert snap["training_step_seconds"]["value"]["count"] == 16

    def test_mfu_published_with_explicit_flops(self):
        reg = MetricsRegistry()
        mon = GoodputMonitor(registry=reg, peak_flops=1e12,
                             flops_per_step=5e9)
        model = _model()
        model.fit(Toy(8), batch_size=4, epochs=1, verbose=0,
                  callbacks=[mon])
        rep = mon.report()
        assert rep["mfu"] is not None and rep["mfu"] > 0
        assert rep["peak_flops"] == 1e12
        assert reg.snapshot()["training_mfu"]["value"]["current"] == \
            pytest.approx(rep["mfu"])

    def test_checkpoint_phase_and_save_histogram(self, tmp_path):
        from paddle_tpu.hapi import CheckpointCallback
        from paddle_tpu.observability import default_registry

        reg = default_registry()
        reg.unregister("checkpoint_save_seconds")
        # goodput monitor FIRST: the checkpoint save then lands in the
        # inter-step gap, exercising the gap re-attribution path
        mon = GoodputMonitor(registry=reg)
        ckpt = CheckpointCallback(save_dir=str(tmp_path), every_n_steps=2)
        model = _model()
        model.fit(Toy(16), batch_size=4, epochs=1, verbose=0,
                  callbacks=[mon, ckpt])
        rep = mon.report()
        assert rep["phases_seconds"]["checkpoint"] > 0
        h = reg.get("checkpoint_save_seconds")
        sync = h.labels(mode="sync")
        assert sync.total == 2                    # steps 2 and 4 of 4
        # checkpoint time is excluded from data_wait, not double-billed
        assert sum(rep["phases_seconds"].values()) == \
            pytest.approx(rep["total_seconds"], rel=1e-3)

    def test_async_save_records_blocking_and_background(self, tmp_path):
        from paddle_tpu.hapi import CheckpointCallback
        from paddle_tpu.observability import default_registry
        from paddle_tpu.resilience import CheckpointManager

        reg = default_registry()
        reg.unregister("checkpoint_save_seconds")
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        ckpt = CheckpointCallback(manager=mgr, every_n_steps=2)
        model = _model()
        model.fit(Toy(8), batch_size=4, epochs=1, verbose=0,
                  callbacks=[ckpt])
        mgr.wait()
        h = reg.get("checkpoint_save_seconds")
        modes = {lv[0] for lv, _ in h._series()}
        assert modes == {"async", "background"}
        # "is async actually overlapping?": the blocking (snapshot)
        # series exists independently from the background write series
        assert h.labels(mode="async").total == 1
        assert h.labels(mode="background").total == 1

    def test_benchmark_step_info_exposes_totals(self):
        from paddle_tpu.profiler.timer import Benchmark

        bm = Benchmark(warmup_steps=0)
        bm.before_reader()
        bm.after_reader()
        bm.step_start()
        bm.step_end(num_samples=4)
        info = bm.step_info()
        assert {"batch_cost_total", "reader_cost_total", "samples",
                "reader_ratio"} <= set(info)
        assert info["samples"] == 4
        assert info["batch_cost_total"] >= 0
        bm.before_reader()
        bm.after_reader()
        assert bm.take_pending_reader_cost() >= 0
        assert bm.take_pending_reader_cost() == 0.0   # drained


# ----------------------------------------------------------------- health


class TestHealthMonitor:
    def _drive(self, mon, seq, dt=0.1):
        """Feed (loss, grad_norm) pairs through the batch hooks with a
        manual clock advancing ``dt`` per step (or per-step dt list)."""
        clk = mon._clock
        for i, (loss, gnorm) in enumerate(seq):
            mon.on_train_batch_begin(i)
            clk.t += dt[i] if isinstance(dt, (list, tuple)) else dt
            logs = {"loss": loss}
            if gnorm is not None:
                logs["grad_norm"] = gnorm
            mon.on_train_batch_end(i, logs)

    def _mon(self, **kw):
        kw.setdefault("registry", MetricsRegistry())
        kw.setdefault("tracer", Tracer())
        kw.setdefault("clock", ManualClock())
        mon = HealthMonitor(**kw)
        mon.set_model(None)
        mon.on_train_begin()
        return mon

    def _anomalies(self, mon):
        c = mon.registry().get("training_anomalies_total")
        return {lv[0]: child.value for lv, child in c._series()} \
            if c else {}

    def test_nan_loss_flagged_exactly_once(self):
        mon = self._mon(action="gauge")
        self._drive(mon, [(1.0, None)] * 5 + [(float("nan"), None)] * 5)
        assert self._anomalies(mon) == {"non_finite_loss": 1}
        assert mon.registry().get("training_healthy").value == 0
        assert not mon.healthy
        # a health::<kind> span landed in the flight recorder
        names = [t["name"] for t in mon.tracer().traces()]
        assert names == ["health::non_finite_loss"]

    def test_recovery_flips_gauge_back(self):
        mon = self._mon(action="gauge", recover_after=2)
        self._drive(mon, [(1.0, None)] * 3 + [(float("inf"), None)]
                    + [(1.0, None)])
        assert mon.registry().get("training_healthy").value == 0
        self._drive(mon, [(1.0, None)])     # second clean step
        assert mon.registry().get("training_healthy").value == 1

    def test_grad_spike_zscore(self):
        mon = self._mon(action="gauge", min_samples=5, window=20)
        rng = np.random.RandomState(0)
        seq = [(1.0, 1.0 + 0.05 * rng.randn()) for _ in range(15)]
        seq.append((1.0, 50.0))
        self._drive(mon, seq)
        assert self._anomalies(mon) == {"grad_spike": 1}
        kinds = [e[0] for e in mon.events]
        assert kinds == ["grad_spike"]

    def test_step_time_outlier(self):
        mon = self._mon(action="gauge", min_samples=5,
                        step_time_zscore=4.0)
        rng = np.random.RandomState(1)
        dts = [0.1 + 0.005 * abs(rng.randn()) for _ in range(15)] + [5.0]
        self._drive(mon, [(1.0, None)] * 16, dt=dts)
        assert self._anomalies(mon) == {"step_time_outlier": 1}

    def test_loss_plateau(self):
        mon = self._mon(action="gauge", plateau_window=5,
                        plateau_min_delta=1e-3)
        losses = [1.0 - 0.05 * i for i in range(10)] + [0.5] * 10
        self._drive(mon, [(l, None) for l in losses])
        assert self._anomalies(mon).get("loss_plateau", 0) >= 1

    def test_action_raise(self):
        mon = self._mon(action="raise")
        with pytest.raises(TrainingHealthError) as ei:
            self._drive(mon, [(float("nan"), None)])
        assert ei.value.kind == "non_finite_loss"

    def test_fit_injected_nan_batch(self):
        """Acceptance: an injected-NaN batch in a real Model.fit is
        flagged exactly once and training_healthy flips to 0."""
        reg = MetricsRegistry()
        mon = HealthMonitor(action="gauge", registry=reg, tracer=Tracer())
        model = _model()
        model.fit(Toy(16, bad_at=8), batch_size=4, epochs=1, verbose=0,
                  callbacks=[mon])
        snap = reg.snapshot()
        series = snap["training_anomalies_total"]["series"]
        by_kind = {s["labels"]["kind"]: s["value"] for s in series}
        # batch 2 goes non-finite, poisons the params, every later loss
        # is NaN too -> still ONE event (the condition stays active)
        assert by_kind["non_finite_loss"] == 1
        assert snap["training_healthy"]["value"]["current"] == 0

    def test_fit_reports_grad_norm(self):
        """HealthMonitor turns on grad-norm logging; the jitted step
        then reports a finite global gradient norm every batch."""
        seen = []

        class Spy(paddle.hapi.Callback):
            def on_train_batch_end(self, step, logs=None):
                seen.append((logs or {}).get("grad_norm"))

        mon = HealthMonitor(action="gauge", registry=MetricsRegistry(),
                            tracer=Tracer())
        model = _model()
        model.fit(Toy(8), batch_size=4, epochs=1, verbose=0,
                  callbacks=[mon, Spy()])
        assert len(seen) == 2
        assert all(g is not None and np.isfinite(g) and g > 0
                   for g in seen)
        assert mon.healthy


# ------------------------------------------------- health-triggered rollback


class _Arrays(Dataset):
    def __init__(self, x, y):
        self.x, self.y = x, y

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _rollback_problem(bad_batches=(5,), batch=4, n=32):
    """(poisoned dataset, reference dataset) — the reference simply has
    the poisoned batches' samples removed, which is exactly what a
    rollback + skipped-window run should be equivalent to."""
    rng = np.random.RandomState(7)
    y = rng.randint(0, 2, (n,)).astype(np.int64)
    x = (rng.randn(n, 4) * 0.3 + y[:, None] * 2.0).astype(np.float32)
    bad = x.copy()
    keep = np.ones(n, bool)
    for b in bad_batches:
        bad[b * batch:(b + 1) * batch] = np.nan
        keep[b * batch:(b + 1) * batch] = False
    return _Arrays(bad, y), _Arrays(x[keep], y[keep])


def _rb_model(seed=11):
    paddle.seed(seed)
    model = paddle.Model(nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                       nn.Linear(8, 2)))
    opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                    parameters=model.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    return model


class _Losses(paddle.hapi.Callback):
    def __init__(self):
        super().__init__()
        self.losses = []

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(logs["loss"])


def _rollback_count(reason):
    from paddle_tpu.observability import default_registry

    fam = default_registry().get("training_rollbacks_total")
    return fam.labels(reason=reason).value if fam else 0


class TestHealthRollback:
    def test_nan_batch_rolls_back_once_and_skips_window(self, tmp_path):
        """Acceptance: an injected-NaN batch triggers exactly one
        rollback to the last good checkpoint
        (training_rollbacks_total{reason="non_finite_loss"} == 1) and
        the continued loss curve past the skipped window equals a run
        that never saw the poisoned batch."""
        from paddle_tpu.hapi import CheckpointCallback
        from paddle_tpu.resilience import CheckpointManager

        data, ref_data = _rollback_problem(bad_batches=(5,))
        ref_rec = _Losses()
        _rb_model().fit(ref_data, batch_size=4, epochs=1, shuffle=False,
                        verbose=0,
                        callbacks=[ref_rec,
                                   HealthMonitor(action="gauge")])
        assert len(ref_rec.losses) == 7

        before = _rollback_count("non_finite_loss")
        rec = _Losses()
        mon = HealthMonitor(action="rollback")
        ckdir = str(tmp_path / "ck")
        _rb_model().fit(data, batch_size=4, epochs=1, shuffle=False,
                        verbose=0,
                        callbacks=[rec, mon,
                                   CheckpointCallback(ckdir,
                                                      every_n_steps=1)])
        assert len(rec.losses) == 8
        assert not np.isfinite(rec.losses[5])       # the poisoned step
        assert _rollback_count("non_finite_loss") == before + 1
        assert mon.rollbacks == 1
        assert mon.healthy                           # recovered
        # pre-window and post-window segments line up with the
        # never-saw-that-batch reference, step for step
        np.testing.assert_allclose(rec.losses[:5], ref_rec.losses[:5],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(rec.losses[6:], ref_rec.losses[5:],
                                   rtol=1e-5, atol=1e-6)
        # the skipped window is durable in the newest manifest
        _, _, manifest = CheckpointManager(ckdir).restore()
        windows = manifest["extra"]["skipped_windows"]
        assert len(windows) == 1
        w = windows[0]
        assert w["reason"] == "non_finite_loss"
        assert (w["first_step"], w["last_step"]) == (5, 5)
        assert w["restored_global_step"] == 5
        # the rollback left a supervisor::rollback span in the recorder
        from paddle_tpu.observability import default_tracer

        names = [t["name"] for t in default_tracer().traces()]
        assert "supervisor::rollback" in names

    @pytest.mark.faultinject
    def test_kill_right_after_rollback_resumes_past_window(self,
                                                           tmp_path):
        """The skipped window is committed the instant the rollback
        happens: a process killed immediately after must resume PAST
        the poisoned batch — never replay it, never re-anomaly."""
        from paddle_tpu.hapi import CheckpointCallback
        from paddle_tpu.resilience import (CheckpointManager, FaultSpec,
                                           SimulatedCrash,
                                           injected_faults)

        data, ref_data = _rollback_problem(bad_batches=(5,))
        ref_rec = _Losses()
        _rb_model().fit(ref_data, batch_size=4, epochs=1, shuffle=False,
                        verbose=0,
                        callbacks=[ref_rec,
                                   HealthMonitor(action="gauge")])

        ckdir = str(tmp_path / "ck")
        rec_a = _Losses()
        with injected_faults(FaultSpec("hapi.train_step", "kill",
                                       occurrence=6)):
            with pytest.raises(SimulatedCrash):
                _rb_model().fit(
                    data, batch_size=4, epochs=1, shuffle=False,
                    verbose=0,
                    callbacks=[rec_a, HealthMonitor(action="rollback"),
                               CheckpointCallback(ckdir,
                                                  every_n_steps=1)])
        assert len(rec_a.losses) == 6       # killed at the bad step

        rec_b = _Losses()
        mon_b = HealthMonitor(action="rollback")
        _rb_model(seed=99).fit(
            data, batch_size=4, epochs=1, shuffle=False, verbose=0,
            callbacks=[rec_b, mon_b,
                       CheckpointCallback(ckdir, every_n_steps=1)],
            resume_from=ckdir)
        assert len(rec_b.losses) == 2       # batches 6 and 7 only
        assert mon_b.events == []           # the bad batch never replayed
        np.testing.assert_allclose(rec_b.losses, ref_rec.losses[5:],
                                   rtol=1e-5, atol=1e-6)
        # the window survives the relaunch's own manifests
        _, _, manifest = CheckpointManager(ckdir).restore()
        assert len(manifest["extra"]["skipped_windows"]) == 1

    def test_rollback_without_checkpoint_callback_raises(self):
        data, _ = _rollback_problem(bad_batches=(2,), n=16)
        with pytest.raises(TrainingHealthError) as ei:
            _rb_model().fit(data, batch_size=4, epochs=1, shuffle=False,
                            verbose=0,
                            callbacks=[HealthMonitor(action="rollback")])
        assert ei.value.kind == "non_finite_loss"
        assert "CheckpointCallback" in str(ei.value)

    def test_max_rollbacks_escalates(self, tmp_path):
        """Two poisoned batches with max_rollbacks=1: the first rolls
        back, the second escalates — a run that keeps needing rewinds
        must die loudly, not thrash forever."""
        from paddle_tpu.hapi import CheckpointCallback

        data, _ = _rollback_problem(bad_batches=(2, 5))
        mon = HealthMonitor(action="rollback", max_rollbacks=1)
        with pytest.raises(TrainingHealthError):
            _rb_model().fit(
                data, batch_size=4, epochs=1, shuffle=False, verbose=0,
                callbacks=[mon,
                           CheckpointCallback(str(tmp_path / "ck"),
                                              every_n_steps=1)])
        assert mon.rollbacks == 2

    def test_grad_spike_requests_rollback(self):
        """Unit: a grad-norm outlier under action='rollback' files a
        rollback request on the model (the fit loop executes it)."""
        class Stub:
            _rollback_request = None

        mon = HealthMonitor(action="rollback", min_samples=5, window=20,
                            registry=MetricsRegistry(), tracer=Tracer(),
                            clock=ManualClock())
        mon.set_model(Stub())
        mon.on_train_begin()
        rng = np.random.RandomState(0)
        for i in range(15):
            mon.on_train_batch_begin(i)
            mon.on_train_batch_end(
                i, {"loss": 1.0, "grad_norm": 1.0 + 0.05 * rng.randn()})
        mon.on_train_batch_begin(15)
        mon.on_train_batch_end(15, {"loss": 1.0, "grad_norm": 50.0})
        req = mon.model._rollback_request
        assert req is not None and req["reason"] == "grad_spike"
        assert mon.rollbacks == 1


# ------------------------------------------------------ cross-rank merge


def _rank_registry(rank, step_time):
    reg = MetricsRegistry()
    h = reg.histogram("training_step_seconds")
    for _ in range(8):
        h.observe(step_time)
    reg.counter("steps_done_total").inc(8)
    reg.gauge("training_goodput_ratio").set(0.9 - 0.1 * rank)
    return reg


class TestCrossRankAggregation:
    STEP_TIMES = {0: 0.10, 1: 0.12, 2: 1.0}    # rank 2 is the straggler

    def _publish_from_threads(self, master):
        """3 simulated ranks, each a thread with its own TCPStore
        client, publish their registry snapshots."""
        errs = []

        def worker(rank):
            try:
                from paddle_tpu.distributed.store import TCPStore

                st = TCPStore(port=master.port, is_master=False,
                              world_size=3)
                reg = _rank_registry(rank, self.STEP_TIMES[rank])
                RankMetricsPublisher(st, rank, registry=reg).publish()
            except Exception as e:      # pragma: no cover
                errs.append((rank, e))

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert errs == []

    def test_merged_exposition_and_skew(self):
        from paddle_tpu.distributed.store import TCPStore

        master = TCPStore(is_master=True, world_size=3)
        self._publish_from_threads(master)
        local = MetricsRegistry()
        agg = ClusterAggregator(master, world_size=3, registry=local)
        text = agg.expose_prometheus()
        # every series carries its rank label
        for r in range(3):
            assert f'steps_done_total{{rank="{r}"}} 8' in text
        assert 'training_goodput_ratio{rank="1"} 0.8' in text
        # histograms travel as summaries
        assert 'training_step_seconds{rank="2",quantile="0.5"} 1' in text
        assert 'training_step_seconds_count{rank="0"} 8' in text
        # straggler skew: rank 2 at 1.0s vs rank 0 at 0.10s
        assert agg.last_skew_s == pytest.approx(0.9, rel=1e-6)
        assert local.get("training_step_time_skew_seconds").value == \
            pytest.approx(0.9, rel=1e-6)
        assert "training_step_time_skew_seconds 0.9" in text
        assert "cluster_ranks_reporting 3" in text
        snap = agg.merged_snapshot(collect=False)
        assert set(snap["ranks"]) == {"0", "1", "2"}
        assert snap["step_time_skew_seconds"] == \
            pytest.approx(0.9, rel=1e-6)

    def test_killed_rank_ages_out(self):
        from paddle_tpu.distributed.store import TCPStore

        master = TCPStore(is_master=True, world_size=3)
        clk = ManualClock(t=1000.0)
        pubs = [RankMetricsPublisher(
                    master, r, registry=_rank_registry(r, 0.1), clock=clk)
                for r in range(3)]
        for p in pubs:
            p.publish()
        agg = ClusterAggregator(master, world_size=3, stale_after_s=30.0,
                                registry=MetricsRegistry(), clock=clk)
        assert set(agg.collect()) == {0, 1, 2}
        # rank 2 dies; 0 and 1 keep publishing past the staleness window
        clk.t += 60.0
        pubs[0].publish()
        pubs[1].publish()
        fresh = agg.collect()
        assert set(fresh) == {0, 1}
        assert agg.stale_ranks == [2]
        text = agg.expose_prometheus(collect=False)
        assert 'rank="2"' not in text      # aged out, not poisoning
        assert 'steps_done_total{rank="0"} 8' in text
        assert "cluster_ranks_stale 1" in text

    def test_missing_rank_never_published(self):
        from paddle_tpu.distributed.store import TCPStore

        master = TCPStore(is_master=True, world_size=2)
        RankMetricsPublisher(master, 0,
                             registry=_rank_registry(0, 0.1)).publish()
        agg = ClusterAggregator(master, world_size=2,
                                registry=MetricsRegistry())
        assert set(agg.collect()) == {0}
        assert agg.missing_ranks == [1]
        assert agg.last_skew_s is None      # one rank -> no skew

    def test_fleet_metrics_endpoint(self):
        """Acceptance: rank 0's /metrics serves the merged fleet view."""
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.observability import start_telemetry_server

        master = TCPStore(is_master=True, world_size=3)
        self._publish_from_threads(master)
        local = MetricsRegistry()
        agg = ClusterAggregator(master, world_size=3, registry=local)
        srv = start_telemetry_server(port=0, registry=local,
                                     tracer=Tracer(), aggregator=agg)
        try:
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=10) as r:
                body = r.read().decode()
            assert 'steps_done_total{rank="1"} 8' in body
            assert "training_step_time_skew_seconds" in body
            with urllib.request.urlopen(srv.url + "/varz",
                                        timeout=10) as r:
                varz = json.loads(r.read().decode())
            assert set(varz["cluster"]["ranks"]) == {"0", "1", "2"}
        finally:
            srv.stop()

    def test_publisher_thread_republishes(self):
        from paddle_tpu.distributed.store import TCPStore

        master = TCPStore(is_master=True, world_size=1)
        pub = RankMetricsPublisher(master, 0,
                                   registry=_rank_registry(0, 0.1))
        with pub.start(interval_s=0.01):
            deadline = time.time() + 10
            while pub.published < 3 and time.time() < deadline:
                time.sleep(0.01)
        assert pub.published >= 3
        payload = json.loads(master.get("metrics/rank_0"))
        assert payload["rank"] == 0
        assert "training_step_seconds" in payload["metrics"]


# -------------------------------------------------------- stat bridge


class TestStatBridge:
    def test_stats_appear_on_scrape(self):
        from paddle_tpu.utils.monitor import StatRegistry, bridge_to_metrics

        sr = StatRegistry()
        mr = MetricsRegistry()
        collector = bridge_to_metrics(sr, mr)
        assert mr.snapshot() == {}          # nothing to bridge yet
        sr.add("pool_alloc", 5)
        sr.add("pool_alloc", -2)            # peak 5, current 3
        sr.add("host_buffers", 1)
        snap = mr.snapshot()
        series = {s["labels"]["name"]: s["value"]
                  for s in snap["runtime_stat"]["series"]}
        assert series["pool_alloc"]["current"] == 3
        assert series["pool_alloc"]["peak"] == 5
        assert series["host_buffers"]["current"] == 1
        text = mr.expose_prometheus()
        assert 'runtime_stat{name="pool_alloc"} 3' in text
        assert 'runtime_stat_peak{name="pool_alloc"} 5' in text
        mr.remove_collector(collector)

    def test_default_bridge_installed(self):
        from paddle_tpu.observability import default_registry
        from paddle_tpu.utils import stat_add, stat_reset

        stat_reset()
        stat_add("bridge_check", 7)
        try:
            snap = default_registry().snapshot()
            series = {s["labels"]["name"]: s["value"]
                      for s in snap["runtime_stat"]["series"]}
            assert series["bridge_check"]["current"] == 7
        finally:
            stat_reset()

    def test_broken_collector_does_not_break_scrape(self):
        mr = MetricsRegistry()
        mr.gauge("ok_gauge").set(1)

        def broken():
            raise RuntimeError("bridge died")

        mr.add_collector(broken)
        snap = mr.snapshot()                # must not raise
        assert snap["ok_gauge"]["value"]["current"] == 1
        mr.remove_collector(broken)


# ------------------------------------------------------ naming lint


class TestUnitSuffixLint:
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

    def test_unit_suffix_rules(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "from paddle_tpu.observability import Gauge, Histogram\n"
            "a = Histogram('request_latency_ms')\n"   # abbreviated unit
            "b = Histogram('step_time')\n"            # no unit suffix
            "c = Gauge('drain_s')\n"                  # abbreviated unit
            "d = Gauge('queue_depth')\n"              # unitless gauge: ok
            "e = Histogram('load_seconds')\n"         # canonical: ok
            "f = Gauge('mem_bytes')\n")               # canonical: ok
        violations = self._tool().check(root=str(tmp_path))
        text = "\n".join(violations)
        assert "request_latency_ms" in text
        assert "step_time" in text and "canonical unit suffix" in text
        assert "drain_s" in text
        assert "queue_depth" not in text
        assert "load_seconds" not in text
        assert "mem_bytes" not in text
        assert len(violations) == 3

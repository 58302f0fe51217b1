"""Silent-corruption sentinel tests: parameter-tree fingerprints and
the cross-rank compare, sampled step-replay verification, the bitflip
fault kind, audit-on-save, the param_divergence rewind-and-replay
repair, exporter integration, and the silent-except lint."""
import importlib.util
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.hapi import CheckpointCallback, IntegrityCallback
from paddle_tpu.io import Dataset
from paddle_tpu.observability import (HealthMonitor, MetricsRegistry,
                                      Tracer, default_registry,
                                      start_telemetry_server)
from paddle_tpu.resilience import (CheckpointAuditError,
                                   CheckpointManager, FaultSpec,
                                   SimulatedCrash, injected_faults)
from paddle_tpu.resilience.faults import fault_point
from paddle_tpu.resilience.integrity import (compare_digests,
                                             first_divergent_leaf,
                                             majority_partition,
                                             shard_fingerprint,
                                             tree_fingerprint)

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ------------------------------------------------------------ fingerprints


class TestTreeFingerprint:
    def test_leaf_paths_and_stability(self):
        tree = {"a": {"w": np.arange(4, dtype=np.float32)},
                "b": [np.ones(2, np.int32), None, 7]}
        fp = tree_fingerprint(tree)
        assert set(fp) == {"a/w", "b/0", "b/2"}    # None leaf skipped
        assert fp == tree_fingerprint(tree)        # deterministic

    def test_shape_and_dtype_ride_in_the_digest(self):
        flat = np.zeros(4, np.float32)
        assert tree_fingerprint({"x": flat}) != \
            tree_fingerprint({"x": flat.reshape(2, 2)})
        assert tree_fingerprint({"x": np.zeros(4, np.float32)}) != \
            tree_fingerprint({"x": np.zeros(8, np.float16)})

    def test_one_bit_changes_the_leaf_digest(self):
        a = np.arange(64, dtype=np.float32)
        b = a.copy()
        b.view(np.uint8)[17] ^= 1
        fa, fb = tree_fingerprint({"w": a}), tree_fingerprint({"w": b})
        assert fa["w"] != fb["w"]
        assert first_divergent_leaf(fa, fb) == "w"

    def test_first_divergent_leaf_counts_missing(self):
        assert first_divergent_leaf({"a": 1, "b": 2}, {"a": 1}) == "b"
        assert first_divergent_leaf({"a": 1}, {"a": 1}) is None

    def test_majority_partition_and_tie_anchor(self):
        good = {"w": 1}
        bad = {"w": 2}
        maj, mino, d = majority_partition({0: good, 1: bad, 2: good})
        assert (maj, mino, d) == ([0, 2], [1], good)
        # 1-vs-1 tie anchors to the group holding the lowest rank
        maj, mino, _ = majority_partition({0: good, 1: bad})
        assert (maj, mino) == ([0], [1])

    def test_compare_digests(self):
        good = {"w": 1, "b": 5}
        bad = {"w": 2, "b": 5}
        assert compare_digests({0: good, 1: good}) is None
        assert compare_digests({0: good}) is None      # nothing to compare
        rep = compare_digests({0: good, 1: bad, 2: good})
        assert rep["divergent_ranks"] == [1]
        assert rep["majority_ranks"] == [0, 2]
        assert rep["first_divergent_leaf"] == {1: "w"}


class TestShardFingerprint:
    """GSPMD shard-view fingerprints on a 2x2 (dp x mp) mesh — the
    multi-chip regression the ROADMAP asked for: the sentinel digests
    each rank's ADDRESSABLE shards and compares only within dp replica
    groups (mp peers hold different windows and legitimately differ)."""

    def _mesh_tree(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.distributed import mesh as mesh_mod

        mesh = mesh_mod.build_mesh(dp=2, mp=2)
        w = jax.device_put(
            np.arange(64, dtype=np.float32).reshape(8, 8),
            NamedSharding(mesh, P(None, "mp")))
        g = jax.device_put(np.ones(4, np.float32),
                           NamedSharding(mesh, P()))
        return mesh, {"w": w, "g": g}

    def _rank_devices(self, mesh):
        """One simulated process per mesh device of the (dp, mp) grid,
        rank = dp_idx * mp + mp_idx (build_mesh's row-major layout)."""
        grid = mesh.devices.reshape(2, 2)
        return {d * 2 + m: [grid[d, m]] for d in range(2)
                for m in range(2)}

    def test_window_keys_and_dedup(self):
        mesh, tree = self._mesh_tree()
        fp = shard_fingerprint(tree)
        # w: 2 distinct mp windows (dp replicas dedup); g: 1 window
        assert set(fp) == {"w@0:8,0:4", "w@0:8,4:8", "g@0:4"}
        assert fp == shard_fingerprint(tree)

    def test_dp_replicas_match_mp_peers_differ(self):
        from paddle_tpu.distributed.mesh import replica_peers

        mesh, tree = self._mesh_tree()
        devs = self._rank_devices(mesh)
        digests = {r: shard_fingerprint(tree, devices=devs[r])
                   for r in range(4)}
        # dp replicas (ranks differing only in dp coord) are bitwise
        # identical; mp neighbours hold DIFFERENT windows
        axes = {"dp": 2, "mp": 2}
        assert replica_peers(0, axes) == [0, 2]
        assert digests[0] == digests[2]
        assert digests[1] == digests[3]
        assert digests[0] != digests[1]
        # restricted to the dp replica group: no divergence
        assert compare_digests({r: digests[r]
                                for r in replica_peers(0, axes)}) is None
        assert compare_digests({r: digests[r]
                                for r in replica_peers(1, axes)}) is None
        # the naive all-ranks compare would false-positive — exactly
        # why the callback takes peers=
        assert compare_digests(digests) is not None

    def test_corrupt_shard_detected_within_replica_group(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, tree = self._mesh_tree()
        devs = self._rank_devices(mesh)
        bad = np.asarray(tree["w"]).copy()
        bad[3, 5] += 1e-3                    # lands in the mp=1 window
        tree_bad = {"w": jax.device_put(
            bad, NamedSharding(mesh, P(None, "mp"))), "g": tree["g"]}
        # rank 3 (dp=1, mp=1) computes from the corrupted state
        digests = {1: shard_fingerprint(tree, devices=devs[1]),
                   3: shard_fingerprint(tree_bad, devices=devs[3])}
        rep = compare_digests(digests)
        assert rep is not None
        leaf = list(rep["first_divergent_leaf"].values())[0]
        assert leaf == "w@0:8,4:8"           # names the exact window

    def test_callback_peers_restriction(self):
        """IntegrityCallback wired for the 2x2 mesh: rank 1 publishes
        its mp=1 shard view; rank 3 (its dp replica) sees a match while
        rank 0's digest — present in the store — is never consulted."""
        from paddle_tpu.hapi import IntegrityCallback

        mesh, tree = self._mesh_tree()
        devs = self._rank_devices(mesh)
        store = TCPStore(is_master=True, world_size=1)
        cbs = {}
        for r in (0, 1, 3):
            cb = IntegrityCallback(
                store=store, rank=r, world_size=4,
                fingerprint_every=1, peers=[r % 2, r % 2 + 2],
                fingerprint_shards=True, local_devices=devs[r],
                registry=MetricsRegistry())
            cb._fingerprint_tree = (
                lambda t=tree, rr=r: {"params": t})   # bypass model
            cb.model = None
            cbs[r] = cb
        for r in (0, 1, 3):
            cbs[r]._global_step = 1
            cbs[r]._run_fingerprint(step=0)
        assert cbs[3].divergence_active is False
        assert cbs[3].last_verified_global_step == 1
        assert cbs[1].events == [] and cbs[3].events == []


# --------------------------------------------------------- bitflip fault


def _flip_count(site):
    fam = default_registry().get("faults_injected_total")
    return fam.labels(site=site, kind="bitflip").value if fam else 0


class TestBitflipFault:
    def test_pinned_leaf_and_bit(self):
        orig = np.zeros(8, np.float32)
        tree = {"w": orig, "b": np.ones(2, np.float32)}
        before = _flip_count("t.tree")
        with injected_faults(FaultSpec("t.tree", "bitflip",
                                       leaf="w", bit=3)):
            fault_point("t.tree", tree=tree)
        flipped = np.asarray(tree["w"]).view(np.uint8)
        assert flipped[0] == 1 << 3
        assert flipped[1:].sum() == 0
        np.testing.assert_array_equal(tree["b"], np.ones(2, np.float32))
        # the caller's original array object is never mutated in place —
        # the injector swaps in a corrupted COPY (jax arrays are
        # immutable; the live-tree writeback is the call site's job)
        assert orig.view(np.uint8).sum() == 0
        assert _flip_count("t.tree") == before + 1

    def test_seed_deterministic_choice(self):
        def run():
            tree = {"a": np.zeros(16, np.float32),
                    "b": np.zeros(16, np.float32)}
            with injected_faults(FaultSpec("t.seed", "bitflip"), seed=5):
                fault_point("t.seed", tree=tree)
            return {k: np.asarray(v).tobytes() for k, v in tree.items()}

        one, two = run(), run()
        assert one == two
        assert sum(v != np.zeros(16, np.float32).tobytes()
                   for v in one.values()) == 1

    def test_missing_pinned_leaf_raises(self):
        with injected_faults(FaultSpec("t.miss", "bitflip", leaf="nope")):
            with pytest.raises(KeyError, match="nope"):
                fault_point("t.miss", tree={"w": np.ones(2)})

    def test_file_mode_flips_exactly_one_bit(self, tmp_path):
        p = tmp_path / "blob.bin"
        p.write_bytes(bytes(64))
        with injected_faults(FaultSpec("t.file", "bitflip", bit=9)):
            fault_point("t.file", path=str(p))
        data = p.read_bytes()
        assert data[1] == 1 << 1 and sum(data) == 2

    def test_directory_mode_flips_one_file(self, tmp_path):
        for name in ("a.bin", "b.bin"):
            (tmp_path / name).write_bytes(bytes(32))
        with injected_faults(FaultSpec("t.dir", "bitflip"), seed=0):
            fault_point("t.dir", path=str(tmp_path))
        changed = [n for n in ("a.bin", "b.bin")
                   if (tmp_path / n).read_bytes() != bytes(32)]
        assert len(changed) == 1
        blob = (tmp_path / changed[0]).read_bytes()
        assert bin(int.from_bytes(blob, "big")).count("1") == 1


# ------------------------------------------------------------- fit harness


class _Arrays(Dataset):
    def __init__(self, n=32):
        rng = np.random.RandomState(7)
        self.y = rng.randint(0, 2, (n,)).astype(np.int64)
        self.x = (rng.randn(n, 4) * 0.3
                  + self.y[:, None] * 2.0).astype(np.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class _Losses(paddle.hapi.Callback):
    def __init__(self):
        super().__init__()
        self.losses = []

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(logs["loss"])


def _model(seed=11):
    paddle.seed(seed)
    model = paddle.Model(nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                       nn.Linear(8, 2)))
    opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                    parameters=model.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    return model


def _params_bytes(model):
    return {k: np.asarray(p.data).tobytes()
            for k, p in model.network.named_parameters()}


def _fit(model, callbacks, data=None):
    model.fit(data or _Arrays(), batch_size=4, epochs=1, shuffle=False,
              verbose=0, callbacks=callbacks)


def _rollback_count(reason):
    fam = default_registry().get("training_rollbacks_total")
    return fam.labels(reason=reason).value if fam else 0


# ------------------------------------------------------------ step replay


class TestStepReplay:
    def test_clean_steps_replay_bitwise_identical(self):
        reg = MetricsRegistry()
        cb = IntegrityCallback(replay_every=3, fingerprint_every=0,
                               registry=reg, tracer=Tracer())
        _fit(_model(), [cb])
        assert cb.events == []
        assert cb.checks["replay"] == 2            # steps 3 and 6 of 8
        snap = reg.snapshot()
        assert snap["integrity_replay_seconds"]["value"]["count"] == 2

    def test_corrupted_step_caught_with_first_leaf_named(self):
        """A bitflip injected into the live step's post-update params
        makes the re-executed step disagree — the sentinel reports the
        first differing leaf (this is SDC or nondeterminism, depending
        on which execution you believe; either is a firing offense)."""
        reg = MetricsRegistry()
        mon = HealthMonitor(action="gauge", registry=MetricsRegistry(),
                            tracer=Tracer())
        cb = IntegrityCallback(replay_every=3, fingerprint_every=0,
                               monitor=mon, registry=reg,
                               tracer=Tracer())
        with injected_faults(FaultSpec("hapi.step_params", "bitflip",
                                       occurrence=3, leaf="0.weight",
                                       bit=21)):
            _fit(_model(), [cb, mon])
        assert len(cb.events) == 1
        ev = cb.events[0]
        assert ev["kind"] == "replay"
        assert ev["global_step"] == 3
        assert ev["first_divergent_leaf"] == "0.weight"
        fam = reg.get("integrity_divergence_total")
        assert fam.labels(kind="replay").value == 1
        # the monitor saw it as a (non-rollback) anomaly kind
        assert [k for k, _, _ in mon.events] == ["step_replay_mismatch"]


# --------------------------------------------- cross-rank fingerprints


class TestCrossRankDivergence:
    def _run_ranks(self, tmp_path, corrupt_rank=1, monitor_ranks=(),
                   world=3, occurrence=5, bit=17):
        """Sequential dp replicas sharing one TCPStore: identical seed,
        identical data, per-rank checkpoints.  ``corrupt_rank`` gets a
        bitflip injected into its post-step params at global step
        ``occurrence``.  Returns (callbacks, losses, final params,
        monitors) per rank."""
        store = TCPStore(is_master=True, world_size=1)
        cbs, losses, finals, mons = {}, {}, {}, {}
        for rank in range(world):
            reg = MetricsRegistry()
            mon = None
            if rank in monitor_ranks:
                mon = HealthMonitor(action="rollback", registry=reg,
                                    tracer=Tracer())
            cb = IntegrityCallback(store=store, rank=rank,
                                   world_size=world,
                                   fingerprint_every=2, history=1000,
                                   monitor=mon, registry=reg,
                                   tracer=Tracer())
            rec = _Losses()
            model = _model()
            ck = CheckpointCallback(str(tmp_path / f"ck{rank}"),
                                    every_n_steps=1)
            cblist = [rec, cb, ck] + ([mon] if mon else [])
            if rank == corrupt_rank:
                with injected_faults(
                        FaultSpec("hapi.step_params", "bitflip",
                                  occurrence=occurrence,
                                  leaf="0.weight", bit=bit)):
                    _fit(model, cblist)
            else:
                _fit(model, cblist)
            cbs[rank], losses[rank] = cb, rec.losses
            finals[rank], mons[rank] = _params_bytes(model), mon
        return cbs, losses, finals, mons

    def test_detection_names_rank_and_leaf(self, tmp_path):
        """Detect-only (no monitor): the divergent rank knows it
        diverged, from which leaf, and stays flagged unhealthy.  The flip
        lands on a step whose fingerprint is taken, so the flipped leaf
        is the only one that differs: one update later every leaf does,
        and the sentinel names the first in sorted order."""
        cbs, _, finals, _ = self._run_ranks(tmp_path, world=2,
                                            occurrence=6)
        assert cbs[0].events == []
        ev = cbs[1].events[0]
        assert ev["kind"] == "cross_rank"
        assert ev["divergent_ranks"] == [1]
        assert ev["first_divergent_leaf"] == {1: "params/0.weight"}
        assert ev["self_divergent"] is True
        assert ev["last_verified_global_step"] == 4    # fp at 2 and 4
        assert ev["global_step"] == 6       # corruption and fp at 6
        # no repair ran: the corruption persists and so does the flag
        assert cbs[1].divergence_active is True
        assert finals[0] != finals[1]

    def test_e2e_bitflip_detected_repaired_bitwise_equal(self, tmp_path):
        """Acceptance: a bitflip in one of 3 dp ranks' params is caught
        by the fingerprint compare within one sampling interval, the
        rank and leaf are named, rollback restores last-verified-good
        state, and the continued curve is bitwise-equal to the ranks
        that never saw the corruption."""
        before = _rollback_count("param_divergence")
        cbs, losses, finals, mons = self._run_ranks(
            tmp_path, monitor_ranks=(0, 1, 2))
        # healthy ranks: clean, and every fingerprint interval verified
        assert cbs[0].events == [] and cbs[2].events == []
        assert cbs[0].last_verified_global_step == 8
        # the divergent rank detected itself at the first fingerprint
        # after the step-5 corruption
        ev = cbs[1].events[0]
        assert ev["divergent_ranks"] == [1]
        # the flip went into 0.weight at step 5; by the fingerprint at
        # step 6 one update has carried it into the other leaves, and
        # the sentinel names the first that differs in sorted order: a
        # leaf of the corrupted layer, for the corrupted rank alone
        leaf = ev["first_divergent_leaf"]
        assert set(leaf) == {1} and leaf[1].startswith("params/0."), leaf
        assert _rollback_count("param_divergence") == before + 1
        # rewind-and-replay: steps 5 and 6 trained twice (8 + 2)
        assert len(losses[1]) == 10 and len(losses[0]) == 8
        # the replayed tail is BITWISE equal to the clean rank's curve
        assert losses[1][6:] == losses[0][4:]
        # ...and the final state reconverged bitwise, fleet-wide
        assert finals[1] == finals[0] == finals[2]
        assert cbs[1].divergence_active is False    # repaired + cleared
        assert mons[1].healthy
        # the repair is durable in the newest manifest
        _, _, man = CheckpointManager(str(tmp_path / "ck1")).restore()
        repairs = man["extra"]["repairs"]
        assert len(repairs) == 1
        assert repairs[0]["reason"] == "param_divergence"
        assert repairs[0]["restored_global_step"] == 4
        assert repairs[0]["rewind"] is True
        # no data was skipped — rewind repairs REPLAY, not drop
        assert "skipped_windows" not in man["extra"]

    def test_poisoned_newer_checkpoints_are_discarded(self, tmp_path):
        """Saves taken between corruption and detection verify clean
        (CRC-wise) but hold poisoned numbers — the repair must remove
        them so a crash mid-replay can't resume from one."""
        tracker = {}

        class _SpyMgr(CheckpointManager):
            def discard_after(self, step):
                tracker["steps_at_discard"] = self.steps()
                removed = super().discard_after(step)
                tracker["removed"] = removed
                return removed

        store = TCPStore(is_master=True, world_size=1)

        def rank(r, faults=None):
            reg = MetricsRegistry()
            mon = HealthMonitor(action="rollback", registry=reg,
                                tracer=Tracer())
            cb = IntegrityCallback(store=store, rank=r, world_size=2,
                                   fingerprint_every=2, history=1000,
                                   monitor=mon, registry=reg,
                                   tracer=Tracer())
            ck = CheckpointCallback(
                manager=_SpyMgr(str(tmp_path / f"ck{r}")),
                every_n_steps=1)
            model = _model()
            if faults:
                with injected_faults(faults):
                    _fit(model, [cb, ck, mon])
            else:
                _fit(model, [cb, ck, mon])
            return ck

        rank(0)
        rank(1, FaultSpec("hapi.step_params", "bitflip",
                          occurrence=5, leaf="0.weight", bit=17))
        # at discard time the poisoned step-5/6 saves existed (intact
        # CRC-wise — they'd win any restore walk)...
        assert tracker["steps_at_discard"][-2:] == [5, 6]
        # ...and the repair removed exactly them, keeping 4
        assert tracker["removed"] == [5, 6]


# ------------------------------------------------------- audit-on-save


_TREE = {"w": np.arange(4096, dtype=np.float32),
         "b": np.ones(8, np.float32)}


@pytest.mark.faultinject
class TestAuditOnSave:
    def test_bitflip_after_commit_fails_audit_old_kept(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1)
        mgr.save(_TREE, step=1)
        with injected_faults(FaultSpec("checkpoint.after_commit",
                                       "bitflip"), seed=0):
            with pytest.raises(CheckpointAuditError) as ei:
                mgr.save(_TREE, step=2, verify=True)
        assert ei.value.step == 2
        # retention GC did NOT run: the good step-1 save survives and
        # restore falls back to it
        assert mgr.steps() == [1, 2]
        step, tree, _ = CheckpointManager(str(tmp_path)).restore()
        assert step == 1
        np.testing.assert_array_equal(tree["w"], _TREE["w"])

    def test_without_verify_corrupt_save_becomes_only_candidate(
            self, tmp_path):
        """The hazard the audit closes: same fault, verify off — the
        corrupted save completes, GC removes the good one, and nothing
        restorable remains."""
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1)
        mgr.save(_TREE, step=1)
        with injected_faults(FaultSpec("checkpoint.after_commit",
                                       "bitflip"), seed=0):
            mgr.save(_TREE, step=2)              # silent
        assert mgr.steps() == [2]
        with pytest.raises(FileNotFoundError):
            CheckpointManager(str(tmp_path)).restore()

    def test_torn_write_after_commit_old_kept(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1)
        mgr.save(_TREE, step=1)
        with injected_faults(FaultSpec("checkpoint.after_commit",
                                       "torn_write"), seed=1):
            with pytest.raises(SimulatedCrash):
                mgr.save(_TREE, step=2, verify=True)
        step, _, _ = CheckpointManager(str(tmp_path)).restore()
        assert step == 1

    def test_async_audit_failure_surfaces_from_wait(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1,
                                async_save=True, verify_on_save=True)
        mgr.save(_TREE, step=1)
        mgr.wait()
        with injected_faults(FaultSpec("checkpoint.after_commit",
                                       "bitflip"), seed=0):
            mgr.save(_TREE, step=2)
            with pytest.raises(CheckpointAuditError):
                mgr.wait()
        assert mgr.steps() == [1, 2]

    def test_clean_save_passes_audit(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last_n=1,
                                verify_on_save=True)
        mgr.save(_TREE, step=1)
        mgr.save(_TREE, step=2)
        assert mgr.steps() == [2]                # GC ran normally

    def test_discard_after(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        for s in range(1, 6):
            mgr.save(_TREE, step=s)
        assert mgr.discard_after(2) == [3, 4, 5]
        assert mgr.steps() == [1, 2] and mgr.latest() == 2


# -------------------------------------------------- exporter endpoints


class TestIntegrityEndpoints:
    def test_integrity_endpoint_serves_report(self):
        reg = MetricsRegistry()
        cb = IntegrityCallback(rank=3, world_size=8,
                               fingerprint_every=25, registry=reg,
                               tracer=Tracer())
        srv = start_telemetry_server(port=0, registry=reg,
                                     tracer=Tracer(), integrity=cb)
        try:
            code, body = _get(srv.url + "/integrity")
            assert code == 200
            rep = json.loads(body)
            assert rep["rank"] == 3 and rep["world_size"] == 8
            assert rep["divergence_active"] is False
        finally:
            srv.stop()

    def test_integrity_404_without_sentinel(self):
        srv = start_telemetry_server(port=0, registry=MetricsRegistry(),
                                     tracer=Tracer())
        try:
            code, _ = _get(srv.url + "/integrity")
            assert code == 404
        finally:
            srv.stop()

    def test_healthz_folds_divergence_both_states(self):
        reg = MetricsRegistry()
        cb = IntegrityCallback(registry=reg, tracer=Tracer())
        srv = start_telemetry_server(port=0, registry=reg,
                                     tracer=Tracer(), integrity=cb)
        try:
            code, body = _get(srv.url + "/healthz")
            assert code == 200
            assert json.loads(body)["integrity_divergence_active"] is \
                False
            cb.divergence_active = True
            code, body = _get(srv.url + "/healthz")
            health = json.loads(body)
            assert code == 503
            assert health["healthy"] is False
            assert health["integrity_divergence_active"] is True
            cb.divergence_active = False         # repair reconverged
            code, _ = _get(srv.url + "/healthz")
            assert code == 200
        finally:
            srv.stop()

    def test_healthz_gauge_fallback_without_callback(self):
        """A multiprocess deployment folds the gauge instead of the
        in-process object."""
        reg = MetricsRegistry()
        reg.gauge("integrity_divergence_active", "t").set(1)
        srv = start_telemetry_server(port=0, registry=reg,
                                     tracer=Tracer())
        try:
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            assert json.loads(body)["integrity_divergence_active"] is \
                True
        finally:
            srv.stop()


# -------------------------------------------- supervisor relaunch evidence


class TestSupervisorEvidence:
    def test_resume_evidence_carries_repairs_and_windows(self, tmp_path):
        from paddle_tpu.resilience import TrainingSupervisor

        mgr = CheckpointManager(str(tmp_path))
        mgr.save(_TREE, step=7, extra={
            "global_step": 7,
            "repairs": [{"reason": "param_divergence",
                         "restored_global_step": 4}],
            "skipped_windows": [{"reason": "non_finite_loss",
                                 "first_step": 2, "last_step": 2}],
        })
        sup = TrainingSupervisor(cmd=["true"],
                                 checkpoint_dir=str(tmp_path))
        ev = sup._resume_evidence()
        assert ev["resume_step"] == 7
        assert ev["integrity_repairs"] == 1
        assert ev["last_repair_reason"] == "param_divergence"
        assert ev["skipped_windows"] == 1
        assert ev["last_rollback_reason"] == "non_finite_loss"

    def test_resume_evidence_plain_checkpoint(self, tmp_path):
        from paddle_tpu.resilience import TrainingSupervisor

        CheckpointManager(str(tmp_path)).save(_TREE, step=3)
        sup = TrainingSupervisor(cmd=["true"],
                                 checkpoint_dir=str(tmp_path))
        assert sup._resume_evidence() == {"resume_step": 3}


# -------------------------------------------------- silent-excepts lint


class TestExceptsLint:
    # the repo-wide sweep now runs ONCE in the consolidated suite:
    # tests/test_static_analysis.py::TestTier1Suite

    def test_lint_catches_planted_violations(self, tmp_path):
        mod = _load_tool("check_excepts")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            "import logging\n"
            "def f():\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        pass\n"                       # naked swallow
            "    try:\n"
            "        work()\n"
            "    except:\n"                        # bare except
            "        ...\n"
            "    for _ in y:\n"
            "        try:\n"
            "            work()\n"
            "        except (ValueError, Exception):\n"
            "            continue\n"               # broad via tuple
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        pass    # silent-ok:\n")      # marker w/o a reason
        out = mod.check(root=str(pkg))
        assert len(out) == 4
        assert all("mod.py" in o for o in out)

    def test_allowed_forms_pass(self, tmp_path):
        mod = _load_tool("check_excepts")
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "ok.py").write_text(
            "import logging\n"
            "def f():\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        pass    # silent-ok: cleanup may race shutdown\n"
            "    try:\n"
            "        work()\n"
            "    except KeyError:\n"               # narrow: fine
            "        pass\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        logging.exception('boom')\n"  # logs: fine
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        raise\n")                     # re-raises: fine
        assert mod.check(root=str(pkg)) == []


# ------------------------------------------------------ overhead smoke


def _sentinel_costs(steps=10, fp_reps=5, replay_reps=3, hidden=1024,
                    batch=128):
    """``(train step, parameter-tree fingerprint, sampled step replay)``
    in seconds, each a median, for a three-layer MLP of ``hidden``
    (2.1 M parameters, 8 MB).  Host clock on the CPU."""
    import time

    from paddle_tpu.core.random import get_rng_state

    def timed(fn, *args):
        t0 = time.perf_counter()
        fn(*args)
        return time.perf_counter() - t0

    paddle.seed(0)
    model = paddle.Model(nn.Sequential(
        nn.Linear(hidden, hidden), nn.ReLU(),
        nn.Linear(hidden, hidden), nn.ReLU(), nn.Linear(hidden, 10)))
    opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                    parameters=model.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    x = rng.randn(batch, hidden).astype(np.float32)
    y = rng.randint(0, 10, (batch,)).astype(np.int64)

    model.train_batch(x, y)                  # compile outside the clock
    step_s = float(np.median([timed(model.train_batch, x, y)
                              for _ in range(steps)]))
    params, buffers = model.network.raw_state()
    tree = {"params": dict(params)}
    tree_fingerprint(tree)                   # warm the digest path
    fp_s = float(np.median([timed(tree_fingerprint, tree)
                            for _ in range(fp_reps)]))
    snapshot = {"params": dict(params), "buffers": dict(buffers),
                "opt_state": model._opt_state,
                "rng": dict(get_rng_state()), "lr": float(opt.get_lr())}
    model.replay_train_batch(snapshot, (x, y))
    replay_s = float(np.median(
        [timed(model.replay_train_batch, snapshot, (x, y))
         for _ in range(replay_reps)]))
    return step_s, fp_s, replay_s


class TestSentinelOverheadSmoke:
    def test_amortized_overhead_under_bound(self):
        """Acceptance: fingerprint + replay cost, amortized over their
        default sampling intervals (every 25 and every 100 steps), stays
        under 3% of the step's time.  A ratio of host times on the CPU;
        not measured on the chip."""
        step_s, fp_s, replay_s = _sentinel_costs()
        assert (fp_s / 25 + replay_s / 100) / step_s < 0.03, (
            step_s, fp_s, replay_s)
        # fingerprints must stay cheap in absolute terms too: digesting
        # ~8MB of params is milliseconds, not a second
        assert fp_s < 0.2, fp_s

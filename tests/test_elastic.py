"""Elastic manager + launcher relaunch tests (reference strategy:
test_fleet_elastic_manager.py mocks etcd; here the membership store is
the framework's real native TCPStore)."""
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.distributed.fleet.elastic import (ELASTIC_EXIT_CODE,
                                                  ElasticManager)
from paddle_tpu.distributed.store import TCPStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestElasticManager:
    def test_register_and_probe(self):
        store = TCPStore(is_master=True, world_size=2)
        a = ElasticManager(store, job_id="j1", np=2, host="nodeA",
                           heartbeat_interval=0.1, node_timeout=0.5)
        b = ElasticManager(store, job_id="j1", np=2, host="nodeB",
                           heartbeat_interval=0.1, node_timeout=0.5)
        a.register()
        b.register()
        assert a.probe("nodeA") and a.probe("nodeB")
        assert a.match(["nodeA", "nodeB"])
        a.deregister()
        b.deregister()

    def test_watch_detects_lost_node(self):
        store = TCPStore(is_master=True, world_size=2)
        a = ElasticManager(store, job_id="j2", np=2, host="nodeA",
                           heartbeat_interval=0.1, node_timeout=0.4)
        b = ElasticManager(store, job_id="j2", np=2, host="nodeB",
                           heartbeat_interval=0.1, node_timeout=0.4)
        a.register()
        b.register()
        assert a.wait_for_np(["nodeA", "nodeB"], timeout=5)
        b.deregister()   # node B dies
        event, dead = a.watch(["nodeA", "nodeB"], timeout=5)
        assert event == "lost" and dead == ["nodeB"]
        a.deregister()

    def test_stale_heartbeat_counts_as_dead(self):
        # liveness = the per-node counter keeps ADVANCING; a node whose
        # counter stalls for > node_timeout (of the READER's monotonic
        # clock — wall clocks never cross hosts) probes dead
        store = TCPStore(is_master=True, world_size=1)
        a = ElasticManager(store, job_id="j3", np=1, host="nodeA",
                           heartbeat_interval=10.0, node_timeout=0.3)
        store.add("elastic/j3/nodeA", 1)       # one beat, then silence
        assert a.probe("nodeA")                # first sighting: alive
        time.sleep(0.4)
        assert not a.probe("nodeA")            # counter never advanced

    def test_relaunch_not_fooled_by_stale_counter(self):
        # a freshly-constructed manager (empty _seen, e.g. right after
        # a relaunch) must NOT wait_for_np-succeed on a crashed peer
        # whose counter merely exists
        store = TCPStore(is_master=True, world_size=2)
        dead = ElasticManager(store, job_id="j5", np=2, host="deadB",
                              heartbeat_interval=0.1, node_timeout=0.3)
        store.add("elastic/j5/deadB", 1)   # B beat once, then crashed
        live = ElasticManager(store, job_id="j5", np=2, host="nodeA",
                              heartbeat_interval=0.1, node_timeout=0.3)
        live.register()
        fresh = ElasticManager(store, job_id="j5", np=2, host="nodeA",
                               heartbeat_interval=0.1, node_timeout=0.3)
        assert not fresh.wait_for_np(["nodeA", "deadB"], timeout=1.5)
        live.deregister()

    def test_never_registered_is_dead(self):
        store = TCPStore(is_master=True, world_size=1)
        a = ElasticManager(store, job_id="j4", np=1, host="nodeA",
                           heartbeat_interval=0.1, node_timeout=0.5)
        assert not a.probe("ghost")


WORKER_ELASTIC = """
import os, sys
marker = os.path.join({tmp!r}, "attempt.flag")
attempt = int(os.environ["PADDLE_RESTART_ATTEMPT"])
rank = int(os.environ["PADDLE_TRAINER_ID"])
print(f"run rank={{rank}} attempt={{attempt}}")
if attempt == 0 and rank == 1:
    sys.exit({code})   # request relaunch
print(f"DONE rank={{rank}} attempt={{attempt}}")
"""


class TestLauncherRestart:
    def _launch(self, tmp_path, max_restarts, code=101):
        script = tmp_path / "w.py"
        script.write_text(WORKER_ELASTIC.format(tmp=str(tmp_path),
                                                code=code))
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "XLA_", "JAX_"))}
        env["PYTHONPATH"] = REPO
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--max_restarts", str(max_restarts),
             "--log_dir", str(tmp_path / "logs"), str(script)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
        logs = {f.name: f.read_text()
                for f in sorted((tmp_path / "logs").iterdir())}
        return proc, logs

    def test_relaunch_after_elastic_exit(self, tmp_path):
        proc, logs = self._launch(tmp_path, max_restarts=1,
                                  code=ELASTIC_EXIT_CODE)
        assert proc.returncode == 0, (proc.stdout, proc.stderr, logs)
        assert "restart attempt 1" in logs["workerlog.1"]
        assert "DONE rank=1 attempt=1" in logs["workerlog.1"]
        assert "DONE rank=0 attempt=1" in logs["workerlog.0"]

    def test_no_restart_budget_fails(self, tmp_path):
        proc, _ = self._launch(tmp_path, max_restarts=0,
                               code=ELASTIC_EXIT_CODE)
        assert proc.returncode == ELASTIC_EXIT_CODE

    def test_tpu_backend_refuses_processes_per_chip(self, tmp_path):
        """A chip belongs to one process: --backend tpu with more than
        one process per node must say so, not start children that hang."""
        from paddle_tpu.distributed.launch import launch

        with pytest.raises(SystemExit, match="ONE process per host"):
            launch(["--backend", "tpu", "--nproc_per_node", "2",
                    "--log_dir", str(tmp_path / "logs"), "unused.py"])
        assert not (tmp_path / "logs").exists()      # nothing was started

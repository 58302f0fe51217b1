"""Launcher implementation (see package docstring for the env contract)."""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a distributed training script, one process per "
                    "host/worker (reference: paddle.distributed.launch)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes to fork on this node (on a TPU "
                        "host: 1 — one process drives all its chips)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--master", default=None,
                   help="coordinator host:port (default: local free port)")
    p.add_argument("--log_dir", default="log",
                   help="directory for per-rank workerlog.N files")
    p.add_argument("--backend", default=None,
                   choices=[None, "tpu", "gloo"],
                   help="'gloo' runs workers on CPU devices (testing)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic relaunch budget: restart the pod when a "
                        "worker exits with ELASTIC_EXIT_CODE (101) or "
                        "crashes, up to this many times (reference: "
                        "fleet/elastic relaunch policy)")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch(argv=None):
    """Fork nproc_per_node workers with the rank env contract, stream each
    worker's output to ``<log_dir>/workerlog.<rank>``, watch them, and
    propagate the first failure (terminating the rest) — the reference's
    Controller.watch() policy (controllers/controller.py:67)."""
    args = _parse(argv if argv is not None else sys.argv[1:])
    nproc = args.nproc_per_node
    if args.backend == "tpu" and nproc > 1:
        # a chip belongs to one process: every child would open every
        # chip of the host, and all but the first fail there ("Unable to
        # initialize backend 'tpu': ABORTED ... libtpu multi-process
        # lockfile", seen on a v5e host) or hang
        raise SystemExit(
            "--backend tpu runs ONE process per host, which drives all of "
            f"that host's chips through the mesh; --nproc_per_node {nproc} "
            "would start processes that each try to take every chip. Use "
            "--nproc_per_node 1 (and --nnodes N for N hosts), or "
            "--backend gloo for N CPU processes.")
    world = nproc * args.nnodes
    if args.nnodes > 1 and not args.master:
        raise SystemExit(
            "--master host:port is required when nnodes > 1 (every node "
            "must rendezvous at the same coordinator)")
    master = args.master or f"127.0.0.1:{_free_port()}"
    os.makedirs(args.log_dir, exist_ok=True)

    # endpoint list is meaningful single-node only (this launcher cannot
    # know other nodes' ports); multi-node rendezvous rides the jax
    # coordinator, so the contract leaves PADDLE_TRAINER_ENDPOINTS empty
    endpoints = "" if args.nnodes > 1 else ",".join(
        f"{master.split(':')[0]}:{_free_port()}" for _ in range(nproc))

    def spawn_pod(attempt):
        procs, logs = [], []
        for local_rank in range(nproc):
            rank = args.node_rank * nproc + local_rank
            env = dict(os.environ)
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_MASTER": master,
                "PADDLE_TRAINER_ENDPOINTS": endpoints,
                "PADDLE_RESTART_ATTEMPT": str(attempt),
            })
            if args.backend:
                env["PADDLE_DIST_BACKEND"] = args.backend
            log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
            mode = "a" if attempt else "w"
            logf = open(log_path, mode)
            if attempt:
                logf.write(f"\n----- restart attempt {attempt} -----\n")
                logf.flush()
            procs.append(subprocess.Popen(
                [sys.executable, "-u", args.training_script,
                 *args.training_script_args],
                env=env, stdout=logf, stderr=subprocess.STDOUT))
            logs.append(logf)
        return procs, logs

    def teardown(procs):
        for other in procs:
            if other.poll() is None:
                other.terminate()
        for other in procs:
            try:
                other.wait(timeout=10)
            except subprocess.TimeoutExpired:
                other.kill()

    attempt = 0
    procs, logs = spawn_pod(attempt)
    rc = 0
    try:
        while procs:
            alive = []
            failed = None
            for pr in procs:
                code = pr.poll()
                if code is None:
                    alive.append(pr)
                elif code != 0:
                    failed = code
                    break
            if failed is not None:
                teardown(procs)
                for f in logs:
                    f.close()
                if attempt < args.max_restarts:
                    # elastic relaunch: a worker asked for restart (101)
                    # or crashed — restart the whole pod
                    attempt += 1
                    procs, logs = spawn_pod(attempt)
                    continue
                rc = failed
                procs = []
                break
            procs = alive
            if procs:
                time.sleep(0.2)
    except KeyboardInterrupt:
        for pr in procs:
            if pr.poll() is None:
                pr.send_signal(signal.SIGINT)
        rc = 130
    finally:
        for f in logs:
            f.close()
    return rc


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()

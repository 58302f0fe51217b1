"""Distributed environment (parity: python/paddle/distributed/parallel.py:91
``init_parallel_env`` + fluid/dygraph/parallel.py ``ParallelEnv``).

TPU model: single-controller SPMD per host.  ``rank``/``world_size`` describe
*processes* (hosts), as in jax.distributed; device-level parallelism lives in
the mesh (topology.py).  Rendezvous: jax coordination service replaces the
reference's TCPStore (distributed/store/tcp_store.cc).

The launcher (`python -m paddle_tpu.distributed.launch`) writes the
PADDLE_* env contract; ``init_parallel_env()`` consumes it and brings up
the multi-process backend.  With ``PADDLE_DIST_BACKEND=gloo`` workers run
on CPU devices with gloo collectives — the multi-process test fixture
(the reference tests multi-node the same way: N local processes).
"""
from __future__ import annotations

import os

import jax

__all__ = ["init_parallel_env", "get_rank", "get_world_size",
           "is_initialized", "ParallelEnv"]

_initialized = [False]


def init_parallel_env(coordinator_address=None, num_processes=None,
                      process_id=None):
    """Initialize the multi-process env from args or the launcher's
    PADDLE_* contract; single-process (one host driving all its chips,
    the common case) is a no-op that still marks the env ready, mirroring
    init_parallel_env on one card."""
    if _initialized[0]:
        return ParallelEnv()
    coord = coordinator_address or os.environ.get("PADDLE_MASTER") or \
        os.environ.get("COORDINATOR_ADDRESS")
    nproc = num_processes or int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    pid = process_id if process_id is not None else \
        int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coord and nproc > 1:
        if os.environ.get("PADDLE_DIST_BACKEND") == "gloo":
            # CPU multi-process fixture: gloo collectives exist only on
            # the CPU backend, so the worker is held to it here
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    _initialized[0] = True
    return ParallelEnv()


def is_initialized():
    return _initialized[0]


def get_rank():
    return jax.process_index()


def get_world_size():
    return jax.process_count()


class ParallelEnv:
    """Parity shim for paddle.distributed.ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        """Rank within this node (launcher contract), NOT the global rank."""
        return int(os.environ.get("PADDLE_LOCAL_RANK", get_rank()))

    @property
    def device_id(self):
        """The local device this process drives (one accelerator per
        process under the launcher; id 0 under single-controller SPMD)."""
        if "PADDLE_LOCAL_RANK" in os.environ and len(jax.local_devices()) > 1:
            return self.local_rank % len(jax.local_devices())
        return 0

    @property
    def nranks(self):
        return get_world_size()

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")

    @property
    def current_endpoint(self):
        eps = self.trainer_endpoints
        r = self.local_rank
        return eps[r] if r < len(eps) else ""

"""Process launcher — ``python -m paddle_tpu.distributed.launch``.

Reference parity: python/paddle/distributed/launch/main.py:18 (``launch``)
+ launch/controllers/collective.py:32,89-91 (CollectiveController.build_pod
env contract) + launch/job/container.py (per-rank ``workerlog.N`` files).

TPU-native mapping: the reference forks one process per GPU and wires
NCCL ids through a TCPStore; here each process is one jax *host* whose
rendezvous is the jax coordination service (`jax.distributed.initialize`).
On TPU hosts one process per host is the rule — it drives all of the host's
chips through the mesh, and ``--backend tpu`` refuses ``--nproc_per_node``
above 1 (a chip belongs to one process); for tests the same contract runs N
CPU processes with gloo collectives.

Env contract written per rank (reference names, collective.py:89-91):
  PADDLE_TRAINER_ID        global rank
  PADDLE_TRAINERS_NUM      world size
  PADDLE_LOCAL_RANK        rank within this node
  PADDLE_MASTER            coordinator host:port
  PADDLE_TRAINER_ENDPOINTS comma list of worker endpoints
  PADDLE_DIST_BACKEND      'tpu' or 'gloo' (CPU testing); unset = jax's default
"""
from .main import launch, main

__all__ = ["launch", "main"]

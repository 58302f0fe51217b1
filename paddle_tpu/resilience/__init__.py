"""paddle_tpu.resilience — correctness under failure.

The north star serves millions of users from preemptible TPU fleets;
this package is the fault boundary that makes that survivable:

- :mod:`.atomic` — the tmp+rename write primitive every durable file
  in the repo commits through (linted by
  ``tools/check_atomic_writes.py``).
- :mod:`.checkpoint_manager` — :class:`CheckpointManager`: atomic
  commit, per-shard CRC32, ``latest()`` discovery that skips torn or
  corrupt checkpoints, newest-intact fallback restore, ``keep_last_n``
  retention, optional background async save.
- :mod:`.faults` — deterministic seed-driven fault injection (named
  sites, off by default, env-gated via ``PADDLE_TPU_FAULTS``); drives
  the crash-consistency tests and counts every fired fault into the
  metrics registry.
- :mod:`.retry` — jittered exponential backoff (:func:`retry`,
  :func:`backoff_delays`) and :class:`Deadline`, adopted by the
  TCPStore client and the serving engine's per-request TTLs.
- :mod:`.integrity` — the silent-corruption sentinel:
  :func:`tree_fingerprint` per-leaf CRC32 digests compared across dp
  ranks over the TCPStore, sampled step-replay verification, and the
  ``param_divergence`` restore-and-replay repair
  (:class:`IntegrityCallback`, exported lazily to keep the layer
  stack acyclic).
- :mod:`.supervisor` — :class:`TrainingSupervisor`: runs the trainer
  as a watched child process and autonomously relaunches it (jittered
  backoff, ``max_restarts`` budget, elastic-membership rendezvous),
  resuming from the newest intact checkpoint — preemption-to-resume
  with zero operator action.

Consumers: ``framework_io.save`` and ``jit.save`` write atomically;
``distributed.checkpoint`` checksums shards and exposes kill sites;
``hapi.CheckpointCallback`` + ``Model.fit(resume_from=...)`` make a
killed training run continue with a matching loss curve; the serving
engine sheds load at watermarks and evicts requests past deadline.
"""
from __future__ import annotations

from .atomic import CRC32Writer, atomic_write  # noqa: F401
from .checkpoint_manager import (  # noqa: F401
    CheckpointAuditError,
    CheckpointManager,
    verify_checkpoint,
)
from .faults import (  # noqa: F401
    FaultInjector,
    FaultSpec,
    SimulatedCrash,
    current_injector,
    fault_armed,
    fault_point,
    injected_faults,
    install,
    install_from_env,
    uninstall,
)
from .retry import Deadline, RetryError, backoff_delays, retry  # noqa: F401
from .supervisor import (  # noqa: F401
    ENV_ATTEMPT,
    ENV_RESUME_DIR,
    TrainingSupervisor,
)

__all__ = [
    "atomic_write", "CRC32Writer",
    "CheckpointManager", "CheckpointAuditError", "verify_checkpoint",
    "IntegrityCallback", "tree_fingerprint", "compare_digests",
    "FaultInjector", "FaultSpec", "SimulatedCrash", "fault_point",
    "fault_armed",
    "install", "uninstall", "current_injector", "injected_faults",
    "install_from_env",
    "Deadline", "RetryError", "backoff_delays", "retry",
    "TrainingSupervisor", "ENV_RESUME_DIR", "ENV_ATTEMPT",
]

_INTEGRITY_NAMES = {"IntegrityCallback", "tree_fingerprint",
                    "compare_digests", "first_divergent_leaf",
                    "majority_partition"}


def __getattr__(name):
    # integrity's sentinel callback needs the hapi hook surface (via
    # observability.goodput); importing it lazily keeps this package
    # importable from the bottom of the layer stack
    if name in _INTEGRITY_NAMES:
        from . import integrity

        return getattr(integrity, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# env-gated fault injection: inert unless PADDLE_TPU_FAULTS is set
install_from_env()

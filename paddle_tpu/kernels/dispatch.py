"""Kernel path selection — the one place that decides how a Pallas kernel
runs.

A kernel's public entry takes ``path=None`` and hands it to
:func:`resolve_path`, which decides from the platform of the process's
default backend:

- ``MOSAIC``    — the kernel compiled for the TPU.  The only answer on a
  TPU: a kernel that cannot be built there is an error, never a quiet
  switch to the reference.
- ``INTERPRET`` — the same kernel body under the Pallas interpreter
  (CPU tests of the kernel logic).
- ``REFERENCE`` — the kernel's ``jnp`` oracle (CPU tests of everything
  around the kernel, and the numerics baseline on the chip).

Off the TPU each kernel names its own default (``off_tpu``); a caller
that wants a specific path — a test, an AOT compile for a TPU topology
from a CPU host, the chip smoke's kernel-vs-reference check — passes
``path=`` explicitly and the platform is not consulted.
"""
from __future__ import annotations

import jax

__all__ = ["MOSAIC", "INTERPRET", "REFERENCE", "resolve_path"]

MOSAIC = "mosaic"
INTERPRET = "interpret"
REFERENCE = "reference"


def resolve_path(path, *, off_tpu, allowed=(MOSAIC, INTERPRET, REFERENCE)):
    """``path`` if the caller named one (it must be among the kernel's
    ``allowed``), else Mosaic on a TPU and ``off_tpu`` anywhere else."""
    if path is None:
        return MOSAIC if jax.default_backend() == "tpu" else off_tpu
    if path not in allowed:
        raise ValueError(f"kernel path {path!r} is not one of {allowed}")
    return path

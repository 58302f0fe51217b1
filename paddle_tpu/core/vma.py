"""Varying-manual-axes (vma) helpers for shard_map(check_vma=True) code.

One shared implementation of the lift-before-predication invariant: any
value consumed inside a lax.cond/switch branch whose predicate varies over
mesh axis A must ALREADY be varying over A before entering the branch —
otherwise AD places the de-varying psum over A inside the branch, where
only some ranks execute it (collective mismatch / deadlock at runtime).
Lifting outside moves the transpose psum onto the all-ranks path.

Used by distributed/engine.py (pp ticks), distributed/pp_layers.py
(heterogeneous stage switch) and kernels/ring_attention.py (sep ring).
"""
from __future__ import annotations

import jax

__all__ = ["vma_of", "lift_to", "lifter"]


def vma_of(*refs):
    """Sorted union of the refs' varying axes."""
    union = set()
    for r in refs:
        union |= jax.typeof(r).vma
    return tuple(sorted(union))


def lift_to(x, axes):
    """pcast ``x`` up to vary over every axis in ``axes`` (no-op for axes
    it already varies on)."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def lifter(*refs_or_axes):
    """Build a lift function targeting either an explicit axis tuple
    (strings) or the vma union of reference values."""
    if refs_or_axes and all(isinstance(a, str) for a in refs_or_axes):
        axes = tuple(refs_or_axes)
    else:
        axes = vma_of(*refs_or_axes)
    return lambda x: lift_to(x, axes)

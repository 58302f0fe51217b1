"""GSPMD mesh construction + sharding rules — the ONE module every
multi-chip consumer speaks through.

The dry-run era gave each layer its own ad-hoc notion of "the mesh":
hapi built a dp-only Mesh inline, the serving engine assumed one chip,
and ``distributed/checkpoint`` trusted whatever shardings the arrays
carried.  This module centralizes all of it (ROADMAP: "one mesh.py
module owning mesh construction + PartitionSpec rules"):

- :func:`build_mesh` — a named-axis logical mesh over physical devices
  (``dp``/``mp``/``pp``/``sharding``, in that fixed order), validated
  against ``jax.devices()``.  CPU-testable: under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` the same code
  path drives 8 virtual host devices that a v5p slice drives over ICI
  — one logical mesh, many physical backends (the portability argument
  of "Joint Training on AMD and NVIDIA GPUs", PAPERS.md).
- :data:`GPT_RULES` / :func:`param_specs` — the PartitionSpec rule
  table for the GPT parameter tree: Megatron column/row splits for
  attention + MLP over ``mp`` (qkv/up column-split, proj/down
  row-split → one all-reduce per residual write, inserted by GSPMD),
  vocab-sharded embedding, replicated norms.  Rules are matched by
  leaf *name* and pruned per-leaf against the actual mesh (an axis the
  mesh lacks, or that doesn't divide the dimension, degrades to
  replication — tiny test shapes and odd meshes stay valid).
- :func:`shard_params` / :func:`shard_batch` / :func:`replicated` —
  NamedSharding application helpers (device_put with the resolved
  specs).
- :func:`zero_opt_specs` — ZeRO-style optimizer-state sharding: each
  slot inherits its parameter's spec plus a split of the largest
  still-replicated dimension along the ``sharding`` axis (stage-1/2
  semantics: params replicated, optimizer state sharded).
- :func:`assert_placement` / :func:`placement_report` — verify via
  ``addressable_shards`` that an array is ACTUALLY laid out as the
  spec intends (a spec proves nothing: ``resolve_spec`` degrades
  silently to replication).
- :func:`replica_peers` — which ranks of a (dp, mp, pp, sharding)
  process grid hold bitwise-identical state (same non-dp coordinates):
  the peer set the integrity sentinel's cross-rank fingerprint compare
  must be restricted to (mp/pp/sharding peers legitimately differ).

Consumers: ``hapi/model.py`` (train/eval steps jitted with
``in_shardings``/``out_shardings``, donated params),
``serving/engine.py`` (KV page pool sharded along ``mp``),
``distributed/checkpoint.py`` (per-rank addressable-shard saves under
the commit barrier).
"""
from __future__ import annotations

import re
import threading

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AXIS_ORDER", "build_mesh", "axis_sizes", "mesh_axis",
           "GPT_RULES", "resolve_spec", "param_specs", "shard_params",
           "shard_batch", "shard_tree", "replicated", "sharding_tree",
           "zero_opt_specs", "assert_placement", "placement_report",
           "replica_peers", "default_mesh", "set_default_mesh"]

#: canonical logical-axis order; build_mesh lays devices out this way so
#: dp-major iteration matches the (dp, mp, pp, sharding) process grid
#: replica_peers() reasons over.  Also the anchor of the axis universe
#: the ``sharding-spec`` static pass validates every PartitionSpec
#: literal against (together with literal Mesh(...) axis tuples
#: elsewhere in the package) — a typo'd axis never errors at runtime,
#: resolve_spec just silently replicates, so the lint is the only
#: thing that catches it before hardware
AXIS_ORDER = ("dp", "mp", "pp", "sharding")

_LOCK = threading.Lock()
_DEFAULT_MESH = None     # guarded-by: _LOCK


def build_mesh(dp=1, mp=1, pp=1, sharding=1, devices=None):
    """A named logical mesh over ``dp*mp*pp*sharding`` devices.

    Axes of degree 1 are kept (a spec naming them is a no-op split),
    so one rule table serves every topology.  ``devices`` defaults to
    ``jax.devices()``; the requested extent must not exceed what the
    backend actually has — this is the validation the dry-run era
    skipped."""
    sizes = {"dp": int(dp), "mp": int(mp), "pp": int(pp),
             "sharding": int(sharding)}
    for name, n in sizes.items():
        if n < 1:
            raise ValueError(f"mesh axis {name!r} must be >= 1, got {n}")
    need = int(np.prod(list(sizes.values())))
    devices = list(jax.devices()) if devices is None else list(devices)
    if need > len(devices):
        raise ValueError(
            f"mesh dp={dp} mp={mp} pp={pp} sharding={sharding} needs "
            f"{need} devices; only {len(devices)} available "
            f"(CPU testing: XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={need})")
    grid = np.array(devices[:need]).reshape(
        [sizes[a] for a in AXIS_ORDER])
    return Mesh(grid, AXIS_ORDER)


def axis_sizes(mesh):
    """{axis name: degree} for any named mesh."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def mesh_axis(mesh, name):
    """Degree of ``name`` on ``mesh`` (1 when the axis is absent)."""
    return axis_sizes(mesh).get(name, 1)


def default_mesh():
    """The process-wide default mesh (None until set) — consumers that
    take ``mesh=None`` fall back to it."""
    with _LOCK:
        return _DEFAULT_MESH


def set_default_mesh(mesh):
    """Install (or clear, with None) the process-wide default mesh."""
    global _DEFAULT_MESH
    with _LOCK:
        _DEFAULT_MESH = mesh
    return mesh


# ------------------------------------------------------- the rule table
#
# Matched against the LAST component of a leaf path ("/"- or "_"-
# joined; hapi flattens "blocks/qkv_w" to "blocks_qkv_w" — both forms
# hit the same rule).  First match wins; no match = replicated.
# Dimension axes name the *intent*; resolve_spec prunes any axis the
# mesh lacks or that does not divide the dimension.

GPT_RULES = (
    # embeddings: vocab rows over mp (the lm_head matmul's contraction
    # partner); positions replicated (every row needs every position)
    (r"(^|[/_])wte$",     P("mp", None)),
    (r"(^|[/_])wpe$",     P(None, None)),
    (r"(^|[/_])lm_head$", P(None, "mp")),
    # attention: qkv column-split (a head group per mp shard), proj
    # row-split — GSPMD inserts the one psum at the residual write
    (r"qkv_w$",  P(None, None, "mp")),
    (r"qkv_b$",  P(None, "mp")),
    (r"proj_w$", P(None, "mp", None)),
    (r"proj_b$", P(None, None)),
    # MLP: up column-split, down row-split (same psum placement)
    (r"(^|[/_])up_w$",   P(None, None, "mp")),
    (r"(^|[/_])up_b$",   P(None, "mp")),
    (r"(^|[/_])down_w$", P(None, "mp", None)),
    (r"(^|[/_])down_b$", P(None, None)),
    # norms are tiny and touched by every shard: replicated
    (r"(ln\d?|lnf)_[gb]$", P()),
)


def _leaf_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in kp)
             for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return flat, treedef, paths


def resolve_spec(spec, shape, mesh):
    """Prune ``spec`` against reality: an axis entry survives only if
    the mesh has it AND its degree divides the dimension; everything
    else degrades to replication on that dim.  A spec shorter than the
    rank is right-padded with None (jax semantics made explicit)."""
    sizes = axis_sizes(mesh)
    out = []
    for i, dim in enumerate(shape):
        ax = spec[i] if i < len(spec) else None
        if ax is None:
            out.append(None)
            continue
        degree = int(np.prod([sizes.get(a, 0) for a in
                              (ax if isinstance(ax, tuple) else (ax,))]))
        out.append(ax if degree and dim % degree == 0 else None)
    return P(*out)


def _match_rule(path, rules):
    for pat, spec in rules:
        if re.search(pat, path):
            return spec
    return P()


def param_specs(tree, mesh, rules=GPT_RULES, extra_rules=()):
    """Resolved PartitionSpec per leaf of ``tree`` (same structure).

    ``extra_rules`` prepend to (and therefore override) the GPT table —
    the hook for non-GPT networks to join the mesh without forking this
    module."""
    rules = tuple(extra_rules) + tuple(rules)
    flat, treedef, paths = _leaf_paths(tree)
    specs = [resolve_spec(_match_rule(p, rules),
                          np.shape(leaf), mesh)
             for p, leaf in zip(paths, flat)]
    return jax.tree_util.tree_unflatten(treedef, specs)


def sharding_tree(tree, mesh, rules=GPT_RULES, extra_rules=()):
    """NamedSharding per leaf — what ``jax.jit(in_shardings=...)``
    consumes."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_specs(tree, mesh, rules=rules, extra_rules=extra_rules),
        is_leaf=lambda x: isinstance(x, P))


def shard_params(tree, mesh, rules=GPT_RULES, extra_rules=()):
    """device_put every leaf onto the mesh under the resolved rules —
    the one-call promotion of a host/single-device param tree to its
    GSPMD layout."""
    return jax.tree_util.tree_map(
        jax.device_put, tree,
        sharding_tree(tree, mesh, rules=rules, extra_rules=extra_rules))


def replicated(mesh):
    """Fully-replicated NamedSharding on ``mesh``."""
    return NamedSharding(mesh, P())


def shard_batch(mesh, *arrays, axis="dp"):
    """Shard each array's leading (batch) dim over ``axis`` (degrading
    to replication when it doesn't divide).  Returns one array or a
    tuple, matching the call."""
    out = []
    for x in arrays:
        n = np.shape(x)[0] if np.ndim(x) else 0
        spec = resolve_spec(P(axis), (n,), mesh) if n else P()
        out.append(jax.device_put(
            x, NamedSharding(mesh, P(*spec, *([None] * (np.ndim(x) - 1))))
            if np.ndim(x) else replicated(mesh)))
    return out[0] if len(out) == 1 else tuple(out)


def shard_tree(tree, mesh, spec_tree):
    """device_put a tree under an explicit same-structure spec tree."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, spec_tree, is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------- ZeRO optimizer state


def zero_opt_specs(param_spec_tree, state_like, mesh, axis="sharding"):
    """Optimizer-slot specs: each slot leaf gets its parameter's own
    spec plus an ``axis`` split of the LARGEST still-replicated
    dimension that divides.

    This is ZeRO stage-1/2 semantics on GSPMD: parameters stay under
    their (possibly mp-sharded) layout while the optimizer state — the
    2-3x memory multiplier — spreads over the ``sharding`` axis.
    ``state_like`` mirrors ``param_spec_tree``'s structure but each
    parameter position may hold a SUBTREE of slot arrays (Adam's
    moment1/moment2) — every slot leaf under one parameter shares that
    parameter's derived spec.  Leaves whose every dim is taken (or
    that don't divide) keep the param spec; scalars replicate."""
    degree = mesh_axis(mesh, axis)

    def leaf_spec(spec, shape):
        shape = tuple(shape)
        if degree <= 1 or not shape:
            return resolve_spec(spec, shape, mesh)
        base = list(resolve_spec(spec, shape, mesh))
        base += [None] * (len(shape) - len(base))
        free = [(shape[i], i) for i in range(len(shape))
                if base[i] is None and shape[i] % degree == 0]
        if free:
            _, i = max(free)
            base[i] = axis
        return P(*base)

    def per_param(spec, sub):
        return jax.tree_util.tree_map(
            lambda a: leaf_spec(spec, np.shape(a)), sub)

    return jax.tree_util.tree_map(
        per_param, param_spec_tree, state_like,
        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------------- placement assertions


def placement_report(tree, prefix=""):
    """{leaf path: {spec, devices, distinct_windows, shard_shape}} from
    each leaf's LIVE ``addressable_shards`` — what is actually on the
    devices, not what was requested."""
    flat, _, paths = _leaf_paths(tree)
    out = {}
    for path, arr in zip(paths, flat):
        key = f"{prefix}{path}"
        shards = getattr(arr, "addressable_shards", None)
        if not shards:
            out[key] = {"devices": 1, "distinct_windows": 1,
                        "shard_shape": list(np.shape(arr)), "spec": None}
            continue
        windows = {tuple((sl.start, sl.stop) for sl in s.index)
                   for s in shards}
        spec = getattr(getattr(arr, "sharding", None), "spec", None)
        out[key] = {
            "devices": len(shards),
            "distinct_windows": len(windows),
            "shard_shape": list(shards[0].data.shape),
            "spec": None if spec is None else
            [None if s is None else str(s) for s in spec],
        }
    return out


def assert_placement(arr, mesh, spec, name="array"):
    """Assert via ``addressable_shards`` that ``arr`` is laid out as
    ``resolve_spec(spec)`` intends: one shard per addressable device,
    shard shape = global shape / axis degrees, and the number of
    DISTINCT index windows equals the product of the sharded axes'
    degrees (replicated dims repeat windows, sharded dims tile them)."""
    spec = resolve_spec(spec, arr.shape, mesh)
    sizes = axis_sizes(mesh)
    shards = list(arr.addressable_shards)
    n_local = len([d for d in mesh.devices.flat
                   if d in set(jax.local_devices())])
    if len(shards) != n_local:
        raise AssertionError(
            f"{name}: {len(shards)} addressable shards, expected one "
            f"per local mesh device ({n_local})")
    want_shape, tiles = [], 1
    for i, dim in enumerate(arr.shape):
        ax = spec[i] if i < len(spec) else None
        degree = int(np.prod([sizes[a] for a in
                              (ax if isinstance(ax, tuple) else (ax,))])
                     ) if ax else 1
        want_shape.append(dim // degree)
        tiles *= degree
    for s in shards:
        if tuple(s.data.shape) != tuple(want_shape):
            raise AssertionError(
                f"{name}: shard shape {tuple(s.data.shape)} != expected "
                f"{tuple(want_shape)} under spec {spec}")
    windows = {tuple((sl.start, sl.stop) for sl in s.index)
               for s in shards}
    if len(windows) != tiles:
        raise AssertionError(
            f"{name}: {len(windows)} distinct shard windows, expected "
            f"{tiles} under spec {spec}")
    return True


# ------------------------------------------------------- replica groups


def replica_peers(rank, axes, axis="dp"):
    """Ranks of the (dp, mp, pp, sharding) process grid holding state
    bitwise-identical to ``rank``'s: same coordinates on every axis
    except ``axis``.

    ``axes`` is {name: degree} in :data:`AXIS_ORDER` layout (row-major,
    dp-major — the layout :func:`build_mesh` uses).  This is the peer
    set a cross-rank fingerprint compare is valid over: dp replicas
    must match bitwise, while mp/pp/sharding neighbours hold DIFFERENT
    shards and legitimately differ."""
    dims = [int(axes.get(a, 1)) for a in AXIS_ORDER]
    world = int(np.prod(dims))
    if not 0 <= rank < world:
        raise ValueError(f"rank {rank} outside world of {world}")
    coords = list(np.unravel_index(rank, dims))
    try:
        vary = AXIS_ORDER.index(axis)
    except ValueError:
        raise ValueError(f"unknown mesh axis {axis!r}") from None
    peers = []
    for i in range(dims[vary]):
        c = list(coords)
        c[vary] = i
        peers.append(int(np.ravel_multi_index(c, dims)))
    return sorted(peers)

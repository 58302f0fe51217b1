"""Collective communication API.

Parity: python/paddle/distributed/collective.py + the C++ collective op set
(paddle/fluid/operators/collective/, N26) and ProcessGroup family
(distributed/collective/ProcessGroup.h:53).

TPU-native design: a Group names a *mesh axis* (or tuple of axes).  Inside a
shard_map/pjit region the functions lower to XLA collectives riding ICI
(psum/all_gather/ppermute/all_to_all) — collectives-as-ops-in-graph, exactly
the property the reference's program-rewriting passes rely on (N26).  Outside
any mesh region (plain eager, world=1 per process) they degrade to their
single-participant semantics so user code runs unchanged on one chip.
There are no streams or Task handles: XLA owns async scheduling.

Every public op routes through the distributed flight recorder
(:func:`~paddle_tpu.observability.flight.record_collective` — enforced
by ``tools/check_collective_instrumented.py``): each call gets a
monotonic sequence number, byte/shape accounting, a ``collective::<op>``
tracer span and the ``collective_*`` registry series.  Inside a jit
region the record is taken at trace time (one per compile — collectives
are ops in the graph there); eager calls record real wall time.  The
``collective.all_reduce`` / ``collective.barrier`` fault sites make
cross-rank hangs reproducible on CPU (``kind="stall"`` freezes a rank
mid-collective with the record in flight — exactly what the
:class:`~paddle_tpu.observability.flight.HangWatchdog` must localize).

Every op here is *rank-uniform*: all participating ranks must reach it,
in the same order, or the fleet wedges.  That contract is enforced
statically by the ``collective-discipline`` pass (``python -m
tools.analysis``): a call to any of these under a rank-conditional
branch (``if rank == 0: all_reduce(...)``) is flagged at lint time as
the hang the watchdog would otherwise only name at runtime;
deliberately asymmetric protocols carry ``# rank-ok: <reason>``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..observability.flight import record_collective
from ..resilience.faults import fault_point

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "all_gather", "reduce", "broadcast", "scatter", "reduce_scatter",
           "all_to_all", "send", "recv", "barrier", "split", "ppermute"]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = a named mesh axis (tuple for fused axes)."""

    def __init__(self, axis_name=None, ranks=None, gid=0):
        self.axis_name = axis_name
        self.ranks = ranks
        self.id = gid

    @property
    def nranks(self):
        if self.ranks is not None:
            return len(self.ranks)
        return 1

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if self.ranks else 0

    def __repr__(self):
        return f"Group(axis={self.axis_name}, ranks={self.ranks})"


_groups: dict[int, Group] = {0: Group(axis_name=None, ranks=None, gid=0)}
_next_gid = [1]


def new_group(ranks=None, backend=None, axis_name=None):
    gid = _next_gid[0]
    _next_gid[0] += 1
    g = Group(axis_name=axis_name, ranks=ranks, gid=gid)
    _groups[gid] = g
    return g


def get_group(gid=0):
    return _groups.get(gid)


def _unwrap(x):
    return x.data if isinstance(x, Tensor) else x


def _wrap_like(x, arr):
    return Tensor(arr) if isinstance(x, Tensor) else arr


def _axis(group):
    return None if group is None else group.axis_name


# --------------------------------------------------------------- collectives


import functools


@functools.lru_cache(maxsize=None)
def _proc_mesh():
    """1-D mesh with ONE device per process (the first), so a per-process
    value contributes exactly once regardless of local device count."""
    import numpy as _np
    from jax.sharding import Mesh

    per_proc = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, d)
    return Mesh(_np.array([per_proc[i] for i in sorted(per_proc)]), ("p",))


@functools.lru_cache(maxsize=None)
def _proc_reduce_fn(op):
    from jax.sharding import NamedSharding, PartitionSpec

    red = {ReduceOp.SUM: jnp.sum, ReduceOp.MAX: jnp.max,
           ReduceOp.MIN: jnp.min, ReduceOp.PROD: jnp.prod,
           ReduceOp.AVG: jnp.mean}[op]
    # one cached jitted callable per op: repeated grad syncs reuse the
    # compiled executable (per shape) instead of recompiling per call
    return jax.jit(functools.partial(red, axis=0),
                   out_shardings=NamedSharding(_proc_mesh(),
                                               PartitionSpec()))


def _cross_process_all_reduce(x, op=ReduceOp.SUM):
    """Eager allreduce across *processes* (the launcher's one-process-per-
    device model): build a global array from the per-process values, reduce
    under jit with replicated output, read the local copy back.  This is
    the TPU-native stand-in for the reference's eager ProcessGroup
    allreduce (ProcessGroupNCCL.cc:317) — XLA runs the collective."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = _proc_mesh()
    stacked = NamedSharding(mesh, PartitionSpec("p"))
    local = jnp.asarray(x)[None]
    n = len(mesh.devices)
    xg = jax.make_array_from_single_device_arrays(
        (n,) + local.shape[1:], stacked,
        [jax.device_put(local, _proc_mesh().devices.flat[
            jax.process_index()])])
    out = _proc_reduce_fn(op)(xg)
    return jnp.asarray(out.addressable_data(0))


@record_collective("all_reduce")
def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """c_allreduce_{sum,max,min,prod} analog; inside shard_map → lax.psum;
    eager with multiple processes → cross-process reduce via XLA."""
    fault_point("collective.all_reduce")
    axis = _axis(group)
    x = _unwrap(tensor)
    if axis is None:
        # concrete value + multiple processes = the launcher's eager DP
        # path; a tracer here means we're inside jit with no group axis
        if jax.process_count() > 1 and not isinstance(x, jax.core.Tracer):
            out = _cross_process_all_reduce(x, op)
        else:
            out = x  # single participant
    elif op == ReduceOp.SUM:
        out = jax.lax.psum(x, axis)
    elif op == ReduceOp.MAX:
        out = jax.lax.pmax(x, axis)
    elif op == ReduceOp.MIN:
        out = jax.lax.pmin(x, axis)
    elif op == ReduceOp.AVG:
        out = jax.lax.pmean(x, axis)
    elif op == ReduceOp.PROD:
        # exact elementwise product: gather the n shards and multiply in
        # the input dtype (an exp/log round-trip is inexact for ints
        # beyond 2^24 and for low-precision floats; c_allreduce_prod is an
        # exact product)
        g = jax.lax.all_gather(x, axis)
        out = jnp.prod(g, axis=0).astype(x.dtype)
    else:
        raise ValueError(f"unknown reduce op {op}")
    if isinstance(tensor, Tensor):
        tensor.data = out  # in-place semantics like the reference
        return tensor
    return out


@record_collective("all_gather")
def all_gather(tensor_or_list, tensor=None, group=None, sync_op=True, axis=0):
    """c_allgather analog; inside shard_map → lax.all_gather."""
    # support both signatures: all_gather(out_list, x) and x2 = all_gather(x)
    if isinstance(tensor_or_list, list) and tensor is not None:
        x = _unwrap(tensor)
        ax = _axis(group)
        if ax is None:
            tensor_or_list.append(_wrap_like(tensor, x))
            return tensor_or_list
        gathered = jax.lax.all_gather(x, ax)  # [n, ...]
        for i in range(gathered.shape[0]):
            tensor_or_list.append(_wrap_like(tensor, gathered[i]))
        return tensor_or_list
    x = _unwrap(tensor_or_list)
    ax = _axis(group)
    if ax is None:
        return _wrap_like(tensor_or_list, x)
    g = jax.lax.all_gather(x, ax, axis=0)
    n = g.shape[0]
    out = jnp.concatenate([g[i] for i in range(n)], axis=axis) if axis != 0 else \
        g.reshape((-1,) + x.shape[1:]) if x.ndim >= 1 else g
    return _wrap_like(tensor_or_list, out)


@record_collective("reduce")
def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # SPMD: every participant computes the reduction (psum), matching dst's
    # value; cheaper than masking and semantically compatible.
    return all_reduce(tensor, op=op, group=group)


@record_collective("broadcast")
def broadcast(tensor, src=0, group=None, sync_op=True):
    """c_broadcast analog: take src's shard value on all members."""
    axis = _axis(group)
    x = _unwrap(tensor)
    if axis is None:
        return tensor
    # select src's value: gather then index (XLA folds this to a broadcast)
    g = jax.lax.all_gather(x, axis)
    out = g[src]
    if isinstance(tensor, Tensor):
        tensor.data = out
        return tensor
    return out


@record_collective("scatter")
def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    axis = _axis(group)
    if axis is None:
        return tensor
    x = _unwrap(tensor_list if tensor_list is not None else tensor)
    idx = jax.lax.axis_index(axis)
    if isinstance(x, (list, tuple)):
        stacked = jnp.stack([_unwrap(t) for t in x])
        out = stacked[idx]
    else:
        out = jnp.split(x, jax.lax.axis_size(axis))[idx]
    if isinstance(tensor, Tensor):
        tensor.data = out
        return tensor
    return out


@record_collective("reduce_scatter")
def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """c_reducescatter analog; inside shard_map → lax.psum_scatter."""
    axis = _axis(group)
    x = _unwrap(tensor_list if tensor_list is not None else tensor)
    if isinstance(x, (list, tuple)):
        x = jnp.concatenate([_unwrap(t) for t in x], axis=0)
    if axis is None:
        return _wrap_like(tensor, x)
    out = jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    return _wrap_like(tensor, out)


@record_collective("all_to_all")
def all_to_all(in_tensor_or_list, out_tensor_list=None, group=None,
               sync_op=True, split_axis=0, concat_axis=0):
    """alltoall analog (MoE global_scatter/global_gather building block);
    inside shard_map → lax.all_to_all."""
    axis = _axis(group)
    if isinstance(in_tensor_or_list, (list, tuple)):
        x = jnp.stack([_unwrap(t) for t in in_tensor_or_list])
        if axis is None:
            return list(in_tensor_or_list)
        out = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0)
        return [_wrap_like(in_tensor_or_list[0], out[i]) for i in range(out.shape[0])]
    x = _unwrap(in_tensor_or_list)
    if axis is None:
        return _wrap_like(in_tensor_or_list, x)
    out = jax.lax.all_to_all(x, axis, split_axis=split_axis,
                             concat_axis=concat_axis, tiled=True)
    return _wrap_like(in_tensor_or_list, out)


@record_collective("ppermute")
def ppermute(tensor, perm, group=None):
    """collective_permute — the partial_send/partial_recv analog used by the
    pipeline schedule (send_v2/recv_v2, N26)."""
    axis = _axis(group)
    x = _unwrap(tensor)
    if axis is None:
        return _wrap_like(tensor, x)
    out = jax.lax.ppermute(x, axis, perm)
    return _wrap_like(tensor, out)


@record_collective("send")
def send(tensor, dst=0, group=None, sync_op=True):
    # point-to-point inside SPMD is a ppermute with a single pair; the caller
    # on the receiving side must issue the matching recv with the same perm.
    raise NotImplementedError(
        "raw send/recv are not SPMD-expressible; use ppermute (both sides) "
        "or the pipeline engine's p2p helpers")


@record_collective("recv")
def recv(tensor, src=0, group=None, sync_op=True):
    raise NotImplementedError(
        "raw send/recv are not SPMD-expressible; use ppermute (both sides) "
        "or the pipeline engine's p2p helpers")


@record_collective("barrier")
def barrier(group=None):
    fault_point("collective.barrier")
    axis = _axis(group)
    if axis is None:
        # eager: drain device queue (closest analog of a stream sync barrier)
        jax.effects_barrier()
        return
    jax.lax.psum(jnp.zeros((), jnp.float32), axis)


@record_collective("split")
def split(x, num_or_sections, axis=0, group=None):
    """c_split analog: take this rank's slice along ``axis``."""
    ax_name = _axis(group)
    arr = _unwrap(x)
    if ax_name is None:
        return _wrap_like(x, arr)
    idx = jax.lax.axis_index(ax_name)
    n = num_or_sections if isinstance(num_or_sections, int) else len(num_or_sections)
    size = arr.shape[axis] // n
    out = jax.lax.dynamic_slice_in_dim(arr, idx * size, size, axis=axis)
    return _wrap_like(x, out)

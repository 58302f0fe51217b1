"""Tensor-parallel layers.

Parity: python/paddle/distributed/fleet/meta_parallel/parallel_layers/
mp_layers.py — VocabParallelEmbedding(:30), ColumnParallelLinear(:97),
RowParallelLinear(:170), ParallelCrossEntropy(:249) — and the collective ops
they use (c_embedding, c_concat, c_split, c_softmax_with_cross_entropy, N26).

TPU-native design: two modes share one class.
- **GSPMD mode (default)**: the layer is an ordinary Linear/Embedding whose
  weight carries a PartitionSpec over the 'mp' mesh axis
  (``sharding_spec()``); under pjit XLA inserts exactly the identity/
  allreduce pairs the reference hand-writes.  This is the perf path.
- **Explicit mode (inside shard_map)**: when called under a shard_map that
  maps the 'mp' axis, forward issues the collectives manually (psum after
  row-parallel etc.) — bit-for-bit the reference's schedule, used by the
  parity tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import ops
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..nn.initializer import Constant, Normal, XavierUniform

__all__ = ["ColumnParallelLinear", "RowParallelLinear",
           "VocabParallelEmbedding", "ParallelCrossEntropy",
           "parallel_cross_entropy"]


def _mp_info(mp_axis):
    """(size, index) of the mp axis inside a shard_map, else (1, 0)."""
    try:
        return jax.lax.axis_size(mp_axis), jax.lax.axis_index(mp_axis)
    except (NameError, KeyError, ValueError):
        return 1, 0


class ColumnParallelLinear(Layer):
    """W split along output dim.  fwd: identity → local matmul; gather or
    keep split.  bwd: allreduce of input grad (automatic via psum transpose).
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, mp_group=None,
                 num_partitions=None, fuse_matmul_bias=False):
        super().__init__()
        from .fleet import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        self.mp_axis = "mp"
        self.world_size = (num_partitions or
                           (hcg.get_model_parallel_world_size() if hcg else 1))
        self.gather_output = gather_output
        self.out_features = out_features
        assert out_features % self.world_size == 0, \
            f"out_features {out_features} not divisible by mp {self.world_size}"
        self.out_per_partition = out_features // self.world_size
        # full weight stored; GSPMD shards it via sharding_spec(); explicit
        # shard_map callers pass pre-split weights via swap_state
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=XavierUniform())
        self.weight.is_distributed = True
        self.bias = self.create_parameter([out_features], is_bias=True) \
            if has_bias else None

    def sharding_specs(self):
        specs = {"weight": P(None, "mp")}
        if self.bias is not None:
            specs["bias"] = P("mp")
        return specs

    def forward(self, x):
        """GSPMD mode: plain matmul on the (sharded-by-spec) full weight.
        Explicit mode (inside shard_map mapping 'mp', weights pre-split):
        local matmul, then all_gather of the output columns when
        gather_output — the reference's c_concat (mp_layers.py:97)."""
        size, _ = _mp_info(self.mp_axis)
        out = ops.matmul(x, self.weight)
        if self.bias is not None:
            out = ops.add(out, self.bias)
        if size > 1 and self.gather_output:
            arr = out.data if isinstance(out, Tensor) else out
            arr = jax.lax.all_gather(arr, self.mp_axis, axis=arr.ndim - 1,
                                     tiled=True)
            out = Tensor(arr) if isinstance(out, Tensor) else arr
        return out


class RowParallelLinear(Layer):
    """W split along input dim.  fwd: local matmul → allreduce(sum).
    Under GSPMD the psum appears automatically from the contraction over the
    'mp'-sharded dimension."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, mp_group=None,
                 num_partitions=None):
        super().__init__()
        from .fleet import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        self.mp_axis = "mp"
        self.world_size = (num_partitions or
                           (hcg.get_model_parallel_world_size() if hcg else 1))
        self.input_is_parallel = input_is_parallel
        assert in_features % self.world_size == 0
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=XavierUniform())
        self.weight.is_distributed = True
        self.bias = self.create_parameter([out_features], is_bias=True) \
            if has_bias else None

    def sharding_specs(self):
        specs = {"weight": P("mp", None)}
        if self.bias is not None:
            specs["bias"] = P(None)
        return specs

    def forward(self, x):
        """GSPMD mode: plain matmul (psum appears from the contraction over
        the sharded dim).  Explicit mode: c_split the input unless it is
        already parallel, local matmul, allreduce, THEN bias (adding it
        pre-psum would count it mp times) — mp_layers.py:170 semantics."""
        size, idx = _mp_info(self.mp_axis)
        if size > 1 and not self.input_is_parallel:
            arr = x.data if isinstance(x, Tensor) else x
            in_local = self.weight.shape[0]
            arr = jax.lax.dynamic_slice_in_dim(
                arr, idx * in_local, in_local, axis=arr.ndim - 1)
            x = Tensor(arr) if isinstance(x, Tensor) else arr
        out = ops.matmul(x, self.weight)
        if size > 1:
            arr = out.data if isinstance(out, Tensor) else out
            arr = jax.lax.psum(arr, self.mp_axis)
            out = Tensor(arr) if isinstance(out, Tensor) else arr
        if self.bias is not None:
            out = ops.add(out, self.bias)
        return out


class VocabParallelEmbedding(Layer):
    """Embedding table split along vocab.  Under GSPMD the take() over a
    vocab-sharded table lowers to the mask+psum pattern the reference
    hand-writes in c_embedding."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None):
        super().__init__()
        from .fleet import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        self.world_size = hcg.get_model_parallel_world_size() if hcg else 1
        self.num_embeddings = num_embeddings
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=Normal(0.0, 0.02))
        self.weight.is_distributed = True

    def sharding_specs(self):
        return {"weight": P("mp", None)}

    def forward(self, ids):
        return ops.embedding(ids, self.weight)


def parallel_cross_entropy(logits, label, mp_axis="mp", ignore_index=-100):
    """Vocab-parallel softmax CE for use inside shard_map: logits are sharded
    on the vocab (last) dim over ``mp_axis``.  Numerically identical to the
    reference's c_softmax_with_cross_entropy: global max + global sum-exp via
    psum, local gather of the true-label logit.

    Pure function over arrays (jit/shard_map friendly).
    """
    vocab_per_part = logits.shape[-1]
    size, idx = _mp_info(mp_axis)
    offset = idx * vocab_per_part

    lf = logits.astype(jnp.float32)
    local_max = jnp.max(jax.lax.stop_gradient(lf), axis=-1, keepdims=True)
    gmax = jax.lax.pmax(local_max, mp_axis) if size != 1 else local_max
    # the shift is purely numerical (cancels in log-softmax): keep it out of AD
    shifted = lf - jax.lax.stop_gradient(gmax)
    local_sumexp = jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
    gsumexp = jax.lax.psum(local_sumexp, mp_axis) if size != 1 else local_sumexp
    # pick the true-class logit if it lives in this shard
    local_label = label - offset
    in_shard = (local_label >= 0) & (local_label < vocab_per_part)
    safe = jnp.clip(local_label, 0, vocab_per_part - 1)
    picked = jnp.take_along_axis(shifted, safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_shard, picked, 0.0)
    if size != 1:
        picked = jax.lax.psum(picked, mp_axis)
    loss = jnp.log(gsumexp[..., 0]) - picked
    return jnp.where(label == ignore_index, 0.0, loss)


from ..core.dispatch import register_op

_parallel_ce = register_op("parallel_cross_entropy")(parallel_cross_entropy)


class ParallelCrossEntropy(Layer):
    def __init__(self, mp_group=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, label):
        return _parallel_ce(logits, label, ignore_index=self.ignore_index)

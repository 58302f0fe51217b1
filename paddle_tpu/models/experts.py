"""A dropless sparse-expert layer that is told which experts it holds.

The layer of expert parallelism, as one chip runs it: the router scores
every token against all ``num_experts`` experts of the model and takes its
``top_k``; this holder owns the experts ``[first, first + count)``
(``experts_held``) and computes, for each token, the weighted outputs of
those of its choices that it holds.  The rest of the sum lives on other
holders; what a holder returns is its part, and a caller with every holder's
part adds them up (``tests/test_expert_matmul.py`` does, at a small size).
Nothing here stands in for the other holders or for their exchange.

**No token is dropped and no capacity exists.**  The (token, expert) pairs
on held experts are ranked by expert into the rows of a buffer of static
size (``kernels/expert_matmul.buffer_rows``: at most ``T * min(top_k,
count)`` pairs, so nothing can overflow) — but the buffer holds no token
rows: what is built at that size is each row's token id and routing weight
(two small scatters).  The kernels move the rows themselves:
``expert_matmul`` fetches a tile's real rows from ``u`` by id and computes
gate and up with the SwiGLU between; ``expert_matmul_add`` multiplies by
the down matrix and adds each float32 result, times its weight, into its
token's row of the output.  Only the pairs a step really has are moved, and
a token's result does not depend on what its batch-mates chose.
``distributed/moe.py`` is the other kind (GShard: one-hot dispatch einsums,
tokens over capacity dropped); it is untouched and not used for serving.

The router's arithmetic is float32 whatever the model's dtype: a top-k over
256 scores flips on a bfloat16 rounding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels import dispatch
from ..kernels.expert_matmul import (buffer_rows, expert_layout,
                                     expert_matmul, expert_matmul_add)

__all__ = ["route_top_k", "dropless_experts", "expert_tile"]


def expert_tile(tokens, top_k, num_experts, dtype):
    """Rows of one tile of the buffer's layout: about the pairs an expert
    expects (``tokens * top_k / num_experts``), a power of two between the
    dtype's sublane tile and 128: a tile far wider than a group is rows of
    padding multiplied for nothing."""
    low = 16 if jnp.dtype(dtype).itemsize == 2 else 8
    want = max(1, -(-tokens * top_k // num_experts))
    tile = low
    while tile < min(want, 128):
        tile *= 2
    return tile


def route_top_k(logits, top_k, *, norm_topk=True, scale=1.0):
    """``(weights [T, k] float32, experts [T, k] int32)`` from the router's
    ``logits [T, E]`` float32: softmax over all experts, the ``top_k``
    largest, renormalised to sum 1 (``norm_topk``) and times ``scale``."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_i = jax.lax.top_k(p, top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p * scale, top_i.astype(jnp.int32)


def dropless_experts(u, weights, experts, w_gate, w_up, w_down, *,
                     experts_held, num_experts=None, layer=None,
                     valid=None, tile=None, path=None):
    """This holder's part of ``sum_k weights[t, k] * E_{experts[t, k]}(u[t])``
    with ``E_e(u) = w_down[e](silu(w_gate[e] u) * (w_up[e] u))``.

    ``u [T, D]``; ``weights``/``experts [T, k]`` from :func:`route_top_k`
    (expert ids of the whole model); ``w_gate``/``w_up [count, D, F]`` and
    ``w_down [count, F, D]`` the held experts' matrices, expert ``first +
    j`` at index ``j`` — or every layer's stack ``[L, count, ...]`` with
    ``layer`` naming the one to read (the kernel takes the block out of
    the stack: no layer's experts are sliced out or copied);
    ``experts_held = (first, count)`` of the model's ``num_experts``
    (which sizes the buffer's tiles: ``expert_tile``).  ``valid [T]`` bool
    marks real tokens (a padding slot is routed nowhere).

    Returns ``(y [T, D] float32, group_sizes [count] int32)``: the sizes
    are the pairs each held expert computed (for counters)."""
    first, count = experts_held
    T, D = u.shape
    k = experts.shape[1]
    if w_gate.shape[-3] != count:
        raise ValueError(f"{w_gate.shape[-3]} expert matrices for "
                         f"experts_held={experts_held}")
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    tile = tile or expert_tile(T, k, num_experts or count, u.dtype)
    local = experts - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    # pair p = t * k + j; a pair on an expert held elsewhere goes nowhere
    flat_e = jnp.where(held, local, count).reshape(T * k)
    hot = flat_e[:, None] == jnp.arange(count, dtype=jnp.int32)[None, :]
    before = jnp.cumsum(hot.astype(jnp.int32), axis=0)       # [T*k, count]
    group_sizes = before[-1]
    rank = jnp.sum(jnp.where(hot, before - 1, 0), axis=1)    # within group
    starts, _ = expert_layout(group_sizes, tile)
    N = buffer_rows(T * min(k, count), count, tile)
    dest = jnp.where(flat_e < count,
                     starts[jnp.minimum(flat_e, count - 1)] + rank, N)
    # the token and the routing weight of each buffer row: two scatters
    # of T * k scalars; the kernels fetch and add the rows of D themselves
    src = jnp.zeros((N,), jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    scale = jnp.zeros((N,), jnp.float32).at[dest].set(
        weights.reshape(T * k), mode="drop")
    h = expert_matmul(u, src, group_sizes, w_gate, w_up, tile=tile,
                      layer=layer, path=path)
    y = expert_matmul_add(h, src, scale, group_sizes, w_down, tokens=T,
                          tile=tile, layer=layer, path=path)
    return y, group_sizes

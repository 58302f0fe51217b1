"""Lightning (decayed linear) attention over ragged rows with a recurrent
state per row and head.

A linear-attention layer keeps no keys or values: per row and head it
carries one ``[hd, hd]`` float32 state

    S_t = lambda_h * S_{t-1} + k_t^T v_t          o_t = q_t S_t

with a fixed decay ``lambda_h = exp(-slope_h)`` per head (Lightning
Attention-2, arXiv:2401.04658).  The serving engine hands this kernel the
same ragged batch it hands the paged-attention kernel: row ``b``
contributes ``query_lens[b]`` tokens (a prompt chunk, or one decode
token, or none), and the row's state lives in its batch slot of a stacked
``[L, B, H, hd, hd]`` pool that the step carries in place.

Per row and head a chunk is processed in sub-chunks of ``block`` tokens:
the intra part ``((Q K^T) * D) V`` with ``D[i, j] = lambda^(i - j)`` for
``j <= i``, the inter part ``lambda^(i + 1) * (Q S)``, and the state
``S' = lambda^n S + sum_j lambda^(n - 1 - j) k_j^T v_j`` over the ``n``
valid tokens of the sub-chunk.  A decode row is the chunk of one.  A row
with ``fresh[b]`` set starts from a zero state (a newly admitted or
recomputed request in a reused slot: no separate reset program); an idle
row (``query_lens[b] == 0``) and the padded slots past a row's tokens are
skipped and leave the state as it was.

Layouts:
  q, k, v     [B, H, Q, hd]   padded per row; slots past ``query_lens[b]``
                              must hold zeros (the caller scatters into
                              zeros); ``q`` comes already scaled
  state       [L, B, H, hd, hd] float32 with ``layer`` (traced int32), or
              [B, H, hd, hd] without
  slopes      [H] float32     ``lambda_h = exp(-slopes[h])``
  query_lens  [B] int32
  fresh       [B] bool/int32  start from zero instead of the stored state
Returns ``(o [B, H, Q, hd] in q.dtype, state)`` with the state updated in
place (aliased) on the kernel path.

Two implementations, one contract (``path=`` as in ``kernels.dispatch``):
the recurrence written as a ``lax.scan`` over positions (CPU, oracle) and
the Pallas kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

__all__ = ["lightning_attention", "lightning_slopes"]


def lightning_slopes(num_heads):
    """The fixed slopes of Lightning Attention-2: ``2^(-8 h / H)`` for
    ``h = 1..H``."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / num_heads)


def _stacked(state, layer):
    if state.ndim == 4:
        return state[None], 0, True
    return state, layer, False


# ---------------------------------------------------------------- reference


def _lightning_ref(q, k, v, state, slopes, query_lens, fresh, layer):
    state, layer, single = _stacked(state, layer)
    B, H, Q, hd = q.shape
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    s0 = jnp.where(fresh.astype(bool)[:, None, None, None], 0.0,
                   state[layer])

    def step(S, xs):
        qt, kt, vt, t = xs                       # [B, H, hd], t scalar
        live = (t < query_lens)[:, None, None, None]
        S_new = lam * S + kt[..., :, None] * vt[..., None, :]
        S = jnp.where(live, S_new, S)
        o = jnp.einsum("bhd,bhde->bhe", qt, S)
        return S, jnp.where(live[..., 0], o, 0.0)

    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 2, 0)
    S, o = jax.lax.scan(step, s0, (f32(q), f32(k), f32(v), jnp.arange(Q)))
    state = state.at[layer].set(S)
    o = jnp.moveaxis(o, 0, 2).astype(q.dtype)
    return o, (state[0] if single else state)


# ------------------------------------------------------------------- kernel


def _lightning_kernel(qlen_ref, fresh_ref, layer_ref, q_ref, k_ref, v_ref,
                      slope_ref, s_in_ref, o_ref, s_out_ref, s_scr, *, block,
                      heads):
    del layer_ref                     # only the state's index_maps read it
    b = pl.program_id(0)
    c = pl.program_id(2)
    q_len = qlen_ref[b]
    last_c = jnp.maximum(q_len - 1, 0) // block

    @pl.when(c == 0)
    def _load():
        s = s_in_ref[0, 0]
        s_scr[:] = jnp.where(fresh_ref[b] != 0, jnp.zeros_like(s), s)

    @pl.when(c * block < q_len)
    def _body():
        f32 = jnp.float32
        n = jnp.minimum(q_len - c * block, block).astype(f32)
        i = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0).astype(f32)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1).astype(f32)
        inside = (j <= i) & (j < n)
        for h in range(heads):        # static: the heads of one grid step
            q = q_ref[0, h].astype(f32)                  # [C, hd]
            k = k_ref[0, h].astype(f32)
            v = v_ref[0, h].astype(f32)
            slope = slope_ref[h][:, :1]                  # [1, 1]
            S = s_scr[h]
            dec = jnp.where(inside,
                            jnp.exp(-slope * jnp.maximum(i - j, 0.0)), 0.0)
            a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * dec
            o = jnp.dot(a, v, preferred_element_type=f32) \
                + jnp.exp(-slope * (i + 1.0)) \
                * jnp.dot(q, S, preferred_element_type=f32)
            o_ref[0, h] = o.astype(o_ref.dtype)
            w = jnp.where(i < n,
                          jnp.exp(-slope * jnp.maximum(n - 1.0 - i, 0.0)),
                          0.0)                           # [C, 1]
            s_scr[h] = jnp.exp(-slope * n) * S + jax.lax.dot_general(
                k * w, v, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)

    @pl.when(c == last_c)
    def _store():
        s_out_ref[0, 0] = s_scr[:]


def _lightning_pallas(q, k, v, state, slopes, query_lens, fresh, layer,
                      block, interpret):
    state, layer, single = _stacked(state, layer)
    B, H, Q, hd = q.shape
    block = min(block, Q)
    if Q % block:
        raise ValueError(f"chunk width {Q} is not a multiple of {block}")
    # several heads a grid step: a step costs some 0.4 us whatever it does,
    # and a decode row's step is one head's 128 KiB of state
    heads = next(n for n in (8, 4, 2, 1) if H % n == 0)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    slope_b = jnp.broadcast_to(
        slopes.astype(jnp.float32)[:, None, None], (H, 1, 128))

    def chunk_block(b, h, c, ql, fr, lyr):
        # past a row's last sub-chunk the index stays: no new DMA, and the
        # output block written there is the one already computed
        return (b, h, jnp.minimum(c, jnp.maximum(ql[b] - 1, 0) // block), 0)

    def state_block(b, h, c, ql, fr, lyr):
        return (lyr[0], b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // heads, Q // block),
        in_specs=[
            pl.BlockSpec((1, heads, block, hd), chunk_block),
            pl.BlockSpec((1, heads, block, hd), chunk_block),
            pl.BlockSpec((1, heads, block, hd), chunk_block),
            pl.BlockSpec((heads, 1, 128), lambda b, h, c, *_: (h, 0, 0)),
            pl.BlockSpec((1, 1, heads, hd, hd), state_block),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, block, hd), chunk_block),
            pl.BlockSpec((1, 1, heads, hd, hd), state_block),
        ],
        scratch_shapes=[pltpu.VMEM((heads, hd, hd), jnp.float32)],
    )
    o, new_state = pl.pallas_call(
        functools.partial(_lightning_kernel, block=block, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 7 (after the 3 prefetch scalars: q, k, v, slopes, state)
        input_output_aliases={7: 1},
        interpret=interpret,
        name="lightning_attention",
    )(query_lens, fresh, layer, q, k, v, slope_b, state)
    # rows that ran no sub-chunk wrote no output block
    live = (jnp.arange(Q)[None, :] < query_lens[:, None])[:, None, :, None]
    o = jnp.where(live, o, jnp.zeros_like(o))
    return o, (new_state[0] if single else new_state)


# -------------------------------------------------------------- public API


def lightning_attention(q, k, v, state, slopes, query_lens, fresh, *,
                        layer=None, path=None, block=128):
    """Decayed linear attention of a ragged batch against its per-row
    state (see the module docstring).  ``path`` is one of
    ``dispatch.MOSAIC`` / ``INTERPRET`` / ``REFERENCE``; ``None`` takes
    the Mosaic kernel on a TPU and the recurrence elsewhere."""
    if (state.ndim == 5) != (layer is not None):
        raise ValueError("a stacked [L, B, H, hd, hd] state comes with "
                         "its `layer`, a one-layer state without")
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    query_lens = query_lens.astype(jnp.int32)
    fresh = fresh.astype(jnp.int32)
    if path == dispatch.REFERENCE:
        return _lightning_ref(q, k, v, state, slopes, query_lens, fresh,
                              layer)
    return _lightning_pallas(q, k, v, state, slopes, query_lens, fresh,
                             layer, block,
                             interpret=(path == dispatch.INTERPRET))

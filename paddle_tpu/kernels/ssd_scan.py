"""Selective state-space scan (Mamba-2 / SSD) over ragged rows with a
recurrent state per row and head.

A state-space mixer keeps, per row and head, one ``[P, N]`` float32 state
(``P`` the head's channels, ``N`` the state size)

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D_h x_t

whose decay ``exp(dt_t A_h)`` depends on the token (``dt_t`` is computed
from it), which is what ``kernels/lightning_attention.py`` cannot express:
its decay is one constant per head, its state is square, and its keys are
per head where ``B_t`` and ``C_t`` here are shared by the ``H / G`` heads
of a group (Mamba-2, arXiv:2405.21060).  The serving engine hands this
kernel the same ragged batch it hands the attention kernels: row ``b``
contributes ``query_lens[b]`` tokens (a prompt chunk, one decode token, or
none), and the row's state lives in its batch slot of a stacked
``[L, B, H, P, N]`` pool that the step carries in place.

Per row and head a chunk is processed in sub-chunks of ``block`` tokens.
With ``cum_i = sum_{k <= i} dt_k A_h`` inside the sub-chunk: the intra part
``((C B^T) * L) (dt x)`` with ``L[i, j] = exp(cum_i - cum_j)`` for
``j <= i``, the carried state's part ``exp(cum_i) * (C_i S^T)``, and the
state ``S' = exp(cum_n) S + sum_j exp(cum_n - cum_j) (dt_j x_j) (x) B_j``.
A padded slot has ``dt = 0``: it decays nothing and adds nothing, so the
sums need no mask.  A decode row is the chunk of one and takes the same
body over the sub-chunk's first ``_SMALL`` slots, not over its width.  A
row with ``fresh[b]`` set starts from a zero state (a newly admitted or
recomputed request in a reused slot: no separate reset program); an idle
row (``query_lens[b] == 0``) leaves the state as it was.

The cumulative sums are float32 and made outside the kernel (``[B, H,
Q]``: a megabyte); the products take their operands in ``x``'s type (bf16
into the unit, float32 out of it; float32 operands under the interpreter)
and the state is float32 throughout.

Layouts:
  x           [B, H, Q, P]    padded per row; slots past ``query_lens[b]``
                              must hold zeros (the caller scatters into
                              zeros)
  dt          [B, H, Q]       float32, after the softplus; padded slots
                              are taken as 0 whatever they hold
  Bm, Cm      [B, G, Q, N]    ``G`` divides ``H``; head ``h`` reads group
                              ``h // (H / G)``
  A, D        [H] float32     ``A`` negative
  state       [L, B, H, P, N] float32 with ``layer`` (traced int32), or
              [B, H, P, N] without
  query_lens  [B] int32
  fresh       [B] bool/int32  start from zero instead of the stored state
Returns ``(y [B, H, Q, P] in x.dtype, state)`` with the state updated in
place (aliased) on the kernel path; ``y`` is zero in padded slots.

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

__all__ = ["ssd_scan"]

# the query slots the narrow body computes: every decode row
_SMALL = 16


def _stacked(state, layer):
    if state.ndim == 4:
        return state[None], 0, True
    return state, layer, False


def _live(Q, query_lens):
    return jnp.arange(Q)[None, :] < query_lens[:, None]            # [B, Q]


# ---------------------------------------------------------------- reference


def _ssd_ref(x, dt, Bm, Cm, A, D, state, query_lens, fresh, layer):
    state, layer, single = _stacked(state, layer)
    B, H, Q, P = x.shape
    rep = H // Bm.shape[1]
    A = A.astype(jnp.float32)[None, :, None, None]
    D = D.astype(jnp.float32)[None, :, None]
    s0 = jnp.where(fresh.astype(bool)[:, None, None, None], 0.0,
                   state[layer])

    def step(S, xs):
        xt, dtt, bt, ct, t = xs         # [B,H,P] [B,H] [B,H,N] [B,H,N]
        live = (t < query_lens)[:, None, None, None]
        d = dtt[..., None, None]
        S_new = jnp.exp(d * A) * S + d * xt[..., :, None] * bt[..., None, :]
        S = jnp.where(live, S_new, S)
        y = jnp.einsum("bhpn,bhn->bhp", S, ct) + D * xt
        return S, jnp.where(live[..., 0], y, 0.0)

    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 2, 0)
    heads = lambda a: jnp.repeat(a, rep, axis=1)       # group -> its heads
    S, y = jax.lax.scan(step, s0, (f32(x), f32(dt), f32(heads(Bm)),
                                   f32(heads(Cm)), jnp.arange(Q)))
    state = state.at[layer].set(S)
    y = jnp.moveaxis(y, 0, 2).astype(x.dtype)
    return y, (state[0] if single else state)


# ------------------------------------------------------------------- kernel


def _ssd_kernel(qlen_ref, fresh_ref, layer_ref, x_ref, b_ref, c_ref,
                cum_ref, cum_t_ref, dt_t_ref, d_ref, s_in_ref, y_ref,
                s_out_ref, s_scr, *, block, heads, small):
    del layer_ref                     # only the state's index_maps read it
    b = pl.program_id(0)
    c = pl.program_id(2)
    q_len = qlen_ref[b]
    last_c = jnp.maximum(q_len - 1, 0) // block
    in_block = q_len - c * block      # the row's tokens in this sub-chunk

    @pl.when(c == 0)
    def _load():
        s = s_in_ref[0, 0]
        s_scr[:] = jnp.where(fresh_ref[b] != 0, jnp.zeros_like(s), s)

    def body(R):
        """The sub-chunk's first ``R`` slots (static) against the carried
        state."""
        f32, cd = jnp.float32, x_ref.dtype
        i = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
        Bm = b_ref[0, 0, :R, :]                               # [R, N]
        Cm = c_ref[0, 0, :R, :]
        # one group's heads share the scores of C against B
        G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)   # [R, R]
        for h in range(heads):        # static: the heads of one grid step
            x = x_ref[0, h, :R, :]                            # [R, P]
            cum_row = cum_ref[0, h:h + 1, :R]                 # [1, R]
            cum_col = cum_t_ref[0, 0, :R, h:h + 1]            # [R, 1]
            dt_col = dt_t_ref[0, 0, :R, h:h + 1]
            d = d_ref[h][:, :1]                               # [1, 1]
            S = s_scr[h]                                      # [P, N]
            # cum falls with the slot: the exponent is <= 0 where j <= i
            L = jnp.where(j <= i,
                          jnp.exp(jnp.minimum(cum_col - cum_row, 0.0)), 0.0)
            xd = x.astype(f32) * dt_col                       # dt_j x_j
            y = jnp.dot((G * L).astype(cd), xd.astype(cd),
                        preferred_element_type=f32) \
                + jnp.exp(cum_col) * jax.lax.dot_general(
                    Cm, S.astype(cd), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) \
                + d * x.astype(f32)
            y_ref[0, h, :R, :] = y.astype(y_ref.dtype)
            # the last slot's (padded slots add nothing); a sum, because
            # Mosaic does not broadcast a [1, 1] cut from the middle
            total = jnp.sum(jnp.where(i == R - 1, cum_col, 0.0), axis=0,
                            keepdims=True)
            w = jnp.exp(total - cum_col)                      # [R, 1]
            s_scr[h] = jnp.exp(total) * S + jax.lax.dot_general(
                (xd * w).astype(cd), Bm, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)

    if small < block:
        pl.when((in_block > 0) & (in_block <= small))(lambda: body(small))
        pl.when(in_block > small)(lambda: body(block))
    else:
        pl.when(in_block > 0)(lambda: body(block))

    @pl.when(c == last_c)
    def _store():
        s_out_ref[0, 0] = s_scr[:]


def _ssd_pallas(x, dt, Bm, Cm, A, D, state, query_lens, fresh, layer, block,
                interpret):
    state, layer, single = _stacked(state, layer)
    B, H, Q, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    block = min(block, Q)
    if Q % block:
        raise ValueError(f"chunk width {Q} is not a multiple of {block}")
    per_group = H // G
    # several heads a grid step, all of one group: a step costs some 0.4 us
    # whatever it does, and a decode row's step is one head's state
    heads = next(n for n in (8, 4, 2, 1) if per_group % n == 0)
    nb = Q // block
    f32 = jnp.float32
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    dt = jnp.where(_live(Q, query_lens)[:, None, :], dt.astype(f32), 0.0)
    cum = jnp.cumsum(
        (dt * A.astype(f32)[None, :, None]).reshape(B, H, nb, block),
        axis=-1).reshape(B, H, Q)
    # the same per slot as a column: [B, head block, Q, heads of the block]
    columns = lambda a: a.reshape(B, H // heads, heads, Q).transpose(
        0, 1, 3, 2)
    d_b = jnp.broadcast_to(D.astype(f32)[:, None, None], (H, 1, 128))

    def sub_chunk(b, ql, c):
        # past a row's last sub-chunk the index stays: no new DMA, and the
        # output block written there is the one already computed
        return jnp.minimum(c, jnp.maximum(ql[b] - 1, 0) // block)

    def x_block(b, h, c, ql, fr, lyr):
        return (b, h, sub_chunk(b, ql, c), 0)

    def group_block(b, h, c, ql, fr, lyr):
        return (b, h * heads // per_group, sub_chunk(b, ql, c), 0)

    def cum_block(b, h, c, ql, fr, lyr):
        return (b, h, sub_chunk(b, ql, c))

    def state_block(b, h, c, ql, fr, lyr):
        return (lyr[0], b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // heads, nb),
        in_specs=[
            pl.BlockSpec((1, heads, block, P), x_block),
            pl.BlockSpec((1, 1, block, N), group_block),
            pl.BlockSpec((1, 1, block, N), group_block),
            pl.BlockSpec((1, heads, block), cum_block),
            pl.BlockSpec((1, 1, block, heads), x_block),
            pl.BlockSpec((1, 1, block, heads), x_block),
            pl.BlockSpec((heads, 1, 128), lambda b, h, c, *_: (h, 0, 0)),
            pl.BlockSpec((1, 1, heads, P, N), state_block),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, block, P), x_block),
            pl.BlockSpec((1, 1, heads, P, N), state_block),
        ],
        scratch_shapes=[pltpu.VMEM((heads, P, N), f32)],
    )
    y, new_state = pl.pallas_call(
        functools.partial(_ssd_kernel, block=block, heads=heads,
                          small=min(_SMALL, block)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 10 (after the 3 prefetch scalars: x, B, C, cum, its
        # columns, dt's columns, D, state)
        input_output_aliases={10: 1},
        interpret=interpret,
        name="ssd_scan",
    )(query_lens, fresh, layer, x, Bm, Cm, cum, columns(cum), columns(dt),
      d_b, state)
    # slots the body did not reach were never written
    y = jnp.where(_live(Q, query_lens)[:, None, :, None], y,
                  jnp.zeros_like(y))
    return y, (new_state[0] if single else new_state)


# -------------------------------------------------------------- public API


def ssd_scan(x, dt, Bm, Cm, A, D, state, query_lens, fresh, *, layer=None,
             path=None, block=128):
    """The selective scan of a ragged batch against its per-row state (see
    the module docstring).  ``path`` is one of ``dispatch.MOSAIC`` /
    ``INTERPRET`` / ``REFERENCE``; ``None`` takes the Mosaic kernel on a
    TPU and the recurrence elsewhere."""
    if (state.ndim == 5) != (layer is not None):
        raise ValueError("a stacked [L, B, H, P, N] state comes with its "
                         "`layer`, a one-layer state without")
    if x.shape[1] % Bm.shape[1]:
        raise ValueError("the groups do not divide the heads")
    path = dispatch.resolve_path(path, off_tpu=dispatch.REFERENCE)
    query_lens = query_lens.astype(jnp.int32)
    fresh = fresh.astype(jnp.int32)
    if path == dispatch.REFERENCE:
        return _ssd_ref(x, dt, Bm, Cm, A, D, state, query_lens, fresh, layer)
    return _ssd_pallas(x, dt, Bm, Cm, A, D, state, query_lens, fresh, layer,
                       block, interpret=(path == dispatch.INTERPRET))

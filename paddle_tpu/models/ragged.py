"""The ragged batch: the one format between the serving scheduler and every
served model's step.

A step advances ``B`` batch rows at once, each at its own point in its
life: a prompt chunk of several tokens, a decode row of one, or idle.  The
scheduler (``serving/engine.py`` ``Engine._pack``) packs the query tokens
of all rows into one flat axis of static width ``T`` and hands the model's
step a :class:`RaggedBatch`; the step (``gpt_ragged_step``,
``hybrid_ragged_step``) reads it through a :class:`RaggedView`.  What the
six arrays mean, how padding is marked and how a packed token finds its
row, its position and its place in the page pool is written here and
nowhere else: a change of the format (one packed transfer) is an edit to
this file and to ``Engine._pack``.  The one entry a model's step never sees
as the scheduler wrote it is a token the host does not hold yet
(:func:`pending_token`): the engine's jitted wrapper puts the id in its
place (:func:`resolve_pending`) before it calls the step.

Nothing here imports ``paddle_tpu.serving``: this is the lowest layer the
model steps and, through ``serving/model.py``, the engine both import.
"""
from __future__ import annotations

import collections
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RaggedBatch", "WindowRaggedBatch", "RaggedView", "empty_batch",
           "batch_shapes", "pending_token", "resolve_pending"]


class RaggedBatch(NamedTuple):
    """Six int32 arrays, in the order the jitted step receives them (a
    ``NamedTuple`` is a pytree: the program's operands are these six).

    Packing contract.  ``tokens`` [T] holds every scheduled query token,
    row-major: row ``b``'s ``query_lens[b]`` tokens are contiguous and in
    order, and rows are packed in ascending batch-slot order, so row
    ``b``'s last token sits at ``cumsum(query_lens)[b] - 1``.  ``rows`` [T]
    names each token's batch row and is ``B`` for a padding slot, which is
    dropped everywhere; ``slots`` [T] is the token's index within its
    row's chunk.  ``query_lens`` [B] is 0 for an idle row.
    ``context_lens`` [B] counts the row's total tokens *including* this
    chunk, so token ``t`` of row ``b`` sits at absolute position
    ``context_lens[b] - query_lens[b] + t``.  ``page_tables``
    [B, max_pages] maps a row's logical page (position // page size) to
    its physical page in the pools; entries past the row's context are
    never read.

    A token that is still on the device.  The scheduler dispatches a step
    while the one before it runs, so a decode row's newest token may be
    one the host has not read: in its place ``tokens`` holds
    ``pending_token(b) = -(b + 1)``, "the id the step before this one
    chose for batch slot ``b``".  Ids are never negative, so a negative
    entry is always that marker.  ``resolve_pending`` replaces it from the
    previous step's ``ids [B]`` inside the engine's jitted program, before
    the model's step runs: a model's step sees ids only.
    """
    tokens: object
    rows: object
    slots: object
    query_lens: object
    context_lens: object
    page_tables: object


WindowRaggedBatch = collections.namedtuple(
    "WindowRaggedBatch", RaggedBatch._fields + ("window_page_tables",))
WindowRaggedBatch.__doc__ = """The batch of a model that keeps pools of kind
``"window_pages"`` (sliding-window attention layers): the six arrays of
:class:`RaggedBatch`, under the same contract, and a seventh.
``window_page_tables`` [B, max_pages] is the same map as ``page_tables`` for
the window pools, whose pages behind the window the cache manager has given
back: their entries are stale and never read (a window layer lists only the
pages its window reaches)."""


def pending_token(slot):
    """What ``tokens`` holds for a token the previous step chose for batch
    slot ``slot`` and the host has not read."""
    return -(slot + 1)


def resolve_pending(batch: RaggedBatch, prev_ids):
    """``batch`` with every :func:`pending_token` replaced by the id it
    names in ``prev_ids [B]``, the ids the previous step chose by batch
    slot.  Traced, inside the engine's jitted step."""
    tokens = batch.tokens
    slot = jnp.clip(-tokens - 1, 0, prev_ids.shape[0] - 1)
    return batch._replace(
        tokens=jnp.where(tokens < 0, jnp.take(prev_ids, slot), tokens))


def _shapes(B, T, max_pages, window_tables=False):
    six = ((T,), (T,), (T,), (B,), (B,), (B, max_pages))
    return WindowRaggedBatch(*six, (B, max_pages)) if window_tables \
        else RaggedBatch(*six)


def empty_batch(B, T, max_pages, window_tables=False):
    """The host batch (numpy) of ``B`` idle rows and ``T`` padding slots,
    for the scheduler to fill row by row; ``window_tables`` for a model
    with window layers (a :class:`WindowRaggedBatch`)."""
    shapes = _shapes(B, T, max_pages, window_tables)
    batch = type(shapes)(*(np.zeros(s, np.int32) for s in shapes))
    batch.rows[:] = B                            # B marks a padding slot
    return batch


def batch_shapes(B, T, max_pages, sharding=None, window_tables=False):
    """The batch as ``ShapeDtypeStruct``s: what a lowering of the step
    takes in place of ``empty_batch``'s arrays."""
    shapes = _shapes(B, T, max_pages, window_tables)
    return type(shapes)(*(jax.ShapeDtypeStruct(s, jnp.int32,
                                               sharding=sharding)
                          for s in shapes))


class RaggedView:
    """What a model step derives from a :class:`RaggedBatch`, computed once
    a step (trace-time Python around a handful of index operations).

    Static sizes: ``max_q`` bounds any single row's chunk and is the padded
    query width ``Q`` handed to the attention kernels (``None``: ``T``);
    ``max_seq_len`` clips positions; ``num_pages`` and ``page_size`` are
    the page pools'.

    Per packed token, ``[T]``: ``row`` (its batch row, clamped into range
    for a padding slot), ``valid`` (a real token of a live row), ``pos``
    (its absolute position), and its scatter target in a page pool,
    ``page`` and ``slot_in_page`` — a masked token's ``page`` is
    ``num_pages``, out of range, so that a scatter with ``mode="drop"``
    discards it — and, where the batch has window tables,
    ``window_page`` likewise in the window pools (``num_window_pages``).
    Per row, ``[B]``: ``fresh`` (the chunk starts at position 0: a newly
    admitted or recomputed request).

    Compute is flat ``[T, ...]`` (a decode row costs one token, not a
    padded chunk); only a kernel that wants one padded row per request
    sees ``[B, Q, ...]``, through :meth:`pad` and :meth:`unpad`, and one
    that takes tiles of query slots ``[NT, n, ...]`` through
    :meth:`pad_tiles` and :meth:`unpad_tiles`.
    """

    @jax.named_scope("batch_view")
    def __init__(self, batch: RaggedBatch, *, max_q, max_seq_len, num_pages,
                 page_size, num_window_pages=None):
        self.batch = batch
        self.T = T = batch.tokens.shape[0]
        self.B = B = batch.query_lens.shape[0]
        self.Q = Q = max_q or T
        rows, slots = batch.rows, batch.slots
        query_lens, context_lens = batch.query_lens, batch.context_lens
        self.row = row = jnp.minimum(rows, B - 1)
        self.valid = valid = ((rows < B)
                              & (slots < jnp.take(query_lens, row)))
        self.pos = pos = jnp.clip(
            jnp.take(context_lens - query_lens, row) + slots, 0,
            max_seq_len - 1)
        def page_in(tables, pool_pages):
            page_of_pos = jnp.take_along_axis(
                jnp.take(tables, row, axis=0),
                (pos // page_size)[:, None], axis=1)[:, 0]
            return jnp.where(valid, page_of_pos, pool_pages)

        self.page = page_in(batch.page_tables, num_pages)
        if isinstance(batch, WindowRaggedBatch):
            self.window_page = page_in(batch.window_page_tables,
                                       num_window_pages)
        self.slot_in_page = pos % page_size
        # the padded [B, Q] place of each token; masked tokens go to row B
        self._pad_row = jnp.where(valid, row, B)
        self._pad_slot = jnp.minimum(slots, Q - 1)
        self.fresh = (context_lens - query_lens) == 0
        self._tilings = {}

    def pad(self, a, fill=0):
        """``a [T, ...]`` as one padded row per request, ``[B, Q, ...]``,
        ``fill`` wherever no valid token landed."""
        return jnp.full((self.B, self.Q) + a.shape[1:], fill, a.dtype).at[
            self._pad_row, self._pad_slot].set(a, mode="drop")

    def unpad(self, a, q_axis=1):
        """``a [B, Q, ...]`` back to packed order, ``[T, ...]``; a padding
        slot reads some live slot's junk, which never reaches pages or
        logits.  ``q_axis`` is where ``a`` has its query slots: 2 for a
        head-major kernel's ``[B, H, Q, ...]``, read in place (a transpose
        before the gather is a copy XLA does not fold) into
        ``[T, H, ...]``."""
        return a[(self.row, *(slice(None),) * (q_axis - 1), self._pad_slot)]

    def _tiling(self, n):
        """The packed tokens regrouped into tiles of ``n`` query slots of
        one row each — one tile for a decode row, one more for every ``n``
        tokens of a chunk: ``(number of tiles, tile of each token [T] (the
        number of tiles for a masked token: out of bounds), row of each
        tile [NT] (``B``: unused), index of each tile within its row's
        chunk [NT])``.  Static count: a row and ``T / n`` more."""
        if n not in self._tilings:
            B, T = self.B, self.T
            nt = B + -(-T // n)
            per_row = -(-self.batch.query_lens // n)
            ends = jnp.cumsum(per_row)
            first = ends - per_row
            tile = jnp.where(self.valid, jnp.take(first, self.row)
                             + self.batch.slots // n, nt)
            t = jnp.arange(nt, dtype=jnp.int32)
            rows = jnp.sum(t[:, None] >= ends[None, :], axis=1).astype(
                jnp.int32)                       # B once past every row
            index = t - jnp.take(first, jnp.minimum(rows, B - 1))
            self._tilings[n] = (nt, tile, rows, index.astype(jnp.int32))
        return self._tilings[n]

    def tiles(self, n):
        """``(tile_rows [NT], tile_index [NT])`` of :meth:`pad_tiles`'s
        layout, as a kernel's ``q_tiles=`` takes them."""
        return self._tiling(n)[2:]

    def pad_tiles(self, a, n, fill=0):
        """``a [T, ...]`` in tiles of ``n`` query slots of one row each,
        ``[NT, n, ...]``: a decode row costs one tile, not a padded chunk
        (``pad`` gives every row the widest chunk's width)."""
        nt, tile, *_ = self._tiling(n)
        return jnp.full((nt, n) + a.shape[1:], fill, a.dtype).at[
            tile, self.batch.slots % n].set(a, mode="drop")

    def unpad_tiles(self, a):
        """``a [NT, n, ...]`` back to packed order, ``[T, ...]`` (a
        padding slot reads some live slot's junk, as ``unpad``)."""
        nt, n = a.shape[:2]
        tile = self._tiling(n)[1]
        return a[jnp.minimum(tile, nt - 1), self.batch.slots % n]

    def last(self, x):
        """``x [T, ...]`` at each row's last packed token, ``[B, ...]``:
        where a decode row or a prompt-completing chunk reads its
        next-token distribution (an idle row reads garbage the engine
        ignores)."""
        at = jnp.clip(jnp.cumsum(self.batch.query_lens) - 1, 0, self.T - 1)
        return jnp.take(x, at, axis=0)

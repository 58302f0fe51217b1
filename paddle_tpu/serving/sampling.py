"""Choosing each row's next token on the device.

The engine's jitted step ends here: after the model's step has produced
``logits [B, V]``, :func:`sample_tokens` turns them into ``[B]`` int32
ids inside the same program, so the host reads 4 bytes a row and never
the table of logits.  It implements the whole of ``SamplingParams``:

- greedy (``temperature <= 0``): ``argmax`` of the row, the first index
  on a tie;
- otherwise the row is divided by the temperature; the ``top_k`` largest
  stay, with every tie of the k-th; a softmax over what stayed; the
  smallest prefix of the descending order whose mass reaches ``top_p``
  stays (an entry stays while the mass *before* it is under ``top_p``,
  equal values in index order, the first always); one draw from what is
  left, by the Gumbel maximum.  No sort: the k-th largest value and the
  value at which the mass reaches ``top_p`` are each found bit by bit
  (:func:`_threshold`, 32 masked sums over the row), because a sort of
  50,304 entries takes the TPU's compiler 22 s to build and a masked sum
  some microseconds to run.

Arithmetic is float32.  The draw's key is the request's 64-bit ``seed``
(the two words of a threefry key) folded with the position of the token
being sampled, the row's ``context_lens`` entry: a request's stream is a
function of (seed, position, its own logits) and of nothing else — not
of its co-tenants, nor of having been preempted and recomputed, nor of
having been moved to another replica with its output so far as prompt.

One program serves every mix of rows: all of that runs under a
``lax.cond`` on "some live row that is owed a token asked for
``temperature > 0``", so a step whose rows are all greedy runs an
``argmax`` and nothing else.
The rows' parameters reach the device as one small table by batch slot
(:func:`slot_entry`, :data:`GREEDY`), sent again only when a slot's new
tenant changed it; a greedy row's entry is all zeros whatever its other
fields, so an engine that serves greedy requests sends the table once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["GREEDY", "slot_entry", "sample_tokens", "any_stochastic"]

#: columns of the table, all uint32: the float32 bits of temperature and
#: top_p, top_k, the seed's low and high word, and the prompt's length
#: (a row is owed a token once its context covers its prompt)
_TEMPERATURE, _TOP_K, _TOP_P, _SEED_LO, _SEED_HI, _PROMPT_LEN = range(6)

#: the entry of a greedy row, and of a slot that never had a tenant
GREEDY = (0,) * 6


def _bits(x):
    return int(np.float32(x).view(np.uint32))


def slot_entry(sampling, prompt_len):
    """A request's row of the table, as a tuple of six ints."""
    if sampling.temperature <= 0.0:
        return GREEDY
    seed = int(sampling.seed)
    return (_bits(sampling.temperature), max(0, int(sampling.top_k)),
            _bits(sampling.top_p), seed & 0xFFFFFFFF,
            (seed >> 32) & 0xFFFFFFFF, int(prompt_len))


def _float(table, column):
    return lax.bitcast_convert_type(table[..., column], jnp.float32)


def any_stochastic(table, query_lens, context_lens):
    """Does some live row that is owed a token ask for a draw?  The
    predicate of the step's ``cond``; the engine counts the same thing
    on the host for ``serving_sample_steps_total``."""
    owed = (query_lens > 0) & (
        context_lens >= table[:, _PROMPT_LEN].astype(jnp.int32))
    return jnp.any(owed & (_float(table, _TEMPERATURE) > 0.0))


def _ordered_keys(x):
    """float32 -> uint32 in the floats' own order (-0.0 beside +0.0)."""
    bits = lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _threshold(keys, weights, need):
    """The largest uint32 ``t`` with ``sum(weights[keys >= t]) >= need``
    (0 where none has), built from the top bit down: the sum falls as
    ``t`` rises, so each bit stays set if the sum still suffices."""
    def bit(i, t):
        higher = t | (jnp.uint32(1 << 31) >> i)
        enough = jnp.sum(jnp.where(keys >= higher, weights, 0)) >= need
        return jnp.where(enough, higher, t)

    return lax.fori_loop(0, 32, bit, jnp.uint32(0))


def _draw_row(logits, entry, position):
    """One row's draw; ``logits [V]`` float32."""
    V = logits.shape[0]
    temperature, top_p = _float(entry, _TEMPERATURE), _float(entry, _TOP_P)
    top_k = entry[_TOP_K].astype(jnp.int32)
    # a greedy row beside a stochastic one passes through here too and
    # is discarded by the caller: divide by 1, not by 0
    scaled = logits / jnp.where(temperature > 0.0, temperature, 1.0)
    keys = _ordered_keys(scaled)
    k = jnp.where((top_k > 0) & (top_k < V), top_k, V)
    in_k = keys >= _threshold(keys, jnp.ones((V,), jnp.int32), k)
    scaled = jnp.where(in_k, scaled, -jnp.inf)
    probs = jax.nn.softmax(scaled)
    # the value at which the descending order's mass reaches top_p: what
    # lies above it stays, and of its equals the first few by index (what
    # top_k removed has no mass, lies under the cut and is -inf already)
    cut = _threshold(keys, probs, top_p)
    at_cut = keys == cut
    above = jnp.sum(jnp.where(keys > cut, probs, 0.0))
    before = above + (jnp.cumsum(at_cut) - at_cut) * jnp.max(
        jnp.where(at_cut, probs, 0.0))
    in_p = (keys > cut) | (at_cut & (before < top_p)) | (top_p >= 1.0)
    first = lax.iota(jnp.int32, V) == jnp.argmax(scaled)
    key = jax.random.fold_in(
        jax.random.wrap_key_data(
            jnp.stack([entry[_SEED_HI], entry[_SEED_LO]]),
            impl="threefry2x32"),
        position)
    return jax.random.categorical(
        key, jnp.where(in_p | first, scaled, -jnp.inf))


def sample_tokens(logits, table, query_lens, context_lens):
    """``[B]`` int32 ids from ``logits [B, V]``: each row by its entry of
    ``table [B, 6]`` (uint32, :func:`slot_entry`); ``query_lens`` and
    ``context_lens`` are the step's ``RaggedBatch`` fields.  An idle
    row's id is garbage the engine ignores."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def stochastic():
        drawn = jax.vmap(_draw_row)(logits.astype(jnp.float32), table,
                                    context_lens)
        return jnp.where(_float(table, _TEMPERATURE) > 0.0,
                         drawn.astype(jnp.int32), greedy)

    return lax.cond(any_stochastic(table, query_lens, context_lens),
                    stochastic, lambda: greedy)

"""Operations and bytes that the mathematics of the hybrid decoder's serving
step requires (block-sparse attention layers beside lightning
linear-attention layers), computed from shapes and from what the window
counted.  The peaks and ``least_seconds`` are ``roofline.py``'s.

Counted: 2 per matrix parameter of every processed token (projections,
both gates, the gated MLP and the head); in each sparse layer the two
products of a query with the positions it reads — its *selected* positions,
as the program's ``serving_attention_positions_total{kind="selected"}``
counts them from the lengths alone; in each lightning layer the products
with the state (``q S`` and ``k^T v``: 4 * heads * hd^2 a token) and the
chunk's own masked square.  Not counted: scoring the compressed keys and
ranking the blocks (the cost of selecting, which a better selection may
change), norms, rotations, gates' sigmoids.
"""
from __future__ import annotations

from benchmark import reference_hybrid

SPARSE, LIGHTNING = reference_hybrid.SPARSE, reference_hybrid.LIGHTNING


def chunk_pairs(prefill_tokens, prefill_chunks, decode_tokens):
    """(query, key) pairs inside the rows' own chunks: a chunk of ``q``
    tokens has ``q (q + 1) / 2``, a decode token 1.  The window counts
    tokens and chunks, not each chunk's length; with every chunk at the
    mean length the sum is least (the square is convex), so this is a
    lower bound."""
    pairs = float(decode_tokens)
    if prefill_chunks:
        q = prefill_tokens / prefill_chunks
        pairs += prefill_chunks * q * (q + 1) / 2
    return pairs


def sparse_attention_ops(s, selected_positions):
    """One sparse layer: scores and weighted values, 2 products of
    ``heads * head_dim`` multiply-adds per position read."""
    return 4 * s.H * s.hd * selected_positions


def lightning_ops(s, tokens, pairs):
    """One lightning layer: per token the two products with the state, and
    per pair inside a chunk the score and the weighted value."""
    return 4 * s.Hl * s.hdl * s.hdl * tokens + 4 * s.Hl * s.hdl * pairs


def step_flops(s, tokens, selected_positions, pairs):
    """The whole step's required operations for ``tokens`` processed."""
    return (2 * s.matmul_params() * tokens
            + s.count(SPARSE) * sparse_attention_ops(s, selected_positions)
            + s.count(LIGHTNING) * lightning_ops(s, tokens, pairs))


def sparse_attention_bytes(s, read_positions, span_reads, itemsize=2):
    """One sparse layer: keys and values of the positions the live rows
    read, and the compressed keys their selection scored."""
    row = s.Hkv * s.hd * itemsize
    return 2 * read_positions * row + span_reads * row


def lightning_bytes(s, row_steps):
    """One lightning layer: each live row's float32 state read and
    written once a step."""
    return 2 * row_steps * s.Hl * s.hdl * s.hdl * 4


def row_reads(s, context):
    """For one live row with ``context`` cached positions after a step:
    (positions whose keys and values the step had to read for it at
    least, compressed keys it had to score) in each sparse layer.  A row
    within ``dense_len`` reads its context; past it, its newest token's
    ``topk`` blocks (a chunk's tokens share most of theirs) and every
    compressed key."""
    if context <= s.dense_len:
        return context, 0
    return (min(context, s.topk * s.block_size),
            max((context - s.kernel_size) // s.kernel_stride + 1, 0))

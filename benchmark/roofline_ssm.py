"""Operations and bytes that the mathematics of the parallel-mixer decoder's
serving step requires (a state-space mixer beside grouped-query attention in
every block), computed from shapes and from what the window counted.  The
peaks and ``least_seconds`` are ``roofline.py``'s.

Counted: 2 per matrix parameter of a block for every processed token (the
attention and state-space projections and the gated MLP); 2 per parameter
of the head only for the rows owed a token (a prompt's other tokens never
reach the head: counting its 1.34 B parameters for them would overstate by
half); in each layer the two products of a query with every position of
its context (dense, as the program's
``serving_attention_positions_total{kind="context"}`` counts them from the
lengths alone), the scan's two products with the state (``dt x (x) B`` and
``S C``: 4 * heads * d_head * d_state a token) and the chunk's own masked
square (``C B^T`` once a group, its product with ``dt x`` once a head).  Not
counted: the convolution (4 multiply-adds a channel), norms, rotations,
gates, the decay's exponentials.
"""
from __future__ import annotations

from benchmark.roofline_hybrid import chunk_pairs


def processed(counts):
    """``(tokens, pairs)`` the window's steps processed, from what the
    program counted: every prompt token and one token for each decode row
    (``serving_state_row_steps_total{kind="decode"}``), and the (query,
    key) pairs inside the rows' own chunks."""
    decode = counts["state_rows_decode"]
    return (counts["prefill_tokens"] + decode,
            chunk_pairs(counts["prefill_tokens"], counts["prefill_chunks"],
                        decode))


def attention_ops(s, context_positions):
    """One layer: scores and weighted values, 2 products of ``heads *
    head_dim`` multiply-adds per position read."""
    return 4 * s.H * s.hd * context_positions


def scan_ops(s, tokens, pairs):
    """One layer: per token the two products with the state, and per
    (query, key) pair inside a chunk the score (shared by a group's heads)
    and the weighted input."""
    return (4 * s.Hs * s.P * s.N * tokens
            + (2 * s.G * s.N + 2 * s.Hs * s.P) * pairs)


def step_flops(s, tokens, head_rows, context_positions, pairs):
    """The whole step's required operations for ``tokens`` processed, of
    which ``head_rows`` were owed a token."""
    return (2 * s.L * s.layer_matmul_params() * tokens
            + 2 * s.head_params() * head_rows
            + s.L * attention_ops(s, context_positions)
            + s.L * scan_ops(s, tokens, pairs))


def attention_bytes(s, read_positions, itemsize=2):
    """One layer: keys and values of the positions the live rows hold."""
    return 2 * read_positions * s.Hkv * s.hd * itemsize


def scan_bytes(s, row_steps):
    """One layer: each advanced row's float32 state read and written once
    a step."""
    return 2 * row_steps * s.Hs * s.P * s.N * 4

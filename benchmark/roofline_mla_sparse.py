"""Operations and bytes that the mathematics of the latent-attention
decoder's serving step requires (full layers reading the positions a
learned indexer chooses, latent sliding-window layers, a dropless expert
layer that holds a share of the experts), computed from shapes and from
what the window counted.  The peaks and ``least_seconds`` are
``roofline.py``'s; the shapes are ``reference_mla_sparse.Sizes``'.

Counted: 2 per *active* matrix parameter for every processed token — each
layer's latent projections, ``W_UK`` and ``W_UV`` (the absorbed form
multiplies each once a token), gate and output matrix at its own head
count, the indexer's three matrices in a full layer, the dense layer's
feed-forward, and of a sparse layer the router and the shared expert; 2 per
parameter of an expert for every (token, expert) pair the held experts
computed (``serving_expert_pairs_total``); 2 per parameter of the head only
for the rows owed a token; in each layer the two absorbed products of a
query with every cached row it reads (``[heads, latent + rope] x [latent +
rope]`` and ``[heads] x [latent]`` a row: the rows the program counted,
``serving_latent_rows_total`` by kind); the indexer's ``2 * index_heads *
index_head_dim`` a scored position, every position of a token's context in
each full layer.  Not counted: norms, rotations, gates, softmaxes, the
ReLU-weighted sum over index heads, the top-k, the sort of the pairs.
"""
from __future__ import annotations

from benchmark.reference_mla_sparse import DENSE, FULL, SLIDING, SPARSE
from benchmark.roofline_moe_window import (expert_bytes,  # noqa: F401
                                           expert_ops, processed)


def token_params(s):
    """Matrix parameters every processed token multiplies, all layers."""
    return (sum(s.attention_params(k) for k in s.kinds)
            + s.count(FULL) * s.indexer_params()
            + s.count(DENSE) * s.dense_params()
            + s.count(SPARSE) * s.sparse_shared_params())


def row_ops(s, kind):
    """The two absorbed products of one query with one cached row."""
    m = s.mla[kind]
    return 2 * m["H"] * (2 * m["Rkv"] + m["dr"])


def attention_ops(s, full_rows, window_rows):
    """All layers: the rows the full layers' queries read (the indexer's
    choice) and the rows the sliding layers' queries read (the window)."""
    return (row_ops(s, FULL) * full_rows
            + row_ops(s, SLIDING) * window_rows)


def indexer_ops(s, context_positions):
    """Every position of a token's context scored by every index head, in
    each full layer; ``context_positions`` is summed over all layers."""
    return (2 * s.Hi * s.di * s.count(FULL) * context_positions
            // max(s.L, 1))


def latent_attention_bytes(s, tokens, full_rows, window_rows, itemsize=2):
    """The least the kernel's calls move: each row a query reads, once,
    at the model's width and type (``latent + rope`` values), and each
    query's absorbed query in and latent output out, every layer."""
    out = 0
    for kind, rows in ((FULL, full_rows), (SLIDING, window_rows)):
        m = s.mla[kind]
        width = m["Rkv"] + m["dr"]
        out += rows * width + tokens * s.count(kind) * m["H"] * (
            width + m["Rkv"])
    return itemsize * out


def step_flops(s, tokens, head_rows, context_positions, full_rows,
               window_rows, pairs):
    """The whole step's required operations for ``tokens`` processed, of
    which ``head_rows`` were owed a token."""
    return (2 * token_params(s) * tokens + expert_ops(s, pairs)
            + 2 * s.head_params() * head_rows
            + attention_ops(s, full_rows, window_rows)
            + indexer_ops(s, context_positions))


def counted(counts):
    """The window's counts hold what ``step_flops`` needs."""
    return all(k in counts for k in ("expert_pairs", "latent_rows_selected",
                                     "latent_rows_window"))

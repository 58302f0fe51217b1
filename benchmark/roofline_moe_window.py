"""Operations and bytes that the mathematics of the sparse-expert decoder's
serving step requires (sliding-window layers beside full-attention layers, a
dropless expert layer that holds a share of the experts), computed from
shapes and from what the window counted.  The peaks and ``least_seconds``
are ``roofline.py``'s; the shapes are ``reference_moe_window.Sizes``'.

Counted: 2 per *active* matrix parameter for every processed token — each
layer's attention projections, gate and output matrix at its own head
count, the dense layer's feed-forward, and of a sparse layer the router and
the shared expert; 2 per parameter of an expert for every (token, expert)
pair the held experts computed (the program's
``serving_expert_pairs_total``: the experts a token did not choose, or that
another chip holds, cost nothing); 2 per parameter of the head only for the
rows owed a token; in each layer the two products of a query with every
position it reads — all of its context in a full layer
(``serving_attention_positions_total{kind="context"}``), the window's share
in a sliding layer (``{kind="selected"}``).  Not counted: norms, rotations,
gates, the softmaxes, the sort of the pairs.
"""
from __future__ import annotations

from benchmark.reference_moe_window import DENSE, FULL, SLIDING, SPARSE


def processed(counts):
    """Tokens the window's steps processed: every prompt token, and one
    token for each decode row (a generated token that was not a prompt's
    first: that one came out of a prompt's last chunk)."""
    return counts["prefill_tokens"] + max(
        counts["generated_tokens"] - counts["first_tokens"], 0)


def token_params(s):
    """Matrix parameters every processed token multiplies, all layers."""
    return (sum(s.attention_params(k) for k in s.kinds)
            + s.count(DENSE) * s.dense_params()
            + s.count(SPARSE) * s.sparse_shared_params())


def attention_ops(s, context_positions, selected_positions):
    """All layers: scores and weighted values, 2 products of ``heads *
    head_dim`` multiply-adds per position read; a full layer reads a
    token's context, a sliding one its window's share."""
    return 4 * s.hd * (
        s.count(FULL) * s.heads_of(FULL) * context_positions
        + s.count(SLIDING) * s.heads_of(SLIDING) * selected_positions)


def attention_bytes(s, context_rows, window_rows, itemsize=2):
    """All layers: keys and values (the key/value heads held here) of the
    positions each live row must have read: its context in a full layer,
    what its window reaches in a sliding one."""
    return 2 * s.Hkv * s.hd * itemsize * (
        s.count(FULL) * context_rows + s.count(SLIDING) * window_rows)


def expert_ops(s, pairs):
    return 2 * s.expert_params() * pairs


def expert_bytes(s, pairs, experts_read, itemsize=2):
    """The matrices of every (layer, held expert) that got a pair, once;
    per pair its input row, the SwiGLU's width written and read, and its
    output row."""
    return itemsize * (experts_read * s.expert_params()
                       + pairs * (2 * s.D + 2 * s.Fe))


def step_flops(s, tokens, head_rows, context_positions, selected_positions,
               pairs):
    """The whole step's required operations for ``tokens`` processed, of
    which ``head_rows`` were owed a token."""
    return (2 * token_params(s) * tokens + expert_ops(s, pairs)
            + 2 * s.head_params() * head_rows
            + attention_ops(s, context_positions, selected_positions))

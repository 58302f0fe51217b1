"""The plain reference of the hybrid decoder: block-sparse grouped-query
attention layers beside lightning (decayed linear) attention layers, as a
straightforward float32 ``jax.numpy`` forward — no kernel, no cache, no
batching: one request at a time, its whole sequence, in blocks so that it
fits.  Nothing here imports the program; what is shared is the *format* of
the parameters (two stacks of layers, ``sparse`` and ``lightning``, each in
the order its layers have in ``mixer_types``), since the same random
weights have to mean the same function on both sides.

The equations (config keys in ``code``; "assumed" items are read from the
configuration file's ``assumed`` block, each by name):

- Stream: ``x = scale_emb * E[token]``; each layer
  ``x += c * Mixer(RMSNorm(x))`` then ``x += c * MLP(RMSNorm(x))`` with
  ``c = scale_depth / sqrt(mup_depth)`` (the published depth under the
  root, whatever depth is held); ``MLP(h) = W_down(silu(W_gate h) * (W_up
  h))``; logits ``= W_head RMSNorm(x) / (hidden_size / dim_model_base)``,
  head untied; no bias anywhere.
- ``minicpm4`` (sparse) layer: ``q`` as ``num_attention_heads`` heads, ``k,
  v`` as ``num_key_value_heads``; RMSNorm per head on ``q`` and ``k``; no
  positions; scale ``1/sqrt(head_dim)``.  A query at position ``t``
  (context ``n = t + 1``): if ``n <= dense_len``, causal softmax over all
  positions.  Else per group: compressed keys ``kbar_j = mean(k[stride*j :
  stride*j + kernel_size])`` over the spans that end at or before ``t``;
  ``p_h = softmax_j(q_h . kbar_j / sqrt(head_dim))``; ``s_j = sum_h p_h``
  over the group's heads; a block of ``block_size`` positions scores the
  max of ``s_j`` over the spans that overlap it; blocks ``< init_blocks``
  and the blocks that cover the last ``window_size`` positions are always
  taken, the best-scoring others (ties: the earlier block) until ``topk``
  are taken in all; each head takes a causal softmax over the positions of
  its group's blocks.  Output ``W_o(sigmoid(W_g h) * heads)``.
- ``lightning-attn`` layer: ``q, k, v`` as ``lightning_nh`` heads; RMSNorm
  per head on ``q``, ``k``; rotary positions over the whole head (halves
  rotated against each other, ``rope_theta``); per head a float32 state
  ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(hd)) S_t``,
  ``lambda_h = exp(-2^(-slope_power * h / H))`` for ``h = 1..H``; RMSNorm
  per head on ``o``; ``y = W_o(sigmoid(W_g h) * o)``.  Written here as the
  masked quadratic form in blocks with the state carried between them.

``numerics`` selects how the matrix products are computed, as in
``reference.py``: ``float32`` (TPU precision ``HIGHEST``), ``bfloat16`` and
``float8`` — the lower two are controls.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference as _dense
from benchmark.reference import (first_token, gap_below_best, nest,  # noqa
                                 seed_key)

_F32 = jnp.float32
SPARSE, LIGHTNING = "sparse", "lightning"
KINDS = {"minicpm4": SPARSE, "lightning-attn": LIGHTNING}


def product(numerics):
    """``einsum`` on two operands in the arithmetic asked for, as
    ``reference.product``.  The lower two round the operands (to bfloat16,
    or to float8 and then bfloat16) and multiply the rounded values in
    float32: every product of two bfloat16 values is exact in float32, so
    this is the one-pass arithmetic with float32 accumulation, written so
    that it also runs where a backend has no mixed-type product for these
    shapes (XLA:CPU, the tests)."""
    exact = _dense.product("float32")
    if numerics == "float32":
        return exact
    if numerics == "bfloat16":
        rnd = lambda a: a.astype(jnp.bfloat16)
    elif numerics == "float8":
        rnd = _dense._quant8
    else:
        raise ValueError(f"numerics {numerics!r} is none of "
                         f"{_dense.NUMERICS}")
    return lambda eq, a, b: exact(eq, rnd(a), rnd(b))


class Sizes:
    """The numbers of one configuration file, under the names used here;
    every item the published config does not give comes from ``assumed``,
    by name."""

    def __init__(self, config):
        a = config["assumed"]
        self.D = int(config["hidden_size"])
        self.F = int(config["intermediate_size"])
        self.V = int(config["vocab_size"])
        self.Vp = int(a.get("padded_vocab_size", self.V))
        self.H = int(config["num_attention_heads"])
        self.Hkv = int(config["num_key_value_heads"])
        self.hd = int(config["head_dim"])
        self.Hl = int(config["lightning_nh"])
        self.hdl = int(config["lightning_head_dim"])
        self.mixers = tuple(KINDS[m] for m in config["mixer_types"])
        if len(self.mixers) != int(config["num_hidden_layers"]):
            raise ValueError("mixer_types and num_hidden_layers disagree")
        self.max_len = int(config["max_position_embeddings"])
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.scale_emb = float(config["scale_emb"])
        self.residual = float(config["scale_depth"]) / math.sqrt(
            float(a["mup_depth"]))
        self.logit_divisor = self.D / float(config["dim_model_base"])
        self.kernel_size = int(a["kernel_size"])
        self.kernel_stride = int(a["kernel_stride"])
        self.block_size = int(a["block_size"])
        self.topk = int(a["topk"])
        self.init_blocks = int(a["init_blocks"])
        self.window_size = int(a["window_size"])
        self.dense_len = int(a["dense_len"])
        self.slope_power = float(a["lightning_slope_power"])
        self.std = float(a["initializer_range"])
        self.norm_jitter = float(a["norm_gain_jitter"])

    def count(self, kind):
        return sum(1 for m in self.mixers if m == kind)

    def matmul_params(self):
        """Parameters that take part in a product for every token."""
        D, F = self.D, self.F
        sparse = D * self.hd * (2 * self.H + 2 * self.Hkv) \
            + self.H * self.hd * D + 3 * D * F
        light = 5 * D * self.Hl * self.hdl + 3 * D * F
        return (self.count(SPARSE) * sparse + self.count(LIGHTNING) * light
                + D * self.Vp)


def leaf_table(s):
    """name -> (shape, init std, or ``"gain"`` for a norm's gain)."""
    D, F = s.D, s.F

    def stack(n, hq, hkv, hd, out_norm):
        t = {"ln1": ((n, D), "gain"), "ln2": ((n, D), "gain"),
             "q_w": ((n, D, hq * hd), s.std),
             "k_w": ((n, D, hkv * hd), s.std),
             "v_w": ((n, D, hkv * hd), s.std),
             "gate_w": ((n, D, hq * hd), s.std),
             "o_w": ((n, hq * hd, D), s.std),
             "q_norm": ((n, hd), "gain"), "k_norm": ((n, hd), "gain"),
             "mlp_gate_w": ((n, D, F), s.std),
             "mlp_up_w": ((n, D, F), s.std),
             "mlp_down_w": ((n, F, D), s.std)}
        if out_norm:
            t["o_norm"] = ((n, hd), "gain")
        return t

    table = {"wte": ((s.Vp, D), s.std)}
    for name, leaf in stack(s.count(SPARSE), s.H, s.Hkv, s.hd,
                            False).items():
        table[f"{SPARSE}/{name}"] = leaf
    for name, leaf in stack(s.count(LIGHTNING), s.Hl, s.Hl, s.hdl,
                            True).items():
        table[f"{LIGHTNING}/{name}"] = leaf
    table["norm_f"] = ((D,), "gain")
    table["lm_head"] = ((D, s.Vp), s.std)
    return table


def make_weights(config, key, dtype):
    """The parameters, from ``seed_key(seed)``: normal matrices and
    embedding, norm gains drawn around 1 (so that a gain left out shows).
    Traceable: jit it (one program, made on the device)."""
    s = Sizes(config)
    table = leaf_table(s)
    flat = {}
    for k, (name, (shape, init)) in zip(jax.random.split(key, len(table)),
                                        table.items()):
        draw = jax.random.normal(k, shape, _F32)
        flat[name] = ((1.0 + s.norm_jitter * draw) if init == "gain"
                      else draw * init).astype(dtype)
    return nest(flat)


def weights(config, seed, dtype):
    return jax.jit(lambda key: make_weights(config, key, dtype))(
        seed_key(seed))


# ------------------------------------------------------------- the pieces


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, pos, theta):
    """``x [S, H, hd]`` rotated at ``pos [S]``: halves against each other."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=_F32) / hd)
    ang = pos.astype(_F32)[:, None, None] * inv
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def overlapping_spans(s, n_blocks):
    """``[n_blocks, m]`` int: for each block the compressed-key spans that
    overlap it, -1 padded — from the intervals themselves."""
    rows = []
    for b in range(n_blocks):
        lo, hi = b * s.block_size, (b + 1) * s.block_size
        rows.append([j for j in range(-(-hi // s.kernel_stride))
                     if j * s.kernel_stride < hi
                     and j * s.kernel_stride + s.kernel_size > lo])
    m = max(len(r) for r in rows)
    return np.asarray([r + [-1] * (m - len(r)) for r in rows], np.int32)


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i].astype(_F32), tree)


class Model:
    """The jitted pieces and the loop over layers and blocks."""

    def __init__(self, config, numerics="float32", block=None):
        s = self.s = Sizes(config)
        mm = product(numerics)
        self.block = block or min(1024, s.max_len)
        self.sub = max(self.block // 4, 1)
        self.padded = -(-s.max_len // self.block) * self.block
        n_blocks = self.padded // s.block_size
        n_spans = self.padded // s.kernel_stride
        spans_of_block = jnp.asarray(overlapping_spans(s, n_blocks))

        @jax.jit
        def embed(wte, tok):
            return jnp.take(wte, tok, axis=0).astype(_F32) * s.scale_emb

        @jax.jit
        def sparse_in(stack, i, x):
            p = _layer(stack, i)
            h = rms_norm(x, p["ln1"], s.eps)
            q = mm("sd,de->se", h, p["q_w"]).reshape(-1, s.H, s.hd)
            k = mm("sd,de->se", h, p["k_w"]).reshape(-1, s.Hkv, s.hd)
            v = mm("sd,de->se", h, p["v_w"]).reshape(-1, s.Hkv, s.hd)
            gate = jax.nn.sigmoid(mm("sd,de->se", h, p["gate_w"]))
            return (rms_norm(q, p["q_norm"], s.eps),
                    rms_norm(k, p["k_norm"], s.eps), v, gate)

        @jax.jit
        def compress(k):
            """``kbar [spans, Hkv, hd]`` of the whole padded sequence."""
            idx = (jnp.arange(n_spans)[:, None] * s.kernel_stride
                   + jnp.arange(s.kernel_size)[None, :])
            return jnp.mean(k[jnp.minimum(idx, k.shape[0] - 1)], axis=1)

        def group(q):
            return q.reshape(q.shape[0], s.Hkv, s.H // s.Hkv, s.hd)

        @jax.jit
        def dense_attention(q, k, v, start):
            """Causal softmax of ``q [n, H, hd]`` at positions ``start +
            arange(n)`` over the keys given (the sequence's first ones)."""
            sc = mm("qhgd,thd->hgqt", group(q), k) / math.sqrt(s.hd)
            t = jnp.arange(k.shape[0])
            ok = t[None, :] <= (start + jnp.arange(q.shape[0]))[:, None]
            p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
            return mm("hgqt,thd->qhgd", p, v).reshape(q.shape)

        @jax.jit
        def select(q, kbar, start):
            """``[n, Hkv, topk]``: the blocks each query and group takes."""
            pos = start + jnp.arange(q.shape[0])
            sc = mm("qhgd,jhd->qhgj", group(q), kbar) / math.sqrt(s.hd)
            span_end = (jnp.arange(n_spans) * s.kernel_stride
                        + s.kernel_size - 1)
            whole = span_end[None, :] <= pos[:, None]             # [n, J]
            p = jax.nn.softmax(
                jnp.where(whole[:, None, None], sc, -jnp.inf), axis=-1)
            p = jnp.where(whole[:, None, None], p, 0.0).sum(2)  # [n,Hkv,J]
            of_block = jnp.where(spans_of_block >= 0,
                                 p[..., jnp.maximum(spans_of_block, 0)],
                                 -jnp.inf)                # [n, Hkv, W, m]
            score = jnp.max(of_block, axis=-1)
            w = jnp.arange(n_blocks)
            own = (pos // s.block_size)[:, None, None]
            first_in_window = (jnp.maximum(pos + 1 - s.window_size, 0)
                               // s.block_size)[:, None, None]
            forced = (w < s.init_blocks) | (w >= first_in_window)
            key = jnp.where(w <= own, jnp.where(forced, jnp.inf, score),
                            -jnp.inf)
            order = jnp.argsort(-key, axis=-1, stable=True)[..., : s.topk]
            taken = jnp.take_along_axis(key, order, axis=-1) > -jnp.inf
            return jnp.where(taken, order, -1)

        @jax.jit
        def selected_attention(q, k, v, blocks, start):
            """Causal softmax of each query over the positions of its
            group's blocks, gathered."""
            n = q.shape[0]
            pos = start + jnp.arange(n)
            at = (jnp.maximum(blocks, 0)[..., None] * s.block_size
                  + jnp.arange(s.block_size))          # [n, Hkv, K, bs]
            ok = (blocks[..., None] >= 0) & (at <= pos[:, None, None, None])
            at = jnp.minimum(at, k.shape[0] - 1).reshape(n, s.Hkv, -1)
            ok = ok.reshape(n, s.Hkv, -1)
            g = jnp.arange(s.Hkv)[None, :, None]
            ks, vs = k[at, g], v[at, g]                # [n, Hkv, K*bs, hd]
            sc = mm("qhgd,qhtd->qhgt", group(q), ks) / math.sqrt(s.hd)
            p = jax.nn.softmax(jnp.where(ok[:, :, None], sc, -jnp.inf),
                               axis=-1)
            return mm("qhgt,qhtd->qhgd", p, vs).reshape(q.shape)

        @jax.jit
        def mixer_out(stack, i, x, heads, gate):
            p = _layer(stack, i)
            x = x + s.residual * mm("se,ed->sd",
                                    gate * heads.reshape(gate.shape),
                                    p["o_w"])
            h = rms_norm(x, p["ln2"], s.eps)
            h = jax.nn.silu(mm("sd,df->sf", h, p["mlp_gate_w"])) \
                * mm("sd,df->sf", h, p["mlp_up_w"])
            return x + s.residual * mm("sf,fd->sd", h, p["mlp_down_w"])

        slopes = jnp.exp2(-s.slope_power
                          * jnp.arange(1, s.Hl + 1, dtype=_F32) / s.Hl)

        @jax.jit
        def lightning(stack, i, x, state, start):
            """One block of one lightning layer: the masked quadratic form
            inside the block, the state carried in and out."""
            p = _layer(stack, i)
            n = x.shape[0]
            pos = start + jnp.arange(n)
            h = rms_norm(x, p["ln1"], s.eps)
            proj = lambda name: mm("sd,de->se", h, p[name]).reshape(
                n, s.Hl, s.hdl)
            q = rope(rms_norm(proj("q_w"), p["q_norm"], s.eps), pos,
                     s.theta) / math.sqrt(s.hdl)
            k = rope(rms_norm(proj("k_w"), p["k_norm"], s.eps), pos, s.theta)
            v = proj("v_w")
            gate = jax.nn.sigmoid(mm("sd,de->se", h, p["gate_w"]))
            i_, j_ = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
            decay = jnp.where(
                j_ <= i_, jnp.exp(-slopes[:, None, None]
                                  * jnp.maximum(i_ - j_, 0)), 0.0)
            a = mm("ihd,jhd->hij", q, k) * decay
            o = mm("hij,jhd->ihd", a, v) + jnp.exp(
                -slopes[None, :, None] * (i_[:, :, None] + 1.0)) \
                * mm("ihd,hde->ihe", q, state)
            w = jnp.exp(-slopes[None, :] * (n - 1.0 - i_))      # [n, H]
            state = jnp.exp(-slopes * n)[:, None, None] * state \
                + mm("jhd,jhe->hde", k * w[:, :, None], v)
            o = rms_norm(o, p["o_norm"], s.eps)
            return mixer_out(stack, i, x, o, gate), state

        @jax.jit
        def logits(norm_f, lm_head, x):
            h = rms_norm(x, norm_f.astype(_F32), s.eps)
            return mm("sd,dv->sv", h, lm_head.astype(_F32)) \
                / s.logit_divisor

        self.embed, self.sparse_in, self.compress = embed, sparse_in, \
            compress
        self.dense_attention, self.select = dense_attention, select
        self.selected_attention, self.mixer_out = selected_attention, \
            mixer_out
        self.lightning, self.logits = lightning, logits

    # ---------------------------------------------------------- the loop

    def _sparse_layer(self, stack, i, xs, length, needed_from):
        """One sparse layer over the sequence's blocks ``xs``; blocks that
        end at or before ``needed_from`` are not computed (``None``)."""
        s, B, sub = self.s, self.block, self.sub
        i = np.int32(i)
        qs, ks, vs, gates = zip(*(self.sparse_in(stack, i, x) for x in xs))
        pad = self.padded - B * len(xs)
        full = lambda parts: jnp.pad(jnp.concatenate(parts),
                                     ((0, pad), (0, 0), (0, 0)))
        k, v = full(ks), full(vs)
        kbar = self.compress(k)
        out = []
        for b, x in enumerate(xs):
            if (b + 1) * B <= needed_from or b * B >= length:
                out.append(None)
                continue
            heads = []
            for c in range(0, B, sub):
                start = b * B + c
                q = qs[b][c:c + sub]
                dense = sparse = None
                if start + 1 <= s.dense_len:        # some context <= it
                    upto = min(-(-(start + sub) // B) * B, self.padded)
                    dense = self.dense_attention(q, k[:upto], v[:upto],
                                                 np.int32(start))
                if start + sub > s.dense_len:       # some context past it
                    sparse = self.selected_attention(
                        q, k, v, self.select(q, kbar, np.int32(start)),
                        np.int32(start))
                if dense is None or sparse is None:
                    heads.append(dense if sparse is None else sparse)
                else:
                    n = start + 1 + np.arange(sub)
                    heads.append(jnp.where(
                        jnp.asarray(n <= s.dense_len)[:, None, None],
                        dense, sparse))
            out.append(self.mixer_out(stack, i, x, jnp.concatenate(heads),
                                      gates[b]))
        return out

    def forward_logits(self, params, tokens, n_prompt):
        """Float32 logits ``[len(tokens) - n_prompt + 1, padded
        vocabulary]`` of one request at the positions ``n_prompt - 1 ..
        len(tokens) - 1``: the positions a server decoded from."""
        s, B = self.s, self.block
        length = len(tokens)
        if length > self.padded:
            raise ValueError(f"{length} tokens, configured for {s.max_len}")
        n = -(-length // B)
        row = np.zeros((n * B,), np.int32)
        row[:length] = tokens
        xs = [self.embed(params["wte"], row[b * B:(b + 1) * B])
              for b in range(n)]
        seen = {SPARSE: 0, LIGHTNING: 0}
        first = n_prompt - 1
        for depth, kind in enumerate(s.mixers):
            i = seen[kind]
            seen[kind] += 1
            last = depth == len(s.mixers) - 1
            if kind == SPARSE:
                xs = self._sparse_layer(params[SPARSE], i, xs, length,
                                        first if last else 0)
            else:
                state = jnp.zeros((s.Hl, s.hdl, s.hdl), _F32)
                out = []
                for b, x in enumerate(xs):
                    parts = []
                    for c in range(0, B, self.sub):
                        y, state = self.lightning(
                            params[LIGHTNING], np.int32(i), x[c:c + self.sub],
                            state, np.int32(b * B + c))
                        parts.append(y)
                    out.append(jnp.concatenate(parts))
                xs = out
        tail = jnp.concatenate([x for x in xs if x is not None])
        offset = next(b for b, x in enumerate(xs) if x is not None) * B
        return self.logits(params["norm_f"], params["lm_head"],
                           tail[first - offset: length - offset])

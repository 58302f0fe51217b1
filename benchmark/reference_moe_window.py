"""The plain reference of the sparse-expert decoder with sliding-window
layers beside full-attention layers (Laguna): a straightforward float32
``jax.numpy`` forward — no kernel, no cache, no batching, no chunking: one
request at a time, its whole sequence.  Nothing here imports the program;
what is shared is the *format* of the parameters (one stack per layer kind),
since the same random weights have to mean the same function on both sides.

From the accepted files it imports ``reference.py``'s ``first_token``,
``gap_below_best``, ``nest`` and ``seed_key``, ``reference_hybrid.py``'s
``product`` and ``rms_norm``, and ``reference_ssm.py``'s blocked draw
(``_normal``).

The equations (config keys in ``code``; every item the published
``config.json`` does not itself fix is in the configuration file's
``assumed`` block and read from there by name):

- Stream: ``x = E[token]``; each layer ``h = x + Attn(RMSNorm(x))``, ``y =
  h + FFN(RMSNorm(h))``; logits ``= W_head RMSNorm(y)``, head untied, no
  bias, ``rms_norm_eps`` 1e-6.
- Attention of layer ``l``, of kind ``layer_types[l]``: ``q = W_q u`` as
  ``num_attention_heads_per_layer[l]`` heads, ``k = W_k u`` and ``v = W_v
  u`` as ``num_key_value_heads``, ``head_dim`` 128.  Rotary positions by
  the kind's ``rope_parameters``: the first ``partial_rotary_factor *
  head_dim`` dimensions rotated (halves against each other), full layers
  with YaRN frequencies and ``attention_factor`` on cos and sin, sliding
  layers plain.  Scores ``q k / sqrt(head_dim)``, causal; a sliding layer
  also ``j > i - sliding_window``.  Softmax in float32.  ``g = sigmoid(W_g
  u)`` one per head, the head's output times it, then ``W_o``.
- ``FFN`` of a ``dense`` layer: ``W_down(silu(W_gate u) * (W_up u))`` at
  ``intermediate_size``.  Of a ``sparse`` layer: ``p = softmax(W_r u)`` over
  all ``router_outputs`` experts in float32, the ``num_experts_per_tok``
  largest, ``w = p_top / sum(p_top) * moe_routed_scaling_factor``;
  ``S(u) + sum_e w_e E_e(u)`` over the chosen experts **that are held**
  (``experts_held``: the share this chip has; the rest of the sum is on
  other chips and is left out here as in the program), ``S`` and every
  ``E_e`` a SwiGLU of width 1024.  No token is dropped.

How the routed sum is computed: the choices come to the host, which lists
for every held expert the tokens that chose it (every one of them, padded
with a zero row to a common length); each expert's rows are gathered,
multiplied and added back, one expert after another, its matrices upcast
one at a time.  Attention is computed a block of queries at a time, so the
scores of a 17,920-token request are never held whole.

``numerics`` selects how the matrix products are computed, as in
``reference.py``: ``float32`` (TPU precision ``HIGHEST``), ``bfloat16`` and
``float8`` — the lower two are controls.  The router's product is float32
in all three: it is float32 in the configuration.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (first_token, gap_below_best, nest,  # noqa
                                 seed_key)
from benchmark.reference_hybrid import product, rms_norm
from benchmark.reference_ssm import _normal

_F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# positions the head is applied to at once, and queries a block of scores
_HEAD_ROWS, _QUERY_ROWS = 256, 512


class Sizes:
    """The numbers of one configuration file, under the names used here;
    every item the published config does not give comes from ``assumed``,
    by name."""

    def __init__(self, config):
        a = config["assumed"]
        self.D = int(config["hidden_size"])
        self.F = int(config["intermediate_size"])
        self.Fe = int(config["moe_intermediate_size"])
        self.Fs = int(config["shared_expert_intermediate_size"])
        self.V = self.Vp = int(config["vocab_size"])
        self.L = int(config["num_hidden_layers"])
        self.kinds = tuple(config["layer_types"])
        self.mlps = tuple(config["mlp_layer_types"])
        self.heads = tuple(int(h) for h in
                           config["num_attention_heads_per_layer"])
        if not len(self.kinds) == len(self.mlps) == len(self.heads) \
                == self.L:
            raise ValueError("layer_types, mlp_layer_types and "
                             "num_attention_heads_per_layer name "
                             "num_hidden_layers layers")
        if [i for i, m in enumerate(self.mlps) if m == DENSE] \
                != list(config["mlp_only_layers"]):
            raise ValueError("mlp_only_layers and mlp_layer_types disagree")
        self.Hkv = int(config["num_key_value_heads"])
        self.hd = int(config["head_dim"])
        self.window = int(config["sliding_window"])
        self.E = int(config["router_outputs"])
        self.first, self.held = (int(v) for v in config["experts_held"])
        if self.held != int(config["num_experts"]):
            raise ValueError("num_experts is the experts held here")
        self.top_k = int(config["num_experts_per_tok"])
        self.routed_scale = float(config["moe_routed_scaling_factor"])
        self.norm_topk = bool(config["norm_topk_prob"])
        if config["moe_apply_router_weight_on_input"] \
                or config["moe_router_logit_softcapping"]:
            raise ValueError("router weights on the input, or softcapping: "
                             "not written here")
        self.max_len = int(config["max_position_embeddings"])
        self.eps = float(config["rms_norm_eps"])
        self.rope = {k: dict(v) for k, v in
                     config["rope_parameters"].items()}
        for name, want in (("router_score", "softmax"),
                           ("attention_gate", "sigmoid"),
                           ("shared_expert_gate", "none"),
                           ("qk_norm", "none"), ("hidden_act", "silu")):
            if a[name] != want:
                raise ValueError(f"assumed.{name} = {a[name]!r}: only "
                                 f"{want!r} is written here")
        self.std = {k: float(v) for k, v in a["init_std"].items()}
        self.norm_jitter = float(a["norm_gain_jitter"])

    def count(self, kind):
        return sum(k == kind for k in self.kinds + self.mlps)

    def heads_of(self, kind):
        return next(h for h, k in zip(self.heads, self.kinds) if k == kind)

    def attention_params(self, kind):
        """Matrix parameters of one attention layer of ``kind`` held here:
        q, k, v, the gate and the output projection."""
        H = self.heads_of(kind)
        return self.D * (self.hd * (H + 2 * self.Hkv) + H) \
            + H * self.hd * self.D

    def expert_params(self):
        return 3 * self.D * self.Fe

    def sparse_shared_params(self):
        """Of one sparse layer, what every token multiplies: the router
        and the shared expert."""
        return self.D * self.E + 3 * self.D * self.Fs

    def dense_params(self):
        return 3 * self.D * self.F

    def head_params(self):
        return self.D * self.Vp

    def n_params(self):
        attn = sum(self.attention_params(k) for k in self.kinds)
        sparse = self.count(SPARSE) * (self.sparse_shared_params()
                                       + self.held * self.expert_params())
        return (attn + sparse + self.count(DENSE) * self.dense_params()
                + 2 * self.L * self.D + self.D + 2 * self.head_params())


def leaf_table(s):
    """name -> (shape, how it is drawn): a float is the deviation of a
    normal matrix (``assumed.init_std``), ``"gain"`` a norm's gain."""
    D, hd, Hkv, std = s.D, s.hd, s.Hkv, s.std
    table = {"wte": ((s.Vp, D), std["wte"])}
    for kind, short in ((FULL, "full"), (SLIDING, "sliding")):
        n, H = s.count(kind), s.heads_of(kind)
        table.update({
            f"{kind}/ln1": ((n, D), "gain"),
            f"{kind}/q_w": ((n, D, H * hd), std[short + "_q_w"]),
            f"{kind}/k_w": ((n, D, Hkv * hd), std[short + "_k_w"]),
            f"{kind}/v_w": ((n, D, Hkv * hd), std[short + "_v_w"]),
            f"{kind}/g_w": ((n, D, H), std[short + "_g_w"]),
            f"{kind}/o_w": ((n, H * hd, D), std[short + "_o_w"])})
    n = s.count(DENSE)
    table.update({
        "dense/ln2": ((n, D), "gain"),
        "dense/gate_w": ((n, D, s.F), std["dense_gate_w"]),
        "dense/up_w": ((n, D, s.F), std["dense_up_w"]),
        "dense/down_w": ((n, s.F, D), std["dense_down_w"])})
    n = s.count(SPARSE)
    table.update({
        "sparse/ln2": ((n, D), "gain"),
        "sparse/router_w": ((n, D, s.E), std["router_w"]),
        "sparse/shared_gate_w": ((n, D, s.Fs), std["shared_gate_w"]),
        "sparse/shared_up_w": ((n, D, s.Fs), std["shared_up_w"]),
        "sparse/shared_down_w": ((n, s.Fs, D), std["shared_down_w"]),
        "sparse/gate_w": ((n, s.held, D, s.Fe), std["expert_gate_w"]),
        "sparse/up_w": ((n, s.held, D, s.Fe), std["expert_up_w"]),
        "sparse/down_w": ((n, s.held, s.Fe, D), std["expert_down_w"])})
    table["norm_f"] = ((D,), "gain")
    table["lm_head"] = ((D, s.Vp), std["lm_head"])
    return table


def make_weights(config, key, dtype):
    """The parameters, from ``seed_key(seed)``.  Traceable: jit it (one
    program, made on the device); a leaf is drawn in blocks of its leading
    axes so that no float32 array of the experts exists."""
    s = Sizes(config)
    table = leaf_table(s)
    flat = {}
    for k, (name, (shape, init)) in zip(jax.random.split(key, len(table)),
                                        table.items()):
        if init == "gain":
            flat[name] = (1.0 + s.norm_jitter * jax.random.normal(
                k, shape, _F32)).astype(dtype)
        elif len(shape) == 4:       # [layers, experts, ...]: more blocks
            flat[name] = _normal(k, (shape[0] * shape[1],) + shape[2:],
                                 init, dtype).reshape(shape)
        else:
            flat[name] = _normal(k, shape, init, dtype)
    return nest(flat)


def weights(config, seed, dtype):
    return jax.jit(lambda key: make_weights(config, key, dtype))(
        seed_key(seed))


# ------------------------------------------------------------------ rotary


def inverse_frequencies(rope, head_dim):
    """``(inverse frequencies [rot / 2], factor on cos and sin, rot)`` of
    one entry of ``rope_parameters``, in float64 numpy."""
    rot = int(head_dim * rope["partial_rotary_factor"])
    base = float(rope["rope_theta"])
    plain = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope["rope_type"] == "default":
        return plain, 1.0, rot
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    # YaRN (arXiv:2309.00071): dimension d turns original_max / (2 pi
    # base^(2d/rot)) times in the trained context; those with more turns
    # than beta_fast keep their frequency, those with fewer than beta_slow
    # are divided by factor, and a linear ramp over whole dimensions lies
    # between
    orig = float(rope["original_max_position_embeddings"])

    def dimension(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dimension(rope["beta_fast"])), 0)
    high = min(math.ceil(dimension(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = plain / float(rope["factor"]) * ramp + plain * (1.0 - ramp)
    return inv, float(rope["attention_factor"]), rot


def rotate(x, pos, table):
    """``x [S, H, hd]`` at positions ``pos [S]``."""
    inv, factor, rot = table
    ang = pos.astype(_F32)[:, None, None] * jnp.asarray(inv, _F32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


# ------------------------------------------------------------- the model


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


class Model:
    """The jitted pieces and the loop over layers."""

    def __init__(self, config, numerics="float32", block=None):
        s = self.s = Sizes(config)
        mm = product(numerics)
        exact = product("float32")
        # a sequence is padded to a block times a power of two: five
        # shapes up to 17,920 positions, where multiples of the block were
        # eighteen, each with seven float32 programs to compile (176
        # compiles and 338 s of a run's reference: my chip run, PR 35)
        self.block = block or min(1024, s.max_len)
        tables = {FULL: inverse_frequencies(s.rope[FULL], s.hd),
                  SLIDING: inverse_frequencies(s.rope[SLIDING], s.hd)}
        self.tables = tables

        @jax.jit
        def embed(wte, tok):
            return jnp.take(wte, tok, axis=0).astype(_F32)

        def attention(kind, p, x):
            """``x + Attn(RMSNorm(x))`` for a layer of ``kind``; ``p`` its
            parameters."""
            n, H = x.shape[0], s.heads_of(kind)
            G = H // s.Hkv
            p = jax.tree_util.tree_map(lambda a: a.astype(_F32), p)
            u = rms_norm(x, p["ln1"], s.eps)
            pos = jnp.arange(n)
            q = mm("sd,de->se", u, p["q_w"]).reshape(n, H, s.hd)
            k = mm("sd,de->se", u, p["k_w"]).reshape(n, s.Hkv, s.hd)
            v = mm("sd,de->se", u, p["v_w"]).reshape(n, s.Hkv, s.hd)
            q, k = rotate(q, pos, tables[kind]), rotate(k, pos, tables[kind])
            rows = min(_QUERY_ROWS, n)
            if n % rows:
                raise ValueError(f"{n} positions are not whole blocks of "
                                 f"{rows} queries")

            def queries(start):
                qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
                at = start + jnp.arange(rows)
                sc = mm("qhgd,thd->hgqt", qb.reshape(rows, s.Hkv, G, s.hd),
                        k) / math.sqrt(s.hd)
                ok = pos[None, :] <= at[:, None]
                if kind == SLIDING:
                    ok = ok & (pos[None, :] > at[:, None] - s.window)
                pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
                return mm("hgqt,thd->qhgd", pr, v).reshape(rows, H, s.hd)

            heads = jax.lax.map(queries, jnp.arange(0, n, rows)).reshape(
                n, H, s.hd)
            gate = jax.nn.sigmoid(mm("sd,dh->sh", u, p["g_w"]))
            heads = (heads * gate[..., None]).reshape(n, H * s.hd)
            return x + mm("se,ed->sd", heads, p["o_w"])

        def swiglu(u, gate_w, up_w, down_w):
            up = lambda w: mm("sd,df->sf", u, w.astype(_F32))
            return mm("sf,fd->sd", jax.nn.silu(up(gate_w)) * up(up_w),
                      down_w.astype(_F32))

        @jax.jit
        def dense(p, h):
            return h + swiglu(rms_norm(h, p["ln2"].astype(_F32), s.eps),
                              p["gate_w"], p["up_w"], p["down_w"])

        @jax.jit
        def route(p, h):
            """``(u, shared expert's output, weights [S, k], experts [S,
            k])`` of a sparse layer."""
            u = rms_norm(h, p["ln2"].astype(_F32), s.eps)
            scores = jax.nn.softmax(
                exact("sd,de->se", u, p["router_w"]), axis=-1)
            top, idx = jax.lax.top_k(scores, s.top_k)
            if s.norm_topk:
                top = top / jnp.sum(top, axis=-1, keepdims=True)
            shared = swiglu(u, p["shared_gate_w"], p["shared_up_w"],
                            p["shared_down_w"])
            return u, shared, top * s.routed_scale, idx

        @jax.jit
        def routed(p, u, rows, w):
            """``sum_e w_e E_e(u)`` over the held experts: ``rows [held,
            C]`` the tokens that chose each (``S``, a zero row, as
            padding), ``w [held, C]`` their weights."""
            u0 = jnp.concatenate([u, jnp.zeros((1, s.D), _F32)])

            def one(acc, at):
                gate_w, up_w, down_w, r, we = at
                y = swiglu(u0[r], gate_w, up_w, down_w) * we[:, None]
                return acc.at[r].add(y), None

            acc, _ = jax.lax.scan(
                one, jnp.zeros_like(u0),
                (p["gate_w"], p["up_w"], p["down_w"], rows, w))
            return acc[:-1]

        @jax.jit
        def head(norm_f, lm_head, h, r):
            """Logits of ``_HEAD_ROWS`` positions from ``r``."""
            h = jax.lax.dynamic_slice_in_dim(h, r, _HEAD_ROWS, axis=0)
            return mm("sd,dv->sv", rms_norm(h, norm_f.astype(_F32), s.eps),
                      lm_head.astype(_F32))

        self.embed, self.dense, self.route = embed, dense, route
        self.routed, self.head = routed, head
        self.attention = {k: jax.jit(lambda p, x, k=k: attention(k, p, x))
                          for k in (FULL, SLIDING)}

    def held_rows(self, weights, experts, n_real):
        """On the host: for every held expert the tokens (of the first
        ``n_real``) that chose it and their weights, padded to one length
        (a power of two: few shapes) with the zero row ``S`` and weight
        0."""
        s = self.s
        weights, experts = np.asarray(weights), np.asarray(experts)
        S = experts.shape[0]
        local = experts[:n_real] - s.first
        lists = [np.nonzero(local == e) for e in range(s.held)]
        longest = max(1, max(len(t) for t, _ in lists))
        C = 1 << (longest - 1).bit_length()
        rows = np.full((s.held, C), S, np.int32)
        w = np.zeros((s.held, C), np.float32)
        for e, (tok, choice) in enumerate(lists):
            rows[e, : len(tok)] = tok
            w[e, : len(tok)] = weights[tok, choice]
        return rows, w

    def layers(self, params, h, n_real):
        s = self.s
        seen = dict.fromkeys((FULL, SLIDING, DENSE, SPARSE), 0)
        for kind, mlp in zip(s.kinds, s.mlps):
            i, j = seen[kind], seen[mlp]
            seen[kind] += 1
            seen[mlp] += 1
            h = self.attention[kind](_at(params[kind], i), h)
            if mlp == DENSE:
                h = self.dense(_at(params[DENSE], j), h)
                continue
            p = _at(params[SPARSE], j)
            u, shared, w, idx = self.route(p, h)
            rows, w = self.held_rows(w, idx, n_real)
            h = h + shared + self.routed(p, u, rows, w)
        return h

    def forward_logits(self, params, tokens, n_prompt):
        """Float32 logits ``[len(tokens) - n_prompt + 1, vocabulary]`` of
        one request at the positions ``n_prompt - 1 .. len(tokens) - 1``:
        the positions a server decoded from."""
        s, B = self.s, self.block
        length = len(tokens)
        longest = -(-s.max_len // B) * B
        if length > longest:
            raise ValueError(f"{length} tokens, configured for {s.max_len}")
        blocks = -(-length // B)
        row = np.zeros((min(B << (blocks - 1).bit_length(), longest),),
                       np.int32)
        row[:length] = tokens
        h = self.layers(params, self.embed(params["wte"], row), length)
        first = n_prompt - 1
        # room for the last block of rows: a slice is never clamped
        h = jnp.pad(h, ((0, _HEAD_ROWS), (0, 0)))
        rows = [self.head(params["norm_f"], params["lm_head"], h,
                          np.int32(r))
                for r in range(first, length, _HEAD_ROWS)]
        return jnp.concatenate(rows)[: length - first]

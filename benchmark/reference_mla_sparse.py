"""The plain reference of the latent-attention decoder with a learned
token-level indexer in its full layers, latent sliding-window layers with
their own ranks, and sigmoid-routed experts with a shared one (dots3-note):
a straightforward float32 ``jax.numpy`` forward — no kernel, no cache, no
batching, no chunking: one request at a time, its whole sequence.  Nothing
here imports the program; what is shared is the *format* of the parameters
(one stack per layer kind), since the same random weights have to mean the
same function on both sides.

From the accepted files it imports ``reference.py``'s ``first_token``,
``gap_below_best``, ``nest`` and ``seed_key``, ``reference_hybrid.py``'s
``product`` and ``rms_norm``, ``reference_ssm.py``'s blocked draw
(``_normal``) and ``reference_moe_window.py``'s ``_at``.

The equations (config keys in ``code``; every item the published
``config.json`` does not itself fix is in the configuration file's
``assumed`` block, with its source, and read from there by name):

- Stream: ``x = E[token]``; each layer ``h = x + Attn(RMSNorm(x))``, ``y =
  h + FFN(RMSNorm(h))``; logits ``= W_head RMSNorm(y)``, head untied,
  ``rms_norm_eps`` 1e-5.
- Attention, **written in its expanded form**: ``c_q = RMSNorm(u W_qa)
  s_q``; ``q = c_q W_qb`` as heads of ``[q_nope | q_rope]``; ``[c_kv |
  k_rope] = u W_kva``, ``c_kv = RMSNorm(c_kv) s_kv``; per head ``k =
  [c_kv W_UK,h | k_rope]`` and ``v = c_kv W_UV,h``; rotary positions on
  ``q_rope`` and ``k_rope``; ``softmax(q k / sqrt(nope + rope)) v`` in
  float32 over the positions the layer reads; the head's output times
  ``sigmoid(u W_g)_h``; ``W_o``.  ``s_q = sqrt(hidden / q_lora_rank)``,
  ``s_kv = sqrt(hidden / kv_lora_rank)`` (``apply_mla_qkv_lora_rescale``).
  A ``sliding_attention`` layer reads ``i - sliding_window_size < j <= i``,
  with its own ``swa_`` ranks, heads, head sizes and ``swa_rope_theta``.
  A ``full_attention`` layer reads the positions its **indexer** chooses:
  ``q^I = c_q W^I_q`` (``index_n_heads`` of ``index_head_dim``), ``k^I =
  LayerNorm(u W^I_k)``, rotary positions on the first ``qk_rope_head_dim``
  dimensions of both, ``w = u W^I_w / sqrt(index_n_heads)``, ``I[t, s] =
  sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]) / sqrt(index_head_dim)`` for ``s <=
  t``, computed densely; the query reads the top ``min(index_topk, t + 1)``
  of ``I[t, .]`` (``lax.top_k``: exact), a mask on the causal scores.
- ``FFN`` of layer 0 (``first_k_dense_replace``): ``W_down(silu(W_gate u)
  * (W_up u))`` at ``intermediate_size``.  Of the others: ``p =
  sigmoid(W_r u)`` over all ``router_outputs`` experts in float32; the
  ``num_experts_per_tok`` largest of ``p + b`` (``b`` the selection bias);
  ``w = p_sel / (sum p_sel + 1e-6)`` times ``routed_scaling_factor``;
  ``S(u) + sum_e w_e E_e(u)`` over the chosen experts **that are held**
  (``experts_held``: this chip's share; the rest of the sum lives on other
  chips and is left out here as in the program), ``S`` the shared expert,
  every one a SwiGLU of ``moe_intermediate_size``.

Departures, all of them also the program's: the vision and audio towers
and multi-token prediction are left out (``assumed.left_out``); the
indexer's float8 keys and Hadamard rotation are not written (storage).

How it is computed so that a 34,816-position request fits one chip: the
indexer's scores a block of ``_INDEX_ROWS`` queries at a time, each block's
exact top-k kept as positions; attention a group of ``_HEAD_GROUP`` heads at
a time (its keys and values expanded for the whole sequence), a block of
``_QUERY_ROWS`` queries at a time within it, the full layers' mask built
from the kept positions, the sliding layers' keys sliced to the window; the
routed sum as ``reference_moe_window`` computes it (the host lists each held
expert's tokens).

``numerics`` selects how the matrix products are computed, as in
``reference.py``: ``float32`` (TPU precision ``HIGHEST``), ``bfloat16`` and
``float8`` — the lower two are controls, rounding each operand once where
it is made (an attention layer's queries, keys and values once for all its
blocks of queries; float8 with one scale for the whole operand).  The
router's product is float32 in all three: it is float32 in the
configuration.  The arithmetic and a
fault (``Model(fault=)``) are arguments of one set of compiled programs,
shared by every ``Model`` of a configuration: on the chip a control or a
fault is a forward pass and no compile (a set takes minutes to compile).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (NUMERICS, _quant8, first_token,  # noqa
                                 gap_below_best, nest,
                                 seed_key)
from benchmark.reference_hybrid import product, rms_norm
from benchmark.reference_moe_window import _at
from benchmark.reference_ssm import _normal

_F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# positions the head is applied to at once; queries a block of the
# indexer's scores and of the attention's; heads a group of expanded keys
_HEAD_ROWS, _INDEX_ROWS, _QUERY_ROWS, _HEAD_GROUP = 256, 64, 256, 8
FAULTS = ("no_selection", "newest", "no_rescale")
# a model's mode: the index of its arithmetic in NUMERICS, and of its fault
# in (None,) + FAULTS
_NO_SELECTION, _NEWEST, _NO_RESCALE = 1, 2, 3
_PIECES = {}        # (configuration, block) -> the jitted pieces
_LN_EPS = 1e-6          # the indexer's LayerNorm (assumed.indexer_is)


class Sizes:
    """The numbers of one configuration file, under the names used here;
    every item the published config does not give comes from ``assumed``,
    by name."""

    def __init__(self, config):
        a = config["assumed"]
        self.D = int(config["hidden_size"])
        self.F = int(config["intermediate_size"])
        self.Fe = int(config["moe_intermediate_size"])
        self.V = self.Vp = int(config["vocab_size"])
        self.L = int(config["num_hidden_layers"])
        self.kinds = tuple(config["layer_types"])
        if len(self.kinds) != self.L or set(self.kinds) - {FULL, SLIDING}:
            raise ValueError("layer_types names num_hidden_layers layers of "
                             "full_attention and sliding_attention")
        first_dense = int(config["first_k_dense_replace"])
        if int(config["moe_layer_freq"]) != 1:
            raise ValueError("moe_layer_freq other than 1: not written here")
        self.mlps = tuple(DENSE if i < first_dense else SPARSE
                          for i in range(self.L))
        self.E = int(config["router_outputs"])
        published = config.get("published", {}).get("n_routed_experts")
        if published is not None and int(published) != self.E:
            raise ValueError("router_outputs is the published expert count")
        self.first, self.held = (int(v) for v in config["experts_held"])
        if self.held != int(config["n_routed_experts"]):
            raise ValueError("n_routed_experts is the experts held here")
        if int(config["n_shared_experts"]) != 1:
            raise ValueError("one shared expert is written here")
        self.top_k = int(config["num_experts_per_tok"])
        self.routed_scale = float(config["routed_scaling_factor"])
        self.norm_topk = bool(config["norm_topk_prob"])
        self.max_len = int(config["max_position_embeddings"])
        self.eps = float(config["rms_norm_eps"])
        self.window = int(config["sliding_window_size"])
        self.rescale = bool(config["apply_mla_qkv_lora_rescale"])
        # per layer kind: heads, ranks, head sizes, rotary theta
        self.mla = {
            FULL: dict(H=int(config["num_attention_heads"]),
                       Rq=int(config["q_lora_rank"]),
                       Rkv=int(config["kv_lora_rank"]),
                       dn=int(config["qk_nope_head_dim"]),
                       dr=int(config["qk_rope_head_dim"]),
                       dv=int(config["v_head_dim"]),
                       theta=float(config["rope_theta"])),
            SLIDING: dict(H=int(config["swa_num_attention_heads"]),
                          Rq=int(config["swa_q_lora_rank"]),
                          Rkv=int(config["swa_kv_lora_rank"]),
                          dn=int(config["swa_qk_nope_head_dim"]),
                          dr=int(config["swa_qk_rope_head_dim"]),
                          dv=int(config["swa_v_head_dim"]),
                          theta=float(config["swa_rope_theta"]))}
        self.Hi = int(config["index_n_heads"])
        self.di = int(config["index_head_dim"])
        self.topk = int(config["index_topk"])
        if config["rope_scaling"] is not None:
            raise ValueError("rope_scaling: not written here")
        if config["scoring_func"] != "sigmoid" \
                or config["topk_method"] != "noaux_tc":
            raise ValueError("a router other than sigmoid with a selection "
                             "bias: not written here")
        if config["attention_bias"] or config["tie_word_embeddings"]:
            raise ValueError("attention bias or a tied head: not written "
                             "here")
        for name, want in (("lora_rescale", "longcat_flash"),
                           ("indexer", "deepseek_v32"),
                           ("attention_gate", "headwise"),
                           ("router_score", "sigmoid"),
                           ("hidden_act", "silu")):
            if a[name] != want:
                raise ValueError(f"assumed.{name} = {a[name]!r}: only "
                                 f"{want!r} is written here")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if config[key] != "headwise":
                raise ValueError(f"{key} {config[key]!r}: not written here")
        self.std = {k: float(v) for k, v in a["init_std"].items()}
        self.norm_jitter = float(a["norm_gain_jitter"])
        self.renorm_eps = 1e-6

    def count(self, kind):
        return sum(k == kind for k in self.kinds + self.mlps)

    def scales(self, kind):
        """``(s_q, s_kv)``: the latents' rescale after their norms."""
        m = self.mla[kind]
        if not self.rescale:
            return 1.0, 1.0
        return math.sqrt(self.D / m["Rq"]), math.sqrt(self.D / m["Rkv"])

    def attention_params(self, kind):
        """Matrix parameters of one attention layer of ``kind``: the two
        down projections, the query's up projection, W_UK and W_UV, the
        gate and the output projection (the indexer apart)."""
        m = self.mla[kind]
        H, Rq, Rkv = m["H"], m["Rq"], m["Rkv"]
        return (self.D * Rq + Rq * H * (m["dn"] + m["dr"])
                + self.D * (Rkv + m["dr"]) + Rkv * H * (m["dn"] + m["dv"])
                + self.D * H + H * m["dv"] * self.D)

    def indexer_params(self):
        Rq = self.mla[FULL]["Rq"]
        return Rq * self.Hi * self.di + self.D * self.di + self.D * self.Hi

    def expert_params(self):
        return 3 * self.D * self.Fe

    def sparse_shared_params(self):
        """Of one sparse layer, what every token multiplies: the router
        and the shared expert."""
        return self.D * self.E + self.expert_params()

    def dense_params(self):
        return 3 * self.D * self.F

    def head_params(self):
        return self.D * self.Vp

    def n_params(self):
        attn = sum(self.attention_params(k) for k in self.kinds)
        index = self.count(FULL) * self.indexer_params()
        sparse = self.count(SPARSE) * (self.sparse_shared_params()
                                       + self.held * self.expert_params())
        return (attn + index + sparse + self.count(DENSE)
                * self.dense_params() + 2 * self.head_params())


def leaf_table(s):
    """name -> (shape, how it is drawn): a float is the deviation of a
    normal matrix (``assumed.init_std``), ``"gain"`` a norm's gain,
    ``"bias"`` a float32 bias of deviation ``init_std[<what>]``."""
    D, std = s.D, s.std
    table = {"wte": ((s.Vp, D), std["wte"])}
    for kind, short in ((FULL, "full"), (SLIDING, "sliding")):
        n, m = s.count(kind), s.mla[kind]
        H, Rq, Rkv = m["H"], m["Rq"], m["Rkv"]
        table.update({
            f"{kind}/ln1": ((n, D), "gain"),
            f"{kind}/q_a": ((n, D, Rq), std[short + "_q_a"]),
            f"{kind}/q_norm": ((n, Rq), "gain"),
            f"{kind}/q_b": ((n, Rq, H * (m["dn"] + m["dr"])),
                            std[short + "_q_b"]),
            f"{kind}/kv_a": ((n, D, Rkv + m["dr"]), std[short + "_kv_a"]),
            f"{kind}/kv_norm": ((n, Rkv), "gain"),
            f"{kind}/k_b": ((n, Rkv, H, m["dn"]), std[short + "_k_b"]),
            f"{kind}/v_b": ((n, Rkv, H, m["dv"]), std[short + "_v_b"]),
            f"{kind}/g_w": ((n, D, H), std[short + "_g_w"]),
            f"{kind}/o_w": ((n, H * m["dv"], D), std[short + "_o_w"])})
    n, Rq = s.count(FULL), s.mla[FULL]["Rq"]
    table.update({
        f"{FULL}/iq": ((n, Rq, s.Hi * s.di), std["index_q"]),
        f"{FULL}/ik": ((n, D, s.di), std["index_k"]),
        f"{FULL}/ik_norm": ((n, s.di), "gain"),
        f"{FULL}/ik_bias": ((n, s.di), ("bias", "index_k_bias")),
        f"{FULL}/iw": ((n, D, s.Hi), std["index_w"])})
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
        "sparse/expert_bias": ((n, s.E), ("bias", "expert_bias")),
        "sparse/shared_gate_w": ((n, D, s.Fe), std["shared_gate_w"]),
        "sparse/shared_up_w": ((n, D, s.Fe), std["shared_up_w"]),
        "sparse/shared_down_w": ((n, s.Fe, D), std["shared_down_w"]),
        "sparse/gate_w": ((n, s.held, D, s.Fe), std["expert_gate_w"]),
        "sparse/up_w": ((n, s.held, D, s.Fe), std["expert_up_w"]),
        "sparse/down_w": ((n, s.held, s.Fe, D), std["expert_down_w"])})
    table["norm_f"] = ((D,), "gain")
    table["lm_head"] = ((D, s.Vp), std["lm_head"])
    return table


def make_weights(config, key, dtype):
    """The parameters, from ``seed_key(seed)``.  Traceable: jit it (one
    program, made on the device); a leaf is drawn in blocks of its leading
    axes so that no float32 array of the experts exists.  Biases stay
    float32 whatever ``dtype``."""
    s = Sizes(config)
    table = leaf_table(s)
    flat = {}
    for k, (name, (shape, init)) in zip(jax.random.split(key, len(table)),
                                        table.items()):
        if init == "gain":
            flat[name] = (1.0 + s.norm_jitter * jax.random.normal(
                k, shape, _F32)).astype(dtype)
        elif isinstance(init, tuple):
            flat[name] = s.std[init[1]] * jax.random.normal(k, shape, _F32)
        elif name.startswith("sparse/") and len(shape) == 4:
            # [layers, experts, ...]: more blocks
            flat[name] = _normal(k, (shape[0] * shape[1],) + shape[2:],
                                 init, dtype).reshape(shape)
        else:
            flat[name] = _normal(k, shape, init, dtype)
    return nest(flat)


def weights(config, seed, dtype):
    return jax.jit(lambda key: make_weights(config, key, dtype))(
        seed_key(seed))


# ------------------------------------------------------------- the model


def rotate(x, pos, theta, rot):
    """The first ``rot`` dimensions of ``x [S, H, d]`` rotated at ``pos
    [S]`` (halves against each other), the rest passed through."""
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = pos.astype(_F32)[:, None, None] * jnp.asarray(inv, _F32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def layer_norm(x, g, b, eps=_LN_EPS):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def route(s, exact, u, router_w, bias):
    """``(weights [S, k], chosen experts [S, k])`` of normalised inputs
    ``u [S, D]``: sigmoid scores, chosen by score plus bias, weighted by
    the unbiased score renormalised."""
    p = jax.nn.sigmoid(exact("sd,de->se", u, router_w))
    _, idx = jax.lax.top_k(p + bias, s.top_k)
    w = jnp.take_along_axis(p, idx, axis=-1)
    if s.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + s.renorm_eps)
    return w * s.routed_scale, idx


def _rnd(mode):
    """An operand rounded in the arithmetic ``mode[0]`` names
    (``NUMERICS``): not at all, to bfloat16, or to float8 then bfloat16;
    float32 out, chosen on the device.  A ``jnp.where`` over the three:
    with a ``lax.switch`` in its place the float8 control read within
    0.0002 of the float32 rows on a v5e, where the CPU reads some 0.08."""

    def rnd(a):
        a = a.astype(_F32)
        return jnp.where(mode[0] == 0, a, jnp.where(
            mode[0] == 1, a.astype(jnp.bfloat16).astype(_F32),
            _quant8(a).astype(_F32)))

    return rnd


def _mm(mode):
    """``einsum`` on two operands in the arithmetic ``mode[0]`` names, as
    ``reference_hybrid.product``: the operands rounded (``_rnd``) and
    multiplied in float32 at ``HIGHEST``."""
    exact, rnd = product("float32"), _rnd(mode)
    return lambda eq, a, b: exact(eq, rnd(a), rnd(b))


def _pieces(s):
    """The jitted pieces of the forward pass; all but ``embed`` take the
    model's ``mode`` (arithmetic, fault) first."""
    exact = product("float32")

    @jax.jit
    def embed(wte, tok):
        return jnp.take(wte, tok, axis=0).astype(_F32)

    def latents(mm, fault, kind, p, u, pos):
        """``(c_q [S, Rq], c_kv [S, Rkv], k_rope [S, dr])``."""
        m = s.mla[kind]
        # the fault "no_rescale" multiplies by 1
        s_q, s_kv = (jnp.where(fault == _NO_RESCALE, 1.0, v)
                     for v in s.scales(kind))
        cq = rms_norm(mm("sd,dr->sr", u, p["q_a"]), p["q_norm"],
                      s.eps) * s_q
        kv = mm("sd,dr->sr", u, p["kv_a"])
        ckv = rms_norm(kv[:, : m["Rkv"]], p["kv_norm"], s.eps) * s_kv
        k_rope = rotate(kv[:, None, m["Rkv"]:], pos, m["theta"],
                        m["dr"])[:, 0]
        return cq, ckv, k_rope

    @jax.jit
    def indexer(mode, p, x):
        """``[S, K]`` positions each query's full layer reads (exact
        top-k of the indexer's scores; entries past ``t`` unused), and
        ``[S]`` how many of them it reads."""
        mm, rnd = _mm(mode), _rnd(mode)
        n = x.shape[0]
        p = jax.tree_util.tree_map(lambda a: a.astype(_F32), p)
        u = rms_norm(x, p["ln1"], s.eps)
        pos = jnp.arange(n)
        m = s.mla[FULL]
        cq, _, _ = latents(mm, mode[1], FULL, p, u, pos)
        ki = layer_norm(mm("sd,di->si", u, p["ik"]), p["ik_norm"],
                        p["ik_bias"])
        ki = rotate(ki[:, None], pos, m["theta"], m["dr"])[:, 0]
        w = mm("sd,dj->sj", u, p["iw"]) / math.sqrt(s.Hi)
        K = min(s.topk, n)
        rows = min(_INDEX_ROWS, n)
        # the operands every block multiplies, rounded once
        cq, iq, ki = rnd(cq), rnd(p["iq"]), rnd(ki)

        def block(start):
            at = start + jnp.arange(rows)
            qi = exact("qr,re->qe", jax.lax.dynamic_slice_in_dim(
                cq, start, rows), iq).reshape(rows, s.Hi, s.di)
            qi = rotate(qi, at, m["theta"], m["dr"])
            wb = jax.lax.dynamic_slice_in_dim(w, start, rows)
            sc = jax.nn.relu(exact("qjd,sd->qjs", rnd(qi), ki))
            score = jnp.einsum("qjs,qj->qs", sc, wb,
                               precision=jax.lax.Precision.HIGHEST) \
                / math.sqrt(s.di)
            score = jnp.where(pos[None, :] <= at[:, None], score,
                              -jnp.inf)
            return jax.lax.top_k(score, K)[1].astype(jnp.int32)

        picked = jax.lax.map(block, jnp.arange(0, n, rows))
        return picked.reshape(n, K), jnp.minimum(pos + 1, K)

    def attention(mode, kind, p, x, picked=None):
        """``x + Attn(RMSNorm(x))`` for a layer of ``kind``, expanded
        form; a full layer reads the positions ``picked`` holds (or,
        under the faults "no_selection" and "newest", every position up
        to the query or the newest ``topk`` of them)."""
        mm, rnd, fault = _mm(mode), _rnd(mode), mode[1]
        n = x.shape[0]
        m = s.mla[kind]
        H, dn, dr, dv = m["H"], m["dn"], m["dr"], m["dv"]
        p = jax.tree_util.tree_map(lambda a: a.astype(_F32), p)
        u = rms_norm(x, p["ln1"], s.eps)
        pos = jnp.arange(n)
        cq, ckv, k_rope = latents(mm, fault, kind, p, u, pos)
        scale = 1.0 / math.sqrt(dn + dr)
        rows = min(_QUERY_ROWS, n)
        W = s.window
        group = min(_HEAD_GROUP, H)
        G = H // group

        def groups(a, axis):
            """``a`` with its head axis ``axis`` split into ``G``
            groups, the group axis first."""
            shape = a.shape[:axis] + (G, group) + a.shape[axis + 1:]
            return jnp.moveaxis(a.reshape(shape), axis, 0)

        # one group of heads at a time (a scan, so that one group's
        # expanded keys and values exist at once), each group's gated
        # output through its rows of W_o added into the residual
        gate = jax.nn.sigmoid(mm("sd,dh->sh", u, p["g_w"]))
        per_group = (groups(p["q_b"].reshape(-1, H, dn + dr), 1),
                     groups(p["k_b"], 1), groups(p["v_b"], 1),
                     groups(gate, 1),
                     p["o_w"].reshape(G, group * dv, s.D))

        def one_group(acc, w):
            q_b, k_b, v_b, g, o_w = w
            # [q_nope | q_rope] against [k_nope | k_rope]
            q = mm("sr,rhe->she", cq, q_b)
            q = jnp.concatenate([q[..., :dn], rotate(
                q[..., dn:], pos, m["theta"], dr)], axis=-1)
            k = jnp.concatenate(
                [mm("sc,chd->shd", ckv, k_b),
                 jnp.broadcast_to(k_rope[:, None], (n, group, dr))],
                axis=-1)
            v = mm("sc,chd->shd", ckv, v_b)
            # the operands every block of queries multiplies, rounded once
            q, k, v = rnd(q), rnd(k), rnd(v)
            if kind == SLIDING:
                # keys from W - 1 positions before the block: padded
                k = jnp.pad(k, ((W, 0), (0, 0), (0, 0)))
                v = jnp.pad(v, ((W, 0), (0, 0), (0, 0)))

            def queries(start):
                qb = jax.lax.dynamic_slice_in_dim(q, start, rows)
                at = start + jnp.arange(rows)
                if kind == SLIDING:
                    kb = jax.lax.dynamic_slice_in_dim(k, start,
                                                      rows + W)
                    vb = jax.lax.dynamic_slice_in_dim(v, start,
                                                      rows + W)
                    kpos = start - W + jnp.arange(rows + W)
                    ok = ((kpos[None, :] <= at[:, None])
                          & (kpos[None, :] > at[:, None] - W)
                          & (kpos[None, :] >= 0))
                else:
                    kb, vb = k, v
                    sel = jax.lax.dynamic_slice_in_dim(picked[0], start,
                                                       rows)
                    cnt = jax.lax.dynamic_slice_in_dim(picked[1], start,
                                                       rows)
                    live = jnp.arange(sel.shape[1])[None, :] \
                        < cnt[:, None]
                    # distinct positions: a chosen one is set True,
                    # one past the query (not chosen) False
                    ok = jnp.zeros((rows, n), bool).at[
                        jnp.arange(rows)[:, None], sel].set(live)
                    kpos = jnp.arange(n)[None, :]
                    causal = kpos <= at[:, None]
                    newest = causal & (kpos > at[:, None] - s.topk)
                    ok = jnp.where(fault == _NO_SELECTION, causal,
                                   jnp.where(fault == _NEWEST, newest, ok))
                sc = exact("qhe,she->hqs", qb, kb) * scale
                pr = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf),
                                    axis=-1)
                return exact("hqs,shd->qhd", rnd(pr), vb)

            heads = jax.lax.map(queries, jnp.arange(0, n, rows))
            heads = heads.reshape(n, group, dv) * g[..., None]
            return acc + mm("se,ed->sd", heads.reshape(n, group * dv),
                            o_w), None

        return jax.lax.scan(one_group, x, per_group)[0]

    def swiglu(mm, u, gate_w, up_w, down_w):
        up = lambda w: mm("sd,df->sf", u, w.astype(_F32))
        return mm("sf,fd->sd", jax.nn.silu(up(gate_w)) * up(up_w),
                  down_w.astype(_F32))

    @jax.jit
    def dense(mode, p, h):
        return h + swiglu(_mm(mode),
                          rms_norm(h, p["ln2"].astype(_F32), s.eps),
                          p["gate_w"], p["up_w"], p["down_w"])

    @jax.jit
    def routing(mode, p, h):
        """``(u, shared expert's output, weights [S, k], experts [S,
        k])`` of a sparse layer."""
        u = rms_norm(h, p["ln2"].astype(_F32), s.eps)
        w, idx = route(s, exact, u, p["router_w"].astype(_F32),
                       p["expert_bias"])
        shared = swiglu(_mm(mode), u, p["shared_gate_w"],
                        p["shared_up_w"], p["shared_down_w"])
        return u, shared, w, idx

    @jax.jit
    def routed(mode, p, u, rows, w):
        """``sum_e w_e E_e(u)`` over the held experts: ``rows [held,
        C]`` the tokens that chose each (``S``, a zero row, as
        padding), ``w [held, C]`` their weights."""
        u0 = jnp.concatenate([u, jnp.zeros((1, s.D), _F32)])
        mm = _mm(mode)

        def one(acc, at):
            gate_w, up_w, down_w, r, we = at
            y = swiglu(mm, u0[r], gate_w, up_w, down_w) * we[:, None]
            return acc.at[r].add(y), None

        acc, _ = jax.lax.scan(
            one, jnp.zeros_like(u0),
            (p["gate_w"], p["up_w"], p["down_w"], rows, w))
        return acc[:-1]

    @jax.jit
    def head(mode, norm_f, lm_head, h, r):
        """Logits of ``_HEAD_ROWS`` positions from ``r``."""
        h = jax.lax.dynamic_slice_in_dim(h, r, _HEAD_ROWS, axis=0)
        return _mm(mode)("sd,dv->sv", rms_norm(h, norm_f.astype(_F32), s.eps),
                  lm_head.astype(_F32))

    return {"embed": embed, "indexer": indexer, "dense": dense,
            "routing": routing, "routed": routed, "head": head,
            "attention": {
                FULL: jax.jit(lambda mode, p, x, picked: attention(
                    mode, FULL, p, x, picked)),
                SLIDING: jax.jit(lambda mode, p, x: attention(
                    mode, SLIDING, p, x))}}


class Model:
    """The jitted pieces and the loop over layers.  ``fault`` (one of
    ``FAULTS``, for the benchmark's calibration) writes the same model with
    a part of its mathematics changed: the full layers reading every
    position up to the query (``"no_selection"``) or the newest ``topk`` of
    them (``"newest"``) in place of the indexer's choice, or the latents'
    rescale left out (``"no_rescale"``)."""

    def __init__(self, config, numerics="float32", block=None, fault=None):
        if numerics not in NUMERICS:
            raise ValueError(f"numerics {numerics!r} is none of "
                             f"{NUMERICS}")
        if fault not in (None,) + FAULTS:
            raise ValueError(f"fault {fault!r} is none of {FAULTS}")
        self.fault = fault
        self.s = Sizes(config)
        # a sequence is padded to a block times a power of two: few shapes
        self.block = block or min(1024, self.s.max_len)
        key = (json.dumps(config, sort_keys=True), self.block)
        if key not in _PIECES:
            _PIECES[key] = _pieces(self.s)
        # the arithmetic and the fault are arguments of one set of
        # programs, so that a control or a fault compiles nothing more
        mode = jnp.asarray([NUMERICS.index(numerics),
                            ((None,) + FAULTS).index(fault)], jnp.int32)
        pieces = _PIECES[key]
        self.embed = pieces["embed"]
        for name in ("indexer", "dense", "routing", "routed", "head"):
            setattr(self, name, functools.partial(pieces[name], mode))
        self.attention = {k: functools.partial(f, mode)
                          for k, f in pieces["attention"].items()}

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

    def picked(self, p, h):
        """The indexer's choice, or under the two faults that read no
        choice zeros of its shapes (no scoring)."""
        if self.fault not in ("no_selection", "newest"):
            return self.indexer(p, h)
        n = h.shape[0]
        return (jnp.zeros((n, min(self.s.topk, n)), jnp.int32),
                jnp.zeros((n,), jnp.int32))

    def layers(self, params, h, n_real):
        s = self.s
        seen = dict.fromkeys((FULL, SLIDING, DENSE, SPARSE), 0)
        for kind, mlp in zip(s.kinds, s.mlps):
            i, j = seen[kind], seen[mlp]
            seen[kind] += 1
            seen[mlp] += 1
            p = _at(params[kind], i)
            if kind == FULL:
                h = self.attention[FULL](p, h, self.picked(p, h))
            else:
                h = self.attention[SLIDING](p, h)
            if mlp == DENSE:
                h = self.dense(_at(params[DENSE], j), h)
                continue
            p = _at(params[SPARSE], j)
            u, shared, w, idx = self.routing(p, h)
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

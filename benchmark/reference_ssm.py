"""The plain reference of the parallel-mixer decoder: a Mamba-2 state-space
mixer and grouped-query attention side by side in every block (Falcon-H1),
as a straightforward float32 ``jax.numpy`` forward — no kernel, no cache,
no batching, no chunking: one request at a time, its whole sequence, the
recurrence position by position.  Nothing here imports the program; what is
shared is the *format* of the parameters (one stack of layers), since the
same random weights have to mean the same function on both sides.

The equations (config keys in ``code``; every placement that the published
``config.json`` does not itself fix is listed in the configuration file's
``assumed`` block):

- Stream: ``h = embedding_multiplier * E[token]``; each block
  ``u = RMSNorm(h)``, ``h += att(u) + ssm(u)``, then
  ``h += MLP(RMSNorm(h))``; logits ``= lm_head_multiplier * W_head
  RMSNorm(h)``, head untied; no bias but the convolution's.
- Attention branch: ``a = attention_in_multiplier * u``; ``q = W_q a`` as
  ``num_attention_heads`` heads, ``k = key_multiplier * W_k a`` and ``v =
  W_v a`` as ``num_key_value_heads``; rotary positions over the whole head
  (halves rotated against each other, ``rope_theta``) on ``q`` and ``k``;
  causal ``softmax(q k^T / sqrt(head_dim)) v``, query head ``j`` reading
  key/value head ``j // (heads / kv heads)``; ``att =
  attention_out_multiplier * W_o heads``.
- State-space branch (Mamba-2, arXiv:2405.21060): ``p = W_in
  (ssm_in_multiplier * u)`` split as ``[z | x | B | C | dt]``, the five
  parts times ``ssm_multipliers[0..4]``; a causal depthwise convolution of
  width ``mamba_d_conv`` with bias over ``[x | B | C]`` (zeros before the
  first token), then SiLU; ``x`` as ``mamba_n_heads`` heads of
  ``mamba_d_head``, ``B`` and ``C`` as ``mamba_n_groups`` groups of
  ``mamba_d_state``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  per head a float32 state ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * SiLU(z))`` with the
  mean square within each group of ``d_ssm / groups`` channels; ``ssm =
  ssm_out_multiplier * W_out y``.
- ``MLP(v) = mlp_multipliers[1] * W_down(SiLU(mlp_multipliers[0] * W_gate
  v) * (W_up v))``.

``numerics`` selects how the matrix products are computed, as in
``reference.py``: ``float32`` (TPU precision ``HIGHEST``), ``bfloat16`` and
``float8`` — the lower two are controls.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import (first_token, gap_below_best, nest,  # noqa
                                 seed_key)
from benchmark.reference_hybrid import product, rms_norm, rope

_F32 = jnp.float32
# the widest float32 array the weights' program may hold while it draws one
# leaf in blocks, and the rows and columns the head is applied to at once
_DRAW_BYTES = 1 << 29
_HEAD_ROWS, _HEAD_COLS = 256, 32640


class Sizes:
    """The numbers of one configuration file, under the names used here;
    every item the published config does not give comes from ``assumed``,
    by name."""

    def __init__(self, config):
        a = config["assumed"]
        self.D = int(config["hidden_size"])
        self.F = int(config["intermediate_size"])
        self.V = self.Vp = int(config["vocab_size"])
        self.L = int(config["num_hidden_layers"])
        self.H = int(config["num_attention_heads"])
        self.Hkv = int(config["num_key_value_heads"])
        self.hd = int(config["head_dim"])
        self.Hs = int(config["mamba_n_heads"])
        self.P = int(config["mamba_d_head"])
        self.N = int(config["mamba_d_state"])
        self.G = int(config["mamba_n_groups"])
        self.K = int(config["mamba_d_conv"])
        self.d_ssm = int(config["mamba_d_ssm"])
        if self.d_ssm != self.Hs * self.P:
            raise ValueError("mamba_d_ssm is not mamba_n_heads * "
                             "mamba_d_head")
        self.bc = self.G * self.N
        self.conv_channels = self.d_ssm + 2 * self.bc
        self.in_width = 2 * self.d_ssm + 2 * self.bc + self.Hs
        self.max_len = int(config["max_position_embeddings"])
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.embedding_multiplier = float(config["embedding_multiplier"])
        self.attention_in = float(config["attention_in_multiplier"])
        self.attention_out = float(config["attention_out_multiplier"])
        self.key_multiplier = float(config["key_multiplier"])
        self.ssm_in = float(config["ssm_in_multiplier"])
        self.ssm_multipliers = tuple(map(float, config["ssm_multipliers"]))
        self.ssm_out = float(config["ssm_out_multiplier"])
        self.mlp_multipliers = tuple(map(float, config["mlp_multipliers"]))
        self.lm_head_multiplier = float(config["lm_head_multiplier"])
        self.std = {k: float(v) for k, v in a["init_std"].items()}
        self.norm_jitter = float(a["norm_gain_jitter"])
        self.a_range = tuple(map(float, a["a_range"]))
        self.dt_range = tuple(map(float, a["dt_range"]))
        self.d_jitter = float(a["d_jitter"])
        self.conv_range = float(a["conv_range"])

    def layer_matmul_params(self):
        """Parameters of one block that take part in a product for every
        token: the attention and state-space projections and the MLP."""
        D = self.D
        return (D * self.hd * (self.H + 2 * self.Hkv) + self.H * self.hd * D
                + D * self.in_width + self.d_ssm * D + 3 * D * self.F)

    def head_params(self):
        return self.D * self.Vp

    def n_params(self):
        per_layer = (self.layer_matmul_params() + 2 * self.D
                     + (self.K + 1) * self.conv_channels + 3 * self.Hs
                     + self.d_ssm)
        return self.L * per_layer + 2 * self.head_params() + self.D


def leaf_table(s):
    """name -> (shape, how it is drawn): a float is the deviation of a
    normal matrix (``assumed.init_std`` by the leaf's name), ``"gain"`` a
    norm's gain, the others what sets the scan."""
    L, D, F = s.L, s.D, s.F
    std = s.std
    blocks = {
        "ln1": ((L, D), "gain"), "ln2": ((L, D), "gain"),
        "q_w": ((L, D, s.H * s.hd), std["q_w"]),
        "k_w": ((L, D, s.Hkv * s.hd), std["k_w"]),
        "v_w": ((L, D, s.Hkv * s.hd), std["v_w"]),
        "o_w": ((L, s.H * s.hd, D), std["o_w"]),
        "in_w": ((L, D, s.in_width), std["in_w"]),
        "conv_w": ((L, s.K, s.conv_channels), "conv"),
        "conv_b": ((L, s.conv_channels), "conv"),
        "A_log": ((L, s.Hs), "a_log"),
        "D": ((L, s.Hs), "d"),
        "dt_bias": ((L, s.Hs), "dt_bias"),
        "ssm_norm": ((L, s.d_ssm), "gain"),
        "out_w": ((L, s.d_ssm, D), std["out_w"]),
        "mlp_gate_w": ((L, D, F), std["mlp_gate_w"]),
        "mlp_up_w": ((L, D, F), std["mlp_up_w"]),
        "mlp_down_w": ((L, F, D), std["mlp_down_w"]),
    }
    table = {"wte": ((s.Vp, D), std["wte"])}
    table.update({"blocks/" + n: leaf for n, leaf in blocks.items()})
    table["norm_f"] = ((D,), "gain")
    table["lm_head"] = ((D, s.Vp), std["lm_head"])
    return table


def _normal(key, shape, std, dtype):
    """``std * normal`` in ``dtype``, drawn in blocks of the leading axis
    so that no float32 array of the whole leaf exists (the two tables of
    the large configuration are 5.3 GB each in float32)."""
    size = 4 * math.prod(shape)
    blocks = next(n for n in range(1, shape[0] + 1)
                  if shape[0] % n == 0 and size // n <= _DRAW_BYTES)
    if blocks == 1:
        return (jax.random.normal(key, shape, _F32) * std).astype(dtype)
    part = (shape[0] // blocks,) + tuple(shape[1:])
    draw = lambda i: (jax.random.normal(jax.random.fold_in(key, i), part,
                                        _F32) * std).astype(dtype)
    return jax.lax.map(draw, jnp.arange(blocks)).reshape(shape)


def make_weights(config, key, dtype):
    """The parameters, from ``seed_key(seed)``.  What sets the decay stays
    float32 whatever ``dtype``.  Traceable: jit it (one program, made on
    the device)."""
    s = Sizes(config)
    table = leaf_table(s)
    flat = {}
    for k, (name, (shape, init)) in zip(jax.random.split(key, len(table)),
                                        table.items()):
        if isinstance(init, float):
            flat[name] = _normal(k, shape, init, dtype)
        elif init == "gain":
            flat[name] = (1.0 + s.norm_jitter * jax.random.normal(
                k, shape, _F32)).astype(dtype)
        elif init == "conv":
            flat[name] = jax.random.uniform(
                k, shape, _F32, -s.conv_range, s.conv_range).astype(dtype)
        elif init == "a_log":
            flat[name] = jnp.log(jax.random.uniform(k, shape, _F32,
                                                    *s.a_range))
        elif init == "d":
            flat[name] = 1.0 + s.d_jitter * jax.random.normal(k, shape, _F32)
        elif init == "dt_bias":
            lo, hi = (math.log(v) for v in s.dt_range)
            step = jnp.exp(jax.random.uniform(k, shape, _F32, lo, hi))
            flat[name] = step + jnp.log(-jnp.expm1(-step))
        else:
            raise ValueError(f"{name}: how is {init!r} drawn")
    return nest(flat)


def weights(config, seed, dtype):
    return jax.jit(lambda key: make_weights(config, key, dtype))(
        seed_key(seed))


# ------------------------------------------------------------- the model


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i].astype(_F32), tree)


class Model:
    """The jitted pieces and the loop over layers."""

    def __init__(self, config, numerics="float32", block=None):
        s = self.s = Sizes(config)
        mm = product(numerics)
        # a sequence is padded to a multiple: few shapes
        self.block = block or min(1024, s.max_len)
        rep_kv, rep_g = s.H // s.Hkv, s.Hs // s.G
        m_z, m_x, m_b, m_c, m_dt = s.ssm_multipliers

        @jax.jit
        def embed(wte, tok):
            return jnp.take(wte, tok, axis=0).astype(_F32) \
                * s.embedding_multiplier

        def attention(p, u):
            n = u.shape[0]
            pos = jnp.arange(n)
            a = u * s.attention_in
            q = mm("sd,de->se", a, p["q_w"]).reshape(n, s.H, s.hd)
            k = (mm("sd,de->se", a, p["k_w"])
                 * s.key_multiplier).reshape(n, s.Hkv, s.hd)
            v = mm("sd,de->se", a, p["v_w"]).reshape(n, s.Hkv, s.hd)
            q, k = rope(q, pos, s.theta), rope(k, pos, s.theta)
            qg = q.reshape(n, s.Hkv, rep_kv, s.hd)
            sc = mm("qhgd,thd->hgqt", qg, k) / math.sqrt(s.hd)
            causal = pos[None, :] <= pos[:, None]
            pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
            heads = mm("hgqt,thd->qhgd", pr, v).reshape(n, s.H * s.hd)
            return mm("se,ed->sd", heads, p["o_w"]) * s.attention_out

        def state_space(p, u):
            n = u.shape[0]
            proj = mm("sd,de->se", u * s.ssm_in, p["in_w"])
            z, x, bm, cm, dt = jnp.split(proj, np.cumsum(
                [s.d_ssm, s.d_ssm, s.bc, s.bc]), axis=1)
            xbc = jnp.concatenate([x * m_x, bm * m_b, cm * m_c], axis=1)
            # zeros before the sequence's first token
            padded = jnp.pad(xbc, ((s.K - 1, 0), (0, 0)))
            conv = sum(padded[j:j + n] * p["conv_w"][j] for j in range(s.K))
            xbc = jax.nn.silu(conv + p["conv_b"])
            x, bm, cm = jnp.split(xbc, np.cumsum([s.d_ssm, s.bc]), axis=1)
            x = x.reshape(n, s.Hs, s.P)
            # a group's B and C for each of its heads
            bm = jnp.repeat(bm.reshape(n, s.G, s.N), rep_g, axis=1)
            cm = jnp.repeat(cm.reshape(n, s.G, s.N), rep_g, axis=1)
            dt = jax.nn.softplus(dt * m_dt + p["dt_bias"])        # [n, Hs]
            A = -jnp.exp(p["A_log"])

            def step(S, at):
                xt, bt, ct, dtt = at
                S = jnp.exp(dtt * A)[:, None, None] * S \
                    + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
                return S, mm("hpn,hn->hp", S, ct) + p["D"][:, None] * xt

            _, y = jax.lax.scan(step, jnp.zeros((s.Hs, s.P, s.N), _F32),
                                (x, bm, cm, dt))
            y = y.reshape(n, s.d_ssm) * jax.nn.silu(z * m_z)
            y = rms_norm(y.reshape(n, s.G, -1), 1.0, s.eps).reshape(
                n, s.d_ssm) * p["ssm_norm"]
            return mm("se,ed->sd", y, p["out_w"]) * s.ssm_out

        @jax.jit
        def layer(stack, i, h):
            p = _layer(stack, i)
            u = rms_norm(h, p["ln1"], s.eps)
            h = h + attention(p, u) + state_space(p, u)
            v = rms_norm(h, p["ln2"], s.eps)
            gate = mm("sd,df->sf", v, p["mlp_gate_w"]) * s.mlp_multipliers[0]
            up = mm("sd,df->sf", v, p["mlp_up_w"])
            return h + mm("sf,fd->sd", jax.nn.silu(gate) * up,
                          p["mlp_down_w"]) * s.mlp_multipliers[1]

        cols = min(_HEAD_COLS, s.Vp)
        if s.Vp % cols:
            raise ValueError(f"{cols} ids at a time do not divide {s.Vp}")

        @jax.jit
        def head(norm_f, lm_head, h, r, c):
            """Logits of ``_HEAD_ROWS`` positions from ``r`` for ``cols``
            ids from ``c``."""
            h = jax.lax.dynamic_slice_in_dim(h, r, _HEAD_ROWS, axis=0)
            w = jax.lax.dynamic_slice_in_dim(lm_head, c, cols, axis=1)
            return mm("sd,dv->sv", rms_norm(h, norm_f.astype(_F32), s.eps),
                      w.astype(_F32)) * s.lm_head_multiplier

        self.cols = cols
        self.embed, self.layer, self.head = embed, layer, head

    def forward_logits(self, params, tokens, n_prompt):
        """Float32 logits ``[len(tokens) - n_prompt + 1, vocabulary]`` of
        one request at the positions ``n_prompt - 1 .. len(tokens) - 1``:
        the positions a server decoded from.  The head is applied to
        ``_HEAD_ROWS`` positions and ``_HEAD_COLS`` ids at a time."""
        s, B = self.s, self.block
        length = len(tokens)
        if length > -(-s.max_len // B) * B:
            raise ValueError(f"{length} tokens, configured for {s.max_len}")
        row = np.zeros((-(-length // B) * B,), np.int32)
        row[:length] = tokens
        h = self.embed(params["wte"], row)
        for i in range(s.L):
            h = self.layer(params["blocks"], np.int32(i), h)
        first = n_prompt - 1
        # room for the last block of rows: a slice is never clamped
        h = jnp.pad(h, ((0, _HEAD_ROWS), (0, 0)))
        rows = [jnp.concatenate([
            self.head(params["norm_f"], params["lm_head"], h, np.int32(r),
                      np.int32(c)) for c in range(0, s.Vp, self.cols)],
            axis=1) for r in range(first, length, _HEAD_ROWS)]
        return jnp.concatenate(rows)[: length - first]

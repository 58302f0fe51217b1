"""The plain reference: a decoder transformer of the GPT-2/3 family in
straightforward ``jax.numpy`` and float32, with its loss, its gradients and
the AdamW step — and the weights, which the benchmark makes from the seed
and hands to the program and to this file alike.

Nothing here imports the program, and nothing the program has made comes in:
``make_weights`` builds the parameters from the seed, ``Reference`` follows a
training run from them, ``forward_logits`` scores served tokens.

What is copied from the program is the *format* of the parameters, because
the same random weights have to mean the same function on both sides:

- block parameters are stacked on a leading layer axis;
- the columns of ``qkv_w`` are head-major ``[head, (q, k, v), head_size]``;
- the output head is the transposed token embedding (tied).

The mathematics is the published one: pre-LayerNorm blocks, learned absolute
positions, causal softmax attention scaled by ``1/sqrt(head_size)``,
``gelu_new`` (the tanh form), cross entropy over the padded vocabulary with
the last position of every row ignored.

Memory.  At 1.3 billion parameters float32 weights, gradients and both Adam
moments are 21 GB, and the chip has 16.  So the reference (1) keeps what the
cell's recipe *stores* in the type the recipe states (bf16 parameters, and
for the recipe with bf16 optimizer state bf16 moments), computing in float32
from them; (2) runs one block of rows at a time, layer by layer, holding only
the layer boundaries of that block and recomputing inside each layer for the
backward pass; (3) accumulates gradients in float32 in place.

``numerics`` selects how matrix products are computed: ``float32`` (TPU
precision ``HIGHEST`` — the reference), ``bfloat16`` (one bf16 pass, float32
accumulation) and ``float8`` (operands rounded to e4m3 with a power-of-two
scale per tensor, then one bf16 pass) — the two lower ones are the controls.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

IGNORE = -100
NUMERICS = ("float32", "bfloat16", "float8")
_F32 = jnp.float32


# ------------------------------------------------------------------ sizes


class Sizes:
    """The numbers of one configuration file, under the names used here."""

    def __init__(self, config):
        self.D = int(config["n_embd"])
        self.L = int(config["n_layer"])
        self.H = int(config["n_head"])
        self.F = int(config.get("n_inner") or 4 * self.D)
        self.P = int(config["n_positions"])
        self.V = int(config["vocab_size"])
        # rows of the embedding table; ids are drawn below vocab_size
        self.Vp = int(config.get("assumed", {}).get("padded_vocab_size",
                                                    self.V))
        self.eps = float(config.get("layer_norm_epsilon", 1e-5))
        self.std = float(config.get("initializer_range", 0.02))
        if self.D % self.H:
            raise ValueError("n_embd is not a multiple of n_head")
        self.hd = self.D // self.H

    def n_params(self):
        D, F, L = self.D, self.F, self.L
        per_layer = (4 * D + 3 * D * D + 3 * D + D * D + D
                     + D * F + F + F * D + D)
        return self.Vp * D + self.P * D + L * per_layer + 2 * D


def leaf_table(s):
    """name -> (shape, init std or the constant it starts at, decayed)."""
    D, F, L = s.D, s.F, s.L
    resid = s.std / math.sqrt(2 * L)
    return {
        "wte": ((s.Vp, D), s.std, True),
        "wpe": ((s.P, D), s.std / 2, True),
        "blocks/ln1_g": ((L, D), "ones", False),
        "blocks/ln1_b": ((L, D), "zeros", False),
        "blocks/qkv_w": ((L, D, 3 * D), s.std, True),
        "blocks/qkv_b": ((L, 3 * D), "zeros", False),
        "blocks/proj_w": ((L, D, D), resid, True),
        "blocks/proj_b": ((L, D), "zeros", False),
        "blocks/ln2_g": ((L, D), "ones", False),
        "blocks/ln2_b": ((L, D), "zeros", False),
        "blocks/up_w": ((L, D, F), s.std, True),
        "blocks/up_b": ((L, F), "zeros", False),
        "blocks/down_w": ((L, F, D), resid, True),
        "blocks/down_b": ((L, D), "zeros", False),
        "lnf_g": ((D,), "ones", False),
        "lnf_b": ((D,), "zeros", False),
    }


def nest(flat):
    out = {}
    for name, val in flat.items():
        node = out
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = val
    return out


def flatten(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def seed_key(seed):
    """The key the weights are drawn from; any whole number is a seed."""
    return jax.random.key(int(seed) % (1 << 32))


def make_weights(config, key, dtype):
    """The parameters, from ``seed_key(seed)``: normal matrices and
    embeddings, unit norm gains, zero biases.  Traceable: jit it (one
    program, made on the device in the type asked for)."""
    s = Sizes(config)
    table = leaf_table(s)
    keys = jax.random.split(key, len(table))
    flat = {}
    for key, (name, (shape, init, _)) in zip(keys, table.items()):
        if init == "ones":
            flat[name] = jnp.ones(shape, dtype)
        elif init == "zeros":
            flat[name] = jnp.zeros(shape, dtype)
        else:
            flat[name] = (jax.random.normal(key, shape, _F32)
                          * init).astype(dtype)
    return nest(flat)


def weights(config, seed, dtype):
    """``make_weights`` as one jitted program, run: the parameters on the
    default device."""
    return jax.jit(lambda key: make_weights(config, key, dtype))(
        seed_key(seed))


# ------------------------------------------------------------- the model


def _quant8(x):
    """Round to float8 e4m3 with a power-of-two scale for the whole tensor;
    comes back as bf16, which holds every e4m3 value exactly."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = jax.lax.stop_gradient(
        jnp.exp2(jnp.floor(jnp.log2(448.0 / amax))))
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(_F32) / scale
    # the rounding has no gradient of its own: pass the operand's through
    return (x + jax.lax.stop_gradient(q - x)).astype(jnp.bfloat16)


def product(numerics):
    """``einsum`` on two operands, in the arithmetic asked for."""
    if numerics == "float32":
        return lambda eq, a, b: jnp.einsum(
            eq, a.astype(_F32), b.astype(_F32),
            precision=jax.lax.Precision.HIGHEST)
    if numerics == "bfloat16":
        return lambda eq, a, b: jnp.einsum(
            eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=_F32)
    if numerics == "float8":
        return lambda eq, a, b: jnp.einsum(
            eq, _quant8(a), _quant8(b), preferred_element_type=_F32)
    raise ValueError(f"numerics {numerics!r} is none of {NUMERICS}")


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(s, mm, bp, x):
    """One pre-LayerNorm block on ``x [rows, positions, D]`` in float32;
    ``bp`` is one layer's parameters in float32."""
    R, S, _ = x.shape
    h = layer_norm(x, bp["ln1_g"], bp["ln1_b"], s.eps)
    qkv = mm("rsd,de->rse", h, bp["qkv_w"]) + bp["qkv_b"]
    qkv = qkv.reshape(R, S, s.H, 3, s.hd)
    q, k, v = (qkv[:, :, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = mm("rhqd,rhkd->rhqk", q, k) / math.sqrt(s.hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mm("rhqk,rhkd->rhqd", probs, v)
    att = att.transpose(0, 2, 1, 3).reshape(R, S, s.D)
    x = x + mm("rsd,de->rse", att, bp["proj_w"]) + bp["proj_b"]
    h = layer_norm(x, bp["ln2_g"], bp["ln2_b"], s.eps)
    h = gelu_new(mm("rsd,df->rsf", h, bp["up_w"]) + bp["up_b"])
    return x + mm("rsf,fd->rsd", h, bp["down_w"]) + bp["down_b"]


def head_logits(s, mm, wte, lnf_g, lnf_b, x):
    return mm("rsd,vd->rsv", layer_norm(x, lnf_g, lnf_b, s.eps), wte)


def head_loss_sum(s, mm, wte, lnf_g, lnf_b, x, labels):
    """Summed cross entropy over the positions whose label is not IGNORE."""
    logits = head_logits(s, mm, wte, lnf_g, lnf_b, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(labels != IGNORE, picked, 0.0))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(_F32), tree)


def _layer_of(blocks, l):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)
        .astype(_F32), blocks)


@jax.jit
def _zeros_f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, _F32), tree)


class Model:
    """The jitted pieces, one layer and one block of rows at a time."""

    def __init__(self, config, numerics="float32"):
        s = self.s = Sizes(config)
        mm = product(numerics)

        @jax.jit
        def embed(wte, wpe, tok):
            return (jnp.take(wte, tok, axis=0).astype(_F32)
                    + wpe[: tok.shape[1]].astype(_F32))

        @jax.jit
        def layer_fwd(blocks, l, x):
            return block(s, mm, _layer_of(blocks, l), x)

        @partial(jax.jit, donate_argnums=(0,))
        def layer_bwd(gblocks, blocks, l, x_in, dx):
            _, vjp = jax.vjp(lambda bp, x: block(s, mm, bp, x),
                             _layer_of(blocks, l), x_in)
            dbp, dx_in = vjp(dx)
            gblocks = jax.tree_util.tree_map(
                lambda g, d: jax.lax.dynamic_update_index_in_dim(
                    g, jax.lax.dynamic_index_in_dim(g, l, 0, False) + d,
                    l, 0), gblocks, dbp)
            return gblocks, dx_in

        @partial(jax.jit, donate_argnums=(0,))
        def head_bwd(gaux, wte, lnf_g, lnf_b, x, labels, weight):
            """Adds the head's gradients of ``weight * loss_sum``."""
            loss, vjp = jax.vjp(
                lambda w, g, b, x: head_loss_sum(s, mm, w, g, b, x, labels),
                wte.astype(_F32), lnf_g.astype(_F32), lnf_b.astype(_F32), x)
            dw, dg, db, dx = vjp(weight)
            gaux = {"wte": gaux["wte"] + dw, "wpe": gaux["wpe"],
                    "lnf_g": gaux["lnf_g"] + dg, "lnf_b": gaux["lnf_b"] + db}
            return gaux, loss, dx

        @partial(jax.jit, donate_argnums=(0,))
        def embed_bwd(gaux, tok, dx):
            S = tok.shape[1]
            gaux = dict(gaux)
            gaux["wte"] = gaux["wte"].at[tok.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1]))
            gaux["wpe"] = gaux["wpe"].at[:S].add(dx.sum(0))
            return gaux

        @jax.jit
        def loss_sum(wte, lnf_g, lnf_b, x, labels):
            return head_loss_sum(s, mm, wte.astype(_F32),
                                 lnf_g.astype(_F32), lnf_b.astype(_F32),
                                 x, labels)

        @jax.jit
        def logits(wte, lnf_g, lnf_b, x):
            return head_logits(s, mm, wte.astype(_F32), lnf_g.astype(_F32),
                               lnf_b.astype(_F32), x)

        self.embed, self.layer_fwd, self.layer_bwd = embed, layer_fwd, \
            layer_bwd
        self.head_bwd, self.embed_bwd = head_bwd, embed_bwd
        self.loss_sum, self.logits = loss_sum, logits

    def hidden(self, params, tok, keep=False):
        """Through the blocks.  ``keep``: also every layer's input."""
        x = self.embed(params["wte"], params["wpe"], tok)
        xs = []
        for l in range(self.s.L):
            if keep:
                xs.append(x)
            x = self.layer_fwd(params["blocks"], np.int32(l), x)
        return (x, xs) if keep else x

    def forward_logits(self, params, tok):
        """``[rows, positions, padded vocabulary]`` float32 logits."""
        x = self.hidden(params, tok)
        return self.logits(params["wte"], params["lnf_g"], params["lnf_b"],
                           x)

    def loss(self, params, tokens, labels, rows):
        """Mean cross entropy of the batch, forward only."""
        count = int(np.sum(labels != IGNORE))
        total = 0.0
        for r in range(0, tokens.shape[0], rows):
            x = self.hidden(params, tokens[r:r + rows])
            total += float(self.loss_sum(
                params["wte"], params["lnf_g"], params["lnf_b"], x,
                labels[r:r + rows]))
        return total / count

    def loss_and_grads(self, params, tokens, labels, rows):
        """Mean cross entropy of the batch and its float32 gradients, as a
        tree of the parameters' shape."""
        count = int(np.sum(labels != IGNORE))
        weight = jnp.asarray(1.0 / count, _F32)
        gblocks = _zeros_f32(params["blocks"])
        gaux = _zeros_f32({k: params[k]
                           for k in ("wte", "wpe", "lnf_g", "lnf_b")})
        total = 0.0
        for r in range(0, tokens.shape[0], rows):
            tok, lab = tokens[r:r + rows], labels[r:r + rows]
            x, xs = self.hidden(params, tok, keep=True)
            gaux, loss, dx = self.head_bwd(
                gaux, params["wte"], params["lnf_g"], params["lnf_b"], x,
                lab, weight)
            total += float(loss)
            for l in reversed(range(self.s.L)):
                gblocks, dx = self.layer_bwd(
                    gblocks, params["blocks"], np.int32(l), xs.pop(), dx)
            gaux = self.embed_bwd(gaux, tok, dx)
        return total / count, dict(gaux, blocks=gblocks)


# ------------------------------------------------- following a training run


_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def parts(s, name, a):
    """The leaves as they are compared: a fused ``qkv`` leaf is three, since
    the key's bias has no gradient under softmax and must be judged (and
    left out) alone."""
    if name.endswith(("qkv_w", "qkv_b")):
        r = a.reshape(a.shape[:-1] + (s.H, 3, s.hd))
        return {f"{name}.{p}": r[..., i, :] for i, p in enumerate("qkv")}
    return {name: a}


SAMPLE_PER_AXIS = 64


def sample(a):
    """An evenly strided sample of a leaf, at most ``SAMPLE_PER_AXIS``
    indices along each axis, as a flat float32 vector.  Traceable."""
    idx = tuple(slice(0, (n // min(n, SAMPLE_PER_AXIS))
                      * min(n, SAMPLE_PER_AXIS),
                      n // min(n, SAMPLE_PER_AXIS)) for n in a.shape)
    return a[idx].astype(_F32).reshape(-1)


def sq_parts(s, flat, minus=None):
    """name of part -> sum of squares (of ``flat - minus`` where given), in
    float32.  Traceable; ``flat`` maps leaf names to parameter-shaped
    arrays."""
    out = {}
    for name, a in flat.items():
        a = a.astype(_F32)
        if minus is not None:
            a = a - minus[name].astype(_F32)
        for part, pa in parts(s, name, a).items():
            out[part] = jnp.sum(jnp.square(pa))
    return out


@partial(jax.jit, static_argnames=("decay", "b1", "b2", "eps", "p_dtype",
                                   "o_dtype"), donate_argnums=(0, 1, 2, 3))
def _adamw(w, m, v, g, lr, bc1, bc2, scale, *, decay, b1, b2, eps, p_dtype,
           o_dtype):
    """One leaf.  ``w`` is the stored weight (the master copy where the
    recipe keeps one, else the parameter itself); arithmetic in float32,
    results rounded to what the recipe stores."""
    g = g * scale
    m = b1 * m.astype(_F32) + (1 - b1) * g
    v = b2 * v.astype(_F32) + (1 - b2) * g * g
    wf = w.astype(_F32)
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + decay * wf
    wf = wf - lr * upd
    return (wf.astype(o_dtype), m.astype(o_dtype), v.astype(o_dtype),
            wf.astype(p_dtype))


def follow_training(config, recipe, seed, batches, numerics="float32",
                    rows=1, steps=2, half_batch=False, frozen=False):
    """What a sound trainer produces from this seed: the loss of each of
    the first ``steps + 1`` batches, the norm of each leaf of the first
    gradient as the optimizer gets it (after the global-norm clip), and the
    norm of each leaf's change over the first ``steps`` updates.

    ``recipe``: the optimizer's settings and what is stored in which type
    (``param_dtype``, ``opt_dtype``; a float32 master copy exists where the
    two differ).  ``half_batch`` plants a fault: the second half of every
    batch is left out and the mean taken over the rest.  ``frozen`` plants
    another: every step returns its state unchanged."""
    model = Model(config, numerics)
    table = leaf_table(model.s)
    p_dtype = _DTYPES[recipe["param_dtype"]]
    o_dtype = _DTYPES[recipe["opt_dtype"]]
    init = lambda: weights(config, seed, p_dtype)
    params = init()
    has_master = o_dtype != p_dtype
    store = (jax.tree_util.tree_map(lambda a: a.astype(o_dtype), params)
             if has_master else None)
    moments = None
    sq_fn = jax.jit(partial(sq_parts, model.s))
    sample_fn = jax.jit(lambda flat: {n: sample(a) for n, a in flat.items()})
    b1, b2 = recipe["beta1"], recipe["beta2"]
    out = {"losses": [], "grad1": {}, "change": {}}
    for k, (tokens, labels) in enumerate(batches[: steps + 1]):
        if half_batch:
            tokens = tokens[: tokens.shape[0] // 2]
            labels = labels[: labels.shape[0] // 2]
        if k == steps:
            out["losses"].append(model.loss(params, tokens, labels, rows))
            break
        loss, grads = model.loss_and_grads(params, tokens, labels, rows)
        out["losses"].append(loss)
        g = flatten(grads)
        del grads
        sq = {n: float(v) for n, v in sq_fn(g).items()}
        gnorm = math.sqrt(sum(sq.values()))
        clip = recipe["grad_clip"]
        scale = min(1.0, clip / max(gnorm, 1e-12)) if clip else 1.0
        if k == 0:
            out["grad1"] = {n: math.sqrt(v) * scale for n, v in sq.items()}
            out["grad1_global_norm"] = gnorm
            out["grad1_sample"] = {n: np.asarray(v) * scale
                                   for n, v in sample_fn(g).items()}
        if frozen:
            del g
            continue
        p, w = flatten(params), flatten(store) if has_master else None
        mo = flatten(moments) if moments is not None else None
        t = k + 1
        new_p, new_w, new_m, new_v = {}, {}, {}, {}
        for name in list(g):
            w_in = w.pop(name) if has_master else p.pop(name)
            if mo is None:
                m_in = jnp.zeros(w_in.shape, o_dtype)
                v_in = jnp.zeros(w_in.shape, o_dtype)
            else:
                m_in, v_in = mo["m/" + name], mo["v/" + name]
                del mo["m/" + name], mo["v/" + name]
            w_out, new_m[name], new_v[name], new_p[name] = _adamw(
                w_in, m_in, v_in, g.pop(name),
                jnp.asarray(recipe["lr"], _F32),
                jnp.asarray(1 - b1 ** t, _F32),
                jnp.asarray(1 - b2 ** t, _F32), jnp.asarray(scale, _F32),
                decay=recipe["weight_decay"] if table[name][2] else 0.0,
                b1=b1, b2=b2, eps=recipe["eps"], p_dtype=p_dtype,
                o_dtype=o_dtype)
            if has_master:
                new_w[name] = w_out
            del w_in, m_in, v_in, w_out
        params = nest(new_p)
        store = nest(new_w) if has_master else None
        moments = {"m": nest(new_m), "v": nest(new_v)}
        del new_p, new_w, new_m, new_v, p, w, mo
    del moments, store
    sq = jax.jit(partial(sq_parts, model.s))(flatten(params),
                                            flatten(init()))
    out["change"] = {n: math.sqrt(float(v)) for n, v in sq.items()}
    return out


def sampled_difference(prog, ref):
    """The largest, over the leaves, of the root mean square of the
    difference between the program's sampled first gradient and the
    reference's, against the reference's root mean square on that leaf or
    on the median leaf, whichever is larger.  Unlike a gap of norms this
    does not average rounding out: it is the number a lower precision
    moves.  Returns (difference, leaf)."""
    rms = lambda v: float(np.sqrt(np.mean(np.square(v, dtype=np.float64))))
    median = float(np.median([rms(v) for v in ref.values()]))
    worst, at = 0.0, None
    for n, r in ref.items():
        d = rms(np.asarray(prog[n], np.float64) - r) / max(rms(r), median,
                                                          1e-30)
        if d > worst or at is None:
            worst, at = d, n
    return worst, at


# --------------------------------------------------------- the comparison


@jax.jit
def gap_below_best(logits, chosen):
    """At each position of ``logits [positions, vocabulary]``: how far the
    logit of ``chosen[position]`` lies below the largest.  On the device:
    the logits of a long row are 200 MB."""
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - picked


@jax.jit
def first_token(logits):
    return jnp.argmax(logits, axis=-1)


def worst_leaf_gap(prog, ref, leave_out=()):
    """The largest, over the leaves, of the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger.  Returns (gap, leaf)."""
    median = float(np.median([ref[n] for n in ref]))
    worst, at = 0.0, None
    for n in ref:
        if n in leave_out:
            continue
        gap = abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
        if gap > worst or at is None:
            worst, at = gap, n
    return worst, at


def still_leaves(ref_grad1):
    """Leaves whose first gradient is nought to rounding in the reference
    (under a thousandth of the median leaf's): Adam moves them by round-off
    alone, so their change is not compared."""
    median = float(np.median(list(ref_grad1.values())))
    return {n for n, v in ref_grad1.items() if v < 1e-3 * median}


def training_numbers(prog, ref):
    """name -> (number compared, detail) for a followed training run."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss{i}"] = (abs(lp - lr) / abs(lr), f"{lp:.6f} vs {lr:.6f}")
    gap, at = worst_leaf_gap(prog["grad1"], ref["grad1"])
    out["grad1_norm"] = (gap, at)
    out["grad1_sample"] = sampled_difference(prog["grad1_sample"],
                                             ref["grad1_sample"])
    gap, at = worst_leaf_gap(prog["change"], ref["change"],
                             leave_out=still_leaves(ref["grad1"]))
    out["change_norm"] = (gap, at)
    return out

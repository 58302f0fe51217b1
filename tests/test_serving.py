"""Serving subsystem tests: paged KV cache, paged-attention decode,
continuous batching, sampling determinism — plus regression tests for
the roi_align edge-semantics and Conll05 parse-guard fixes that rode in
the same PR."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.paged_attention import (_KEY_TILE, _SMALL_Q,
                                                _ragged_attention_kernel,
                                                _ragged_attention_ref,
                                                ragged_paged_attention,
                                                ragged_work_items)
from paddle_tpu.models.gpt import (GPT_CONFIGS, _layer_norm, gpt_forward,
                                   gpt_init, gpt_ragged_step)
from paddle_tpu.models.ragged import RaggedBatch
from paddle_tpu.serving import (Engine, PagedKVCache, RequestState,
                                SamplingParams)


def _tiny_cfg():
    # fp32 everywhere: the greedy-parity tests compare argmax across two
    # computation orders, so bf16 rounding noise is not welcome
    return dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = gpt_init(cfg, jax.random.key(0), dtype=jnp.float32)
    return cfg, params


# one stable jitted forward per config: an EAGER gpt_forward builds a
# fresh scan closure (fresh jaxpr) per call, so every oracle step would
# compile a brand-new executable — churning jax's bounded eager cache
# and the process mmap budget across a long suite.  With a stable jit
# identity each [1, L] compiles exactly once per process.
_ORACLE_FWD = {}


def _oracle_forward(cfg):
    fn = _ORACLE_FWD.get(id(cfg))
    if fn is None:
        fn = _ORACLE_FWD.setdefault(
            id(cfg), jax.jit(lambda p, t: gpt_forward(cfg, p, t)))
    return fn


def naive_generate(cfg, params, prompt, n_new):
    """Full-recompute greedy decoding — the correctness oracle."""
    fwd = _oracle_forward(cfg)
    toks = list(prompt)
    for _ in range(n_new):
        logits = fwd(params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# ------------------------------------------------------------- page pool


class TestPagedKVCache:
    def _cache(self, num_pages=8, page_size=4):
        return PagedKVCache(num_layers=2, num_heads=2, head_dim=4,
                            num_pages=num_pages, page_size=page_size,
                            max_seq_len=32)

    def test_alloc_free_reuse(self):
        c = self._cache()
        assert c.allocate("a", 9)            # 3 pages
        assert c.num_used_pages == 3
        table = c.page_table("a")
        assert len(table) == c.max_pages_per_seq
        assert len(set(table[:3])) == 3
        c.free("a")
        assert c.num_free_pages == 8
        # freed pages are reusable immediately
        assert c.allocate("b", 32)           # all 8 pages
        assert c.num_free_pages == 0
        c.free("b")

    def test_exhaustion_returns_false_without_partial_alloc(self):
        c = self._cache()
        assert c.allocate("a", 20)           # 5 of 8 pages
        free_before = c.num_free_pages
        assert not c.allocate("b", 16)       # needs 4, only 3 left
        assert c.num_free_pages == free_before   # nothing leaked
        assert c.extend("a", 32)             # grow to all 8
        assert not c.extend("a", 33) if c.max_pages_per_seq > 8 else True

    def test_occupancy_and_extend(self):
        c = self._cache()
        c.allocate("a", 4)
        assert c.occupancy() == pytest.approx(1 / 8)
        assert c.extend("a", 5)              # second page
        assert c.occupancy() == pytest.approx(2 / 8)
        assert c.extend("a", 5)              # idempotent: already covered
        assert c.occupancy() == pytest.approx(2 / 8)

    def test_defrag_compacts_and_preserves_contents(self):
        c = self._cache()
        c.allocate("a", 8)
        c.allocate("b", 8)
        c.allocate("c", 8)
        # stamp each sequence's pages with a recognizable value
        for sid, val in (("a", 1.0), ("b", 2.0), ("c", 3.0)):
            for p in c.page_table(sid)[:2]:
                c.k_pages = c.k_pages.at[:, p].set(val)
        c.free("b")                          # hole in the middle
        before = {sid: np.asarray(c.k_pages[0, c.page_table(sid)[:2]])
                  for sid in ("a", "c")}
        moved = c.defrag()
        assert moved > 0
        # live pages now occupy the low-index prefix
        live = sorted(p for sid in ("a", "c") for p in c.page_table(sid)[:2])
        assert live == list(range(4))
        for sid in ("a", "c"):
            after = np.asarray(c.k_pages[0, c.page_table(sid)[:2]])
            np.testing.assert_array_equal(before[sid], after)
        assert c.defrag() == 0               # already compact


# ----------------------------------------------------- paged attention


def paged_attention(q, k_pages, v_pages, page_tables, seq_lens, scale=None,
                    path=None):
    """Decode attention, ``q [B, H, hd]``: one query slot a row (0 keys:
    an idle row) through the one entry."""
    return ragged_paged_attention(
        q[:, None], k_pages, v_pages, page_tables,
        (seq_lens > 0).astype(jnp.int32), seq_lens, scale, path)[:, 0]


class TestPagedAttention:
    def _case(self, dtype=jnp.float32):
        B, H, hd, P, ps, M = 3, 4, 16, 12, 4, 4
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (B, H, hd), dtype)
        kp = jax.random.normal(ks[1], (P, ps, H, hd), dtype)
        vp = jax.random.normal(ks[2], (P, ps, H, hd), dtype)
        tables = jnp.asarray([[3, 1, 7, 2], [5, 8, 0, 0], [9, 0, 0, 0]],
                             jnp.int32)
        lens = jnp.asarray([14, 6, 0], jnp.int32)   # ragged + inactive
        return q, kp, vp, tables, lens

    def test_ref_matches_full_attention(self):
        """The paged gather+mask must equal dense softmax attention over
        each sequence's first seq_len tokens."""
        q, kp, vp, tables, lens = self._case()
        out = paged_attention(q, kp, vp, tables, lens,
                              path=dispatch.REFERENCE)
        ps = kp.shape[1]
        for b in range(q.shape[0]):
            n = int(lens[b])
            if n == 0:
                np.testing.assert_array_equal(np.asarray(out[b]), 0.0)
                continue
            k = jnp.concatenate([kp[p] for p in np.asarray(tables[b])],
                                axis=0)[:n]          # [n, H, hd]
            v = jnp.concatenate([vp[p] for p in np.asarray(tables[b])],
                                axis=0)[:n]
            s = jnp.einsum("hd,thd->ht", q[b].astype(jnp.float32),
                           k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
            p_ = jax.nn.softmax(s, axis=-1)
            ref = jnp.einsum("ht,thd->hd", p_, v.astype(jnp.float32))
            np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)

    def test_kernel_matches_ref_interpret(self):
        q, kp, vp, tables, lens = self._case()
        ref = paged_attention(q, kp, vp, tables, lens,
                              path=dispatch.REFERENCE)
        ker = paged_attention(q, kp, vp, tables, lens,
                              path=dispatch.INTERPRET)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_public_entry_runs(self):
        q, kp, vp, tables, lens = self._case()
        out = paged_attention(q, kp, vp, tables, lens)
        assert out.shape == q.shape and out.dtype == q.dtype


# ------------------------------------------ ragged (fused prefill+decode)


class TestRaggedAttention:
    """The unified kernel: every batch row at an arbitrary position —
    mid-prefill chunk, decode step, or idle."""

    def _case(self, qlens, ctxs, Q=6, dtype=jnp.float32, P=12, M=6, ps=4):
        """Every row's table is its own draw of ``M`` of the ``P`` pages,
        scattered and out of order."""
        B = len(qlens)
        H, hd = 2, 8
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (B, Q, H, hd), dtype)
        kp = jax.random.normal(ks[1], (P, ps, H, hd), dtype)
        vp = jax.random.normal(ks[2], (P, ps, H, hd), dtype)
        rng = np.random.RandomState(0)
        tables = jnp.asarray(
            np.stack([rng.permutation(P)[:M] for _ in range(B)]), jnp.int32)
        return (q, kp, vp, tables, jnp.asarray(qlens, jnp.int32),
                jnp.asarray(ctxs, jnp.int32))

    # the kernel's key tile in pages of 16, and tables wide enough for
    # contexts of three tiles: the walk below is over real item lists
    T = -(-_KEY_TILE // 16) * 16
    S = _SMALL_Q
    WIDE = dict(Q=S + 4, ps=16, P=3 * T // 16 + 8, M=3 * T // 16)

    def test_ref_matches_dense_causal_oracle(self):
        """Each query token must equal dense softmax attention over the
        kv prefix ending at its own absolute position (causal within
        the chunk, full context before it)."""
        # context lengths straddle the page_size=4 boundary: 7, 8, 9
        q, kp, vp, tables, qlens, ctxs = self._case([5, 1, 3, 0],
                                                    [7, 8, 9, 0])
        scale = 1.0 / np.sqrt(q.shape[-1])
        out = _ragged_attention_ref(q, kp, vp, tables, qlens, ctxs, scale)
        for b in range(q.shape[0]):
            ql, cl = int(qlens[b]), int(ctxs[b])
            k = jnp.concatenate([kp[p] for p in np.asarray(tables[b])], 0)
            v = jnp.concatenate([vp[p] for p in np.asarray(tables[b])], 0)
            for t in range(q.shape[1]):
                if t >= ql:
                    np.testing.assert_array_equal(np.asarray(out[b, t]),
                                                  0.0)
                    continue
                n = cl - ql + t + 1          # causal horizon of token t
                s = jnp.einsum("hd,thd->ht", q[b, t], k[:n]) * scale
                ref = jnp.einsum("ht,thd->hd", jax.nn.softmax(s, -1), v[:n])
                np.testing.assert_allclose(np.asarray(out[b, t]),
                                           np.asarray(ref),
                                           rtol=1e-5, atol=1e-5)

    WALK = [
        # the three mixes the kernel has always been held to (one tile)
        ("mixed", (5, 1, 3, 0), (14, 6, 3, 0), {}),
        ("page_edges", (6, 6, 1, 1), (7, 8, 9, 24), {}),
        ("decode", (1, 1, 1, 1), (4, 5, 16, 17), {}),
        # contexts at tile - 1, tile, tile + 1 and several tiles
        ("tile_edges", (1, 1, 1, 1), (T - 1, T, T + 1, 3 * T), WIDE),
        ("chunk_across_tiles", (S + 4, S + 4, 3, 1),
         (T + 2, 2 * T + 1, T, 1), WIDE),
        # query_len at, under and over the narrow body's width in one call
        ("slot_widths", (S, S - 1, S + 1, 1), (T + S, S - 1, 2 * T, 2 * T),
         WIDE),
        # idle rows first, last and in the middle: a row's run of items
        # starts and ends on its own
        ("idle_first", (0, 0, 1, S + 2), (0, 0, 2 * T + 3, T + 1), WIDE),
        ("idle_last", (S + 2, 1, 0, 0), (2 * T, T - 1, 0, 0), WIDE),
        ("idle_middle", (1, 0, 0, S + 1), (T + 1, 0, 0, 3 * T), WIDE),
        ("all_idle", (0, 0, 0, 0), (0, 0, 0, 0), WIDE),
    ]

    # every mix has 4 rows, so the interpreted kernel compiles once per
    # table width and dtype, not once per case (a compile is seconds)
    _kernel = staticmethod(jax.jit(
        lambda q, kp, vp, tables, ql, cl, items=None: ragged_paged_attention(
            q, kp, vp, tables, ql, cl, path=dispatch.INTERPRET, items=items)))

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("name,qlens,ctxs,kw", WALK,
                             ids=[w[0] for w in WALK])
    def test_kernel_matches_ref_mixed_rows(self, name, qlens, ctxs, kw,
                                           dtype, tol):
        """Interpret-mode kernel == ref for batches mixing mid-prefill
        chunks, prompt-completing chunks, decode rows and idle rows, with
        contexts straddling page and tile boundaries, over page tables
        whose pages are scattered and out of order.  float32 within 2e-5;
        bf16 within 2e-2 (the outputs are O(1) and rounded to 8 bits, and
        the kernel rounds the probabilities to bf16 for the second
        product where the reference keeps float32)."""
        q, kp, vp, tables, ql, cl = self._case(list(qlens), list(ctxs),
                                               dtype=dtype, **kw)
        ref = _ragged_attention_ref(q, kp, vp, tables, ql, cl,
                                    1.0 / np.sqrt(q.shape[-1]))
        ker = self._kernel(q, kp, vp, tables, ql, cl)
        assert ker.dtype == dtype
        np.testing.assert_allclose(np.asarray(ker, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("name,qlens,ctxs,kw", WALK,
                             ids=[w[0] for w in WALK])
    def test_work_items_cover_each_live_row_once(self, name, qlens, ctxs,
                                                 kw):
        """The list: one item per tile a live row's context reaches, rows
        in order and a row's tiles ascending, nothing for an idle row; and
        the list handed in is the list the kernel builds itself."""
        q, kp, vp, tables, ql, cl = self._case(list(qlens), list(ctxs),
                                               **kw)
        ps, M = kp.shape[1], tables.shape[1]
        rows, tiles, n = ragged_work_items(ql, cl, ps, M)
        tile = min(M, -(-_KEY_TILE // ps)) * ps
        want = [(b, t) for b, (ql_b, cl_b) in enumerate(zip(qlens, ctxs))
                if ql_b for t in range(max(1, -(-cl_b // tile)))]
        assert int(n[0]) == len(want)
        assert rows.shape == tiles.shape == (len(qlens) * -(-M * ps // tile),)
        got = list(zip(np.asarray(rows)[:len(want)].tolist(),
                       np.asarray(tiles)[:len(want)].tolist()))
        assert got == want
        inside = self._kernel(q, kp, vp, tables, ql, cl)
        handed = self._kernel(q, kp, vp, tables, ql, cl, (rows, tiles, n))
        np.testing.assert_array_equal(np.asarray(inside),
                                      np.asarray(handed))

    def test_decode_entry_is_qlen1_degenerate_row(self):
        """A decode call through the public entry equals the kernel on
        Q=1 rows."""
        q, kp, vp, tables, _, _ = self._case([1, 1, 1], [9, 4, 0], Q=1)
        lens = jnp.asarray([9, 4, 0], jnp.int32)
        scale = 1.0 / np.sqrt(q.shape[-1])
        dec = paged_attention(q[:, 0], kp, vp, tables, lens, scale,
                              path=dispatch.INTERPRET)
        rag = _ragged_attention_kernel(q, kp, vp, tables,
                                       (lens > 0).astype(jnp.int32), lens,
                                       scale, interpret=True)[:, 0]
        np.testing.assert_allclose(np.asarray(dec), np.asarray(rag),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
    @pytest.mark.parametrize("qlens,ctxs", [((5, 1, 3, 0), (14, 6, 3, 0)),
                                            ((6, 6, 1, 1), (7, 8, 9, 24)),
                                            ((1, 1, 1, 1), (4, 5, 16, 17))])
    def test_stacked_pool_layer_equals_one_layer_call(self, path, qlens,
                                                      ctxs):
        """A stacked [L, P, ps, H, hd] pool read at ``layer`` (the serving
        step's call: the pool is never sliced) is bit for bit the
        one-layer call on ``pool[layer]``, and the kernel still matches
        the reference there."""
        q, kp, vp, tables, ql, cl = self._case(list(qlens), list(ctxs))
        ks = jax.random.split(jax.random.key(3), 2)
        kp5 = jnp.stack([kp, *jax.random.normal(ks[0], (2, *kp.shape))])
        vp5 = jnp.stack([vp, *jax.random.normal(ks[1], (2, *vp.shape))])
        stacked = jax.jit(lambda l: ragged_paged_attention(
            q, kp5, vp5, tables, ql, cl, path=path, layer=l))
        outs = []
        for l in range(3):
            one = ragged_paged_attention(q, kp5[l], vp5[l], tables, ql, cl,
                                         path=path)
            outs.append(np.asarray(stacked(jnp.int32(l))))
            np.testing.assert_array_equal(outs[-1], np.asarray(one))
            np.testing.assert_allclose(
                outs[-1], np.asarray(_ragged_attention_ref(
                    q, kp5[l], vp5[l], tables, ql, cl,
                    1.0 / np.sqrt(q.shape[-1]))), rtol=2e-5, atol=2e-5)
        assert not np.array_equal(outs[0], outs[1])  # the layer is read

    def test_pool_and_layer_come_together(self):
        q, kp, vp, tables, ql, cl = self._case([1, 1], [4, 5])
        with pytest.raises(ValueError, match="layer"):
            ragged_paged_attention(q, kp[None], vp[None], tables, ql, cl)
        with pytest.raises(ValueError, match="layer"):
            ragged_paged_attention(q, kp, vp, tables, ql, cl, layer=0)


# ------------------------------------------ the step against its oracle


def _ragged_step_oracle(cfg, params, batch, k_pages, v_pages, max_q, path):
    """``gpt_ragged_step`` (dense branch) written the plain way, the
    batch's arithmetic included (nothing of ``models/ragged.py`` but the
    tuple): a Python loop over layers, each writing its tokens into its
    own [P, ps, H, hd] pool and attending on it through the public
    one-layer API, the pools restacked at the end."""
    tokens, rows, slots, query_lens, context_lens, page_tables = batch
    T, B = tokens.shape[0], query_lens.shape[0]
    H, hd, D = cfg.num_heads, cfg.head_dim, cfg.hidden
    P, page_size = k_pages.shape[1], k_pages.shape[2]
    row_c = jnp.minimum(rows, B - 1)
    valid = (rows < B) & (slots < query_lens[row_c])
    pos = jnp.clip((context_lens - query_lens)[row_c] + slots, 0,
                   cfg.max_seq_len - 1)
    x = (params["wte"][tokens] + params["wpe"][pos]).astype(cfg.jdtype())
    page = jnp.where(valid, page_tables[row_c, pos // page_size], P)
    slot = pos % page_size
    pad_row = jnp.where(valid, row_c, B)
    pad_slot = jnp.minimum(slots, max_q - 1)
    k_out, v_out = [], []
    for l in range(cfg.num_layers):
        bp = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"])
        qkv = (jnp.einsum("td,de->te", h, bp["qkv_w"])
               + bp["qkv_b"]).reshape(T, H, 3, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        kp = k_pages[l].at[page, slot].set(k, mode="drop")
        vp = v_pages[l].at[page, slot].set(v, mode="drop")
        k_out.append(kp)
        v_out.append(vp)
        q_pad = jnp.zeros((B, max_q, H, hd), q.dtype) \
            .at[pad_row, pad_slot].set(q, mode="drop")
        attn = ragged_paged_attention(q_pad, kp, vp, page_tables, query_lens,
                                      context_lens, path=path)
        attn = attn[row_c, pad_slot].reshape(T, D)
        x = x + jnp.einsum("td,de->te", attn, bp["proj_w"]) + bp["proj_b"]
        h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"])
        h = jnp.einsum("td,df->tf", h, bp["up_w"]) + bp["up_b"]
        h = jax.nn.gelu(h, approximate=True)
        x = x + jnp.einsum("tf,fd->td", h, bp["down_w"]) + bp["down_b"]
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    last = jnp.clip(jnp.cumsum(query_lens) - 1, 0, T - 1)
    logits = jnp.einsum("bd,vd->bv", x[last], params["wte"])
    return logits, jnp.stack(k_out), jnp.stack(v_out)


@pytest.mark.parametrize("path", [dispatch.REFERENCE, dispatch.INTERPRET])
def test_ragged_step_equals_per_layer_oracle(tiny_model, path):
    """The step that carries the stacked pools through its layer scan
    (scatter into [layer, page, slot], the kernel on [layer, page]
    blocks) returns logits and both pools bit for bit equal to the
    per-layer oracle.  Rows: a mid-prompt chunk, a decode row, an idle
    row, and a chunk that crosses a page boundary."""
    cfg, params = tiny_model
    assert cfg.tie_embeddings and not cfg.moe_experts
    B, P, ps, M, Q, T = 4, 16, 4, 4, 6, 14
    qlens = np.array([5, 1, 0, 6], np.int32)
    ctxs = np.array([9, 7, 0, 10], np.int32)   # row 3: positions 4..9
    rng = np.random.RandomState(1)
    tables = rng.permutation(P).reshape(B, M).astype(np.int32)
    tokens = np.zeros(T, np.int32)
    rows = np.full(T, B, np.int32)             # == B marks a padding slot
    slots = np.zeros(T, np.int32)
    n = int(qlens.sum())
    tokens[:n] = rng.randint(0, cfg.vocab_size, n)
    rows[:n] = np.repeat(np.arange(B), qlens)
    slots[:n] = np.concatenate([np.arange(q) for q in qlens])
    pool = (cfg.num_layers, P, ps, cfg.num_heads, cfg.head_dim)
    ks = jax.random.split(jax.random.key(5), 2)
    k_pages = jax.random.normal(ks[0], pool, jnp.float32)
    v_pages = jax.random.normal(ks[1], pool, jnp.float32)
    args = [RaggedBatch(*(jnp.asarray(a) for a in (
        tokens, rows, slots, qlens, ctxs, tables))), k_pages, v_pages]

    got = jax.jit(lambda p, *a: gpt_ragged_step(
        cfg, p, *a, max_q=Q, attn_path=path))(params, *args)
    want = jax.jit(lambda p, *a: _ragged_step_oracle(
        cfg, p, *a, max_q=Q, path=path))(params, *args)
    live = qlens > 0                # an idle row's logits are garbage
    np.testing.assert_array_equal(np.asarray(got[0])[live],
                                  np.asarray(want[0])[live])
    for new, ref, old in zip(got[1:], want[1:], (k_pages, v_pages)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(ref))
        # every layer wrote its 12 tokens and nothing else
        changed = np.any(np.asarray(new) != np.asarray(old), axis=(3, 4))
        assert changed.sum(axis=(1, 2)).tolist() == [n] * cfg.num_layers


# ------------------------------------------------- continuous batching


class TestEngine:
    def test_greedy_matches_full_recompute_ragged(self, tiny_model):
        """Acceptance: ragged batch of 4 prompts, token-identical to the
        full-recompute oracle, with max_batch_size 2 forcing two of the
        requests to be admitted only after decoding has started."""
        cfg, params = tiny_model
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, cfg.vocab_size, n))
                   for n in (5, 11, 3, 17)]
        refs = [naive_generate(cfg, params, p, 8) for p in prompts]
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32)
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=8))
        assert outs == refs
        m = eng.metrics.snapshot()
        assert m["requests"]["finished"] == 4
        assert m["tokens"]["generated"] == 32
        assert eng.cache.num_free_pages == eng.cache.num_pages  # all freed

    def test_late_request_admitted_mid_decode(self, tiny_model):
        """Explicit continuous-batching check: a request submitted after
        several decode steps joins the in-flight batch, and neither it
        nor the already-running sequences diverge from their
        single-request outputs."""
        cfg, params = tiny_model
        rng = np.random.RandomState(7)
        early = [list(rng.randint(0, cfg.vocab_size, n)) for n in (6, 9)]
        late = list(rng.randint(0, cfg.vocab_size, 4))
        sp = SamplingParams(max_new_tokens=10)
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=4, chunk_len=32)
        reqs = [eng.add_request(p, sp) for p in early]
        for _ in range(4):
            eng.step()                        # decoding well underway
        # a step's tokens are visible one call later: 4 dispatched, 3 read
        assert all(len(r.output) == 3 and r._pending == 1 for r in reqs)
        late_req = eng.add_request(late, sp)
        while eng.has_work():
            eng.step()
        # the late request was admitted while others were mid-decode and
        # still matches its solo greedy output; so do the early ones
        assert late_req.output == naive_generate(cfg, params, late, 10)
        for r, p in zip(reqs, early):
            assert r.output == naive_generate(cfg, params, p, 10)

    def test_pool_exhaustion_rejects_gracefully(self, tiny_model):
        cfg, params = tiny_model
        eng = Engine(cfg, params, page_size=8, num_pages=4,
                     max_batch_size=2, chunk_len=32)   # 32-token pool
        r = eng.add_request(list(range(20)),
                            SamplingParams(max_new_tokens=20))
        assert r.state == RequestState.REJECTED
        assert "page pool exhausted" in r.finish_reason
        assert eng.metrics.requests_rejected.value == 1
        # a feasible request still runs fine afterwards
        out = eng.generate([list(range(8))],
                           SamplingParams(max_new_tokens=4))
        assert len(out[0]) == 4

    def test_preemption_recompute_is_lossless(self, tiny_model):
        """Two sequences that overflow the pool mid-decode: the youngest
        is preempted back to the queue, recomputed later, and its final
        output equals its uninterrupted solo run."""
        cfg, params = tiny_model
        rng = np.random.RandomState(3)
        p1 = list(rng.randint(0, cfg.vocab_size, 14))
        p2 = list(rng.randint(0, cfg.vocab_size, 14))
        eng = Engine(cfg, params, page_size=8, num_pages=6,
                     max_batch_size=2, chunk_len=32)
        sp = SamplingParams(max_new_tokens=20)
        outs = eng.generate([p1, p2], sp)
        assert eng.metrics.requests_preempted.value > 0
        assert outs[0] == naive_generate(cfg, params, p1, 20)
        assert outs[1] == naive_generate(cfg, params, p2, 20)

    def test_sampling_deterministic_under_fixed_seed(self, tiny_model):
        cfg, params = tiny_model
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (6, 12)]
        sp = SamplingParams(max_new_tokens=10, temperature=0.8, top_k=40,
                            top_p=0.9, seed=1234)
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32)
        a = eng.generate(prompts, sp)
        b = eng.generate(prompts, sp)
        assert a == b
        # a different seed diverges (vocab 1024, 10 steps: collision odds
        # are negligible)
        sp2 = dataclasses.replace(sp, seed=99)
        c = eng.generate(prompts, sp2)
        assert c != a

    def test_stop_token_ends_generation(self, tiny_model):
        cfg, params = tiny_model
        prompt = list(range(4))
        first = naive_generate(cfg, params, prompt, 1)[0]
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=1, chunk_len=32)
        req = eng.add_request(prompt, SamplingParams(
            max_new_tokens=10, stop_token_ids=(first,)))
        while eng.has_work():
            eng.step()
        assert req.output == [first]
        assert req.finish_reason == "stop"

    def test_generation_predictor_api(self, tiny_model):
        cfg, params = tiny_model
        from paddle_tpu.inference import Config, create_predictor

        config = Config().enable_generation(
            cfg, params, page_size=8, num_pages=64, max_batch_size=2,
            chunk_len=32)
        pred = create_predictor(config)
        prompt = list(range(6))
        out = pred.generate([prompt], SamplingParams(max_new_tokens=5))
        assert out[0] == naive_generate(cfg, params, prompt, 5)
        snap = pred.metrics()
        assert snap["requests"]["finished"] == 1
        assert snap["ttft_s"]["count"] == 1


# ------------------------------------------------------- chunked prefill


class TestChunkedPrefill:
    """The unified-step scheduler: prompts become N bounded chunks
    interleaved with decode rows instead of one batch-stalling pass."""

    def test_long_prompt_chunked_greedy_parity(self, tiny_model):
        """Prompts straddling page boundaries, chunked 4 tokens at a
        time (page_size 8 — chunks cross pages mid-way), stay
        token-identical to the full-recompute oracle."""
        cfg, params = tiny_model
        rng = np.random.RandomState(11)
        prompts = [list(rng.randint(0, cfg.vocab_size, n))
                   for n in (15, 16, 17, 3)]
        refs = [naive_generate(cfg, params, p, 6) for p in prompts]
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=4)
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        assert outs == refs
        m = eng.metrics.snapshot()
        assert m["tokens"]["prefill"] == sum(len(p) for p in prompts)
        # at least ceil(len / chunk_len) chunk rows per prompt (fair
        # sharing between concurrent prefills can split finer)
        assert m["tokens"]["prefill_chunks"] >= sum(
            -(-len(p) // 4) for p in prompts)
        assert eng.cache.num_free_pages == eng.cache.num_pages

    def test_prompt_longer_than_chunk_admitted(self, tiny_model):
        """No prompt-length ceiling below max_seq_len: any prompt that
        fits it is admitted and chunked."""
        cfg, params = tiny_model
        rng = np.random.RandomState(13)
        prompt = list(rng.randint(0, cfg.vocab_size, 100))
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=16)
        req = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        assert req.state == RequestState.QUEUED      # not rejected
        while eng.has_work():
            eng.step()
        assert req.state == RequestState.FINISHED
        assert req.output == naive_generate(cfg, params, prompt, 4)
        # infeasible-by-model-size is still rejected hard
        too_long = list(rng.randint(0, cfg.vocab_size, cfg.max_seq_len))
        rej = eng.add_request(too_long, SamplingParams(max_new_tokens=4))
        assert rej.state == RequestState.REJECTED

    def test_chunk_rows_interleave_with_decode_rows(self, tiny_model):
        """A long prompt arriving mid-decode prefills chunk-by-chunk in
        the same steps that keep decoding the running requests — and
        nobody's output diverges from its solo run."""
        cfg, params = tiny_model
        rng = np.random.RandomState(17)
        early = [list(rng.randint(0, cfg.vocab_size, n)) for n in (5, 7)]
        long_p = list(rng.randint(0, cfg.vocab_size, 24))
        sp = SamplingParams(max_new_tokens=10)
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=4, chunk_len=4)
        reqs = [eng.add_request(p, sp) for p in early]
        for _ in range(3):
            eng.step()
        assert all(len(r.output) >= 1 for r in reqs)
        before = [len(r.output) for r in reqs]
        late = eng.add_request(long_p, sp)
        eng.step()                            # late's first chunk runs...
        assert 0 < late.prompt_pos < len(long_p)
        after = [len(r.output) for r in reqs
                 if r.state == RequestState.RUNNING]
        # ...and every still-running early request still got its decode
        # token in that same step (no prefill stall)
        assert all(a > b for a, b in zip(after, before[:len(after)]))
        while eng.has_work():
            eng.step()
        assert late.output == naive_generate(cfg, params, long_p, 10)
        for r, p in zip(reqs, early):
            assert r.output == naive_generate(cfg, params, p, 10)

    def test_ttft_is_first_sampled_token(self, tiny_model):
        """serving_ttft_seconds must cover queueing + every chunk step:
        the first token exists only once the LAST chunk completed."""
        cfg, params = tiny_model

        class Clock:
            def __init__(self):
                self.t = 0.0

            def __call__(self):
                self.t += 1.0
                return self.t

        clk = Clock()
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=1, chunk_len=4, clock=clk)
        prompt = list(range(12))              # 3 chunks
        req = eng.add_request(prompt, SamplingParams(max_new_tokens=2))
        eng.step()
        assert req.prompt_pos == 4 and req.t_first_token is None
        assert eng.metrics.ttft.summary()["count"] == 0
        eng.step()
        assert req.prompt_pos == 8 and req.t_first_token is None
        eng.step()                            # completing chunk samples
        assert req.prompt_pos == 12
        # ... on the device: the host reads the token one call later
        assert req.t_first_token is None and req._pending == 1
        eng.step()
        assert req.t_first_token is not None
        assert len(req.output) == 1
        assert eng.metrics.ttft.summary()["count"] == 1
        assert eng.metrics.prefill_chunks.value == 3
        # tracer shows the chunked lifecycle, not a monolithic prefill
        while eng.has_work():
            eng.step()
        (tr,) = [t for t in eng.tracer.traces()
                 if t["name"] == f"request#{req.id}"]
        names = [s["name"] for s in tr["spans"]]
        assert {"chunk[0]", "chunk[1]", "chunk[2]", "decode[1]"} <= \
            set(names)
        assert "prefill" not in names

    def test_mid_prefill_deadline_eviction_frees_chunk_pages(self,
                                                             tiny_model):
        """Regression (this PR): a request evicted mid-prefill must
        return its already-written chunk pages to the pool."""
        cfg, params = tiny_model

        class ManualClock:
            def __init__(self):
                self.t = 0.0

            def advance(self, dt):
                self.t += dt

            def __call__(self):
                return self.t

        clk = ManualClock()
        eng = Engine(cfg, params, page_size=4, num_pages=32,
                     max_batch_size=2, chunk_len=4, clock=clk)
        req = eng.add_request(list(range(14)), SamplingParams(
            max_new_tokens=4, ttl_s=5.0))
        clk.advance(1.0)
        eng.step()                            # first chunk written
        assert req.state == RequestState.RUNNING
        assert 0 < req.prompt_pos < len(req.prompt)
        assert eng.cache.num_used_pages > 0
        clk.advance(10.0)                     # deadline passes mid-prefill
        done = eng.step()
        assert req in done
        assert req.state == RequestState.EVICTED
        assert req.finish_reason == "deadline"
        assert req.output == []               # never sampled
        assert eng.cache.num_free_pages == eng.cache.num_pages
        assert eng.metrics.deadline_evictions.value == 1

    def test_preemption_mid_prefill_is_lossless(self, tiny_model):
        """Memory pressure that preempts a request WHILE its prompt is
        still chunking must rewind chunk progress too: the recomputed
        request's greedy output equals its uninterrupted solo run."""
        cfg, params = tiny_model
        rng = np.random.RandomState(19)
        p_a = list(rng.randint(0, cfg.vocab_size, 8))
        p_b = list(rng.randint(0, cfg.vocab_size, 14))
        sp_a = SamplingParams(max_new_tokens=8)
        sp_b = SamplingParams(max_new_tokens=2)
        eng = Engine(cfg, params, page_size=4, num_pages=6,
                     max_batch_size=2, chunk_len=4)   # 24-token pool
        a = eng.add_request(p_a, sp_a)
        b = eng.add_request(p_b, sp_b)
        saw_mid_prefill_preemption = False
        while eng.has_work():
            pre = eng.metrics.requests_preempted.value
            mid = {r.id: 0 < r.prompt_pos < len(r.prompt)
                   for r in (a, b)}
            eng.step()
            if eng.metrics.requests_preempted.value > pre:
                # a preemption fired; was the rewound request mid-prefill?
                for r in (a, b):
                    if (r.state == RequestState.QUEUED and mid[r.id]
                            and r.prompt_pos == 0):
                        saw_mid_prefill_preemption = True
        assert eng.metrics.requests_preempted.value > 0
        assert saw_mid_prefill_preemption
        assert a.output == naive_generate(cfg, params, p_a, 8)
        assert b.output == naive_generate(cfg, params, p_b, 2)
        assert eng.cache.num_free_pages == eng.cache.num_pages

    def test_fair_chunk_budget_between_concurrent_prefills(self,
                                                           tiny_model):
        """A short prompt admitted while a long one is mid-prefill
        shares the chunk budget instead of starving behind it — its
        TTFT lands before the long prompt finishes prefilling."""
        cfg, params = tiny_model
        rng = np.random.RandomState(23)
        long_p = list(rng.randint(0, cfg.vocab_size, 60))
        short_p = list(rng.randint(0, cfg.vocab_size, 6))
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=8)
        sp = SamplingParams(max_new_tokens=4)
        long_r = eng.add_request(long_p, sp)
        eng.step()                            # long starts chunking
        assert 0 < long_r.prompt_pos < len(long_p)
        short_r = eng.add_request(short_p, sp)
        steps_to_short_ttft = 0
        while short_r.t_first_token is None and eng.has_work():
            eng.step()
            steps_to_short_ttft += 1
        assert short_r.t_first_token is not None
        assert long_r.prompt_pos < len(long_p)   # long still prefilling
        while eng.has_work():
            eng.step()
        assert short_r.output == naive_generate(cfg, params, short_p, 4)
        assert long_r.output == naive_generate(cfg, params, long_p, 4)


# -------------------------------------------- robustness under overload


class _ManualClock:
    """Deterministic engine clock: deadline tests advance time by hand
    instead of sleeping."""

    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


class TestDeadlineEviction:
    def test_running_request_evicted_mid_decode(self, tiny_model):
        cfg, params = tiny_model
        clk = _ManualClock()
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32, clock=clk)
        req = eng.add_request(list(range(6)), SamplingParams(
            max_new_tokens=50, ttl_s=5.0))
        for _ in range(4):
            clk.advance(1.0)
            eng.step()
        assert req.state == RequestState.RUNNING
        produced = len(req.output)
        assert produced == 3 and req._pending == 1
        clk.advance(10.0)                  # now past the deadline
        done = eng.step()
        assert req in done
        assert req.state == RequestState.EVICTED
        assert req.finish_reason == "deadline"
        # partial output preserved; the token that was in flight for it
        # when the deadline passed is dropped, not appended to a request
        # that has ended
        assert len(req.output) == produced
        assert eng.metrics.overrun_rows.value == 1
        assert not eng.has_work()
        # every page came back to the pool
        assert eng.cache.num_free_pages == eng.cache.num_pages
        assert eng.metrics.deadline_evictions.value == 1

    def test_queued_request_past_deadline_never_admitted(self, tiny_model):
        cfg, params = tiny_model
        clk = _ManualClock()
        # batch of 1: the second request waits in queue
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=1, chunk_len=32, clock=clk)
        sp_long = SamplingParams(max_new_tokens=30)
        sp_ttl = SamplingParams(max_new_tokens=4, ttl_s=2.0)
        eng.add_request(list(range(5)), sp_long)
        queued = eng.add_request(list(range(4)), sp_ttl)
        clk.advance(5.0)                   # queued request expires unseen
        eng.step()
        assert queued.state == RequestState.EVICTED
        assert queued.t_admitted is None   # evicted straight from queue
        assert queued.output == []

    def test_engine_default_ttl_applies(self, tiny_model):
        cfg, params = tiny_model
        clk = _ManualClock()
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32, clock=clk,
                     default_ttl_s=1.0)
        req = eng.add_request(list(range(4)),
                              SamplingParams(max_new_tokens=50))
        assert req.deadline == pytest.approx(1.0)
        clk.advance(2.0)
        eng.step()
        assert req.state == RequestState.EVICTED


class TestWatermarkShedding:
    def test_queue_depth_watermarks_with_hysteresis(self, tiny_model):
        cfg, params = tiny_model
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=1, chunk_len=32,
                     shed_queue_high=3, shed_queue_low=1)
        sp = SamplingParams(max_new_tokens=3)
        reqs = [eng.add_request(list(range(4)), sp) for _ in range(6)]
        states = [r.state for r in reqs]
        # first three queue; hitting the high mark flips to shedding
        assert states[:3] == [RequestState.QUEUED] * 3
        assert states[3:] == [RequestState.RETRY_AFTER] * 3
        shed = reqs[3]
        assert shed.state != RequestState.REJECTED   # soft, not hard
        assert "retry" in shed.finish_reason
        assert eng.metrics.requests_shed.value == 3
        assert eng.metrics.engine_healthy.value == 0   # degraded
        # drain below the LOW mark: health recovers, admission resumes
        while eng.has_work():
            eng.step()
        assert eng.metrics.engine_healthy.value == 1
        ok = eng.add_request(list(range(4)), sp)
        assert ok.state == RequestState.QUEUED
        # admitted requests were unharmed by the overload
        for r in reqs[:3]:
            assert r.state == RequestState.FINISHED
            assert len(r.output) == 3

    def test_occupancy_watermark_sheds_until_pages_free(self, tiny_model):
        cfg, params = tiny_model
        eng = Engine(cfg, params, page_size=8, num_pages=4,
                     max_batch_size=2, chunk_len=16,
                     shed_occupancy_high=0.5, shed_occupancy_low=0.25)
        first = eng.add_request(list(range(10)),
                                SamplingParams(max_new_tokens=4))
        eng.step()                         # admitted: 2/4 pages in use
        assert eng.cache.occupancy() >= 0.5
        shed = eng.add_request(list(range(4)),
                               SamplingParams(max_new_tokens=2))
        assert shed.state == RequestState.RETRY_AFTER
        while eng.has_work():
            eng.step()                     # first finishes, pool drains
        assert first.state == RequestState.FINISHED
        late = eng.add_request(list(range(4)),
                               SamplingParams(max_new_tokens=2))
        assert late.state == RequestState.QUEUED

    def test_admitted_requests_meet_deadlines_under_shedding(self,
                                                            tiny_model):
        """The graceful-degradation contract: with shedding armed, what
        the engine ADMITS it finishes within TTL; overflow is shed with
        the soft status instead of destroying everyone's latency."""
        cfg, params = tiny_model
        clk = _ManualClock()
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32, clock=clk,
                     default_ttl_s=60.0, shed_queue_high=2,
                     shed_queue_low=0)
        sp = SamplingParams(max_new_tokens=4)
        reqs = [eng.add_request(list(range(4)), sp) for _ in range(8)]
        while eng.has_work():
            clk.advance(1.0)               # 1 "second" per decode step
            eng.step()
        admitted = [r for r in reqs if r.state == RequestState.FINISHED]
        shed = [r for r in reqs if r.state == RequestState.RETRY_AFTER]
        assert admitted and shed
        assert len(admitted) + len(shed) == len(reqs)
        for r in admitted:                 # no admitted request blew its
            assert r.t_finished <= r.deadline   # deadline (none evicted)
        assert eng.metrics.deadline_evictions.value == 0

    def test_shedding_disabled_by_default(self, tiny_model):
        cfg, params = tiny_model
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=1, chunk_len=32)
        sp = SamplingParams(max_new_tokens=2)
        reqs = [eng.add_request(list(range(4)), sp) for _ in range(10)]
        assert all(r.state == RequestState.QUEUED for r in reqs)
        assert eng.metrics.engine_healthy.value == 1


class _AutoClock:
    """Manual clock that also self-advances per read — gives steps a
    deterministic nonzero duration so the decode-rate EWMA gets a real
    (and exactly reproducible) sample."""

    def __init__(self, auto=0.25):
        self.t = 0.0
        self.auto = auto

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        self.t += self.auto
        return self.t


class TestColdStartDrainFloor:
    """Regression (this PR): before the decode-rate EWMA has any
    sample, estimated_drain_s/retry_after_s used to report a useless 0
    — a freshly restarted replica looked instantly drainable and the
    router would dump the fleet's whole backlog on it.  The engine now
    reports a conservative configurable floor until the first measured
    decode step."""

    def test_floor_applies_until_first_decode_sample(self, tiny_model):
        cfg, params = tiny_model
        clk = _AutoClock(auto=0.25)
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32, clock=clk,
                     drain_floor_s=3.0, shed_queue_high=1,
                     shed_queue_low=0)
        assert eng.decode_rate() is None
        # idle + cold: the floor, not 0
        assert eng.estimated_drain_s() == 3.0
        first = eng.add_request(list(range(6)),
                                SamplingParams(max_new_tokens=4))
        shed = eng.add_request(list(range(4)),
                               SamplingParams(max_new_tokens=4))
        assert shed.state == RequestState.RETRY_AFTER
        assert shed.retry_after_s >= 3.0      # the hint honors the floor
        while eng.has_work():
            eng.step()
        assert first.state == RequestState.FINISHED
        # a measured rate owns the estimate now: idle really means 0
        assert eng.decode_rate() is not None and eng.decode_rate() > 0
        assert eng.estimated_drain_s() == 0.0

    def test_floor_defaults_on_and_is_configurable(self, tiny_model):
        cfg, params = tiny_model
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32)
        assert eng.drain_floor_s == Engine.DRAIN_FLOOR_S > 0
        assert eng.estimated_drain_s() == Engine.DRAIN_FLOOR_S
        off = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32, drain_floor_s=0.0)
        assert off.estimated_drain_s() == 0.0

    def test_backlog_above_floor_still_wins(self, tiny_model):
        """The floor is a floor, not a cap: a cold engine with a big
        backlog reports the larger assumed-rate estimate."""
        cfg, params = tiny_model
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=32, drain_floor_s=0.1)
        eng.add_request(list(range(4)),
                        SamplingParams(max_new_tokens=100))
        expected = 100 / Engine.ASSUMED_DECODE_RATE      # 1.0 > 0.1
        assert eng.estimated_drain_s() == pytest.approx(expected)


# ----------------------------------------------------------- evacuation


class TestEvacuate:
    """Engine.evacuate() — the fleet router's failover/drain primitive:
    everything in flight comes off the engine with sampled tokens
    intact, pages freed, and a re-admission elsewhere continues
    token-identically."""

    def test_evacuate_returns_all_and_frees_pool(self, tiny_model):
        cfg, params = tiny_model
        rng = np.random.RandomState(29)
        p1 = list(rng.randint(0, cfg.vocab_size, 6))
        p2 = list(rng.randint(0, cfg.vocab_size, 20))   # mid-prefill
        p3 = list(rng.randint(0, cfg.vocab_size, 5))    # still queued
        # a dedicated tracer: the process-wide default ring holds other
        # tests' traces, whose root spans carry no "state" attribute
        from paddle_tpu.observability.tracing import Tracer

        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=2, chunk_len=8, tracer=Tracer())
        sp = SamplingParams(max_new_tokens=8)
        r1, r2, r3 = (eng.add_request(p, sp) for p in (p1, p2, p3))
        for _ in range(3):
            eng.step()
        assert len(r1.output) == 1 and r1._pending == 1  # decoding
        assert 0 < r2.prompt_pos             # chunking
        assert r3.state == RequestState.QUEUED
        got = eng.evacuate()
        # the step in flight was settled first: its token left with r1
        assert len(r1.output) == 2 and r1._pending == 0
        assert eng.metrics.pipeline_drains.labels(
            reason="evacuate").value == 1
        assert [r.id for r in got] == [r1.id, r2.id, r3.id]
        assert all(r.state == RequestState.EVACUATED for r in got)
        assert all(r.finish_reason == "evacuated" for r in got)
        assert eng.cache.num_free_pages == eng.cache.num_pages
        assert not eng.has_work()
        # traces closed in the terminal state
        states = {t["name"]: t["spans"][0]["attributes"]["state"]
                  for t in eng.tracer.traces()}
        assert states[f"request#{r1.id}"] == RequestState.EVACUATED

    def test_reenqueue_elsewhere_is_token_identical(self, tiny_model):
        """The idempotent re-enqueue contract: prompt + harvested
        tokens resubmitted to a fresh engine (KV rebuilt, never
        trusted) completes exactly the un-failed greedy output."""
        cfg, params = tiny_model
        rng = np.random.RandomState(31)
        prompt = list(rng.randint(0, cfg.vocab_size, 9))
        full = naive_generate(cfg, params, prompt, 10)
        eng = Engine(cfg, params, page_size=8, num_pages=64,
                     max_batch_size=1, chunk_len=8)
        req = eng.add_request(prompt, SamplingParams(max_new_tokens=10))
        for _ in range(5):
            eng.step()
        (got,) = eng.evacuate()
        emitted = got.output
        assert 0 < len(emitted) < 10
        other = Engine(cfg, params, page_size=8, num_pages=64,
                       max_batch_size=1, chunk_len=8)
        rest = other.generate(
            [prompt + emitted],
            SamplingParams(max_new_tokens=10 - len(emitted)))[0]
        assert emitted + rest == full
        assert req is got


# ------------------------------------------------------- prefix cache


class TestPrefixCache:
    """Radix/prefix KV reuse: a shared prompt prefix becomes a refcount
    bump instead of prefill FLOPs — never a correctness change.  The
    parity oracle is the same full-recompute greedy decode every other
    engine test uses."""

    def _prompts(self, cfg, sys_len=12, tail_len=5, n_tails=2, seed=41):
        rng = np.random.RandomState(seed)
        system = [int(t) for t in rng.randint(0, cfg.vocab_size, sys_len)]
        tails = [[int(t) for t in rng.randint(0, cfg.vocab_size, tail_len)]
                 for _ in range(n_tails)]
        return system, tails

    # ---- cache-level mechanics -----------------------------------------
    def test_attach_refcounts_and_cow(self):
        c = PagedKVCache(num_layers=2, num_heads=2, head_dim=4,
                         num_pages=16, page_size=4, max_seq_len=64)
        toks = list(range(12))                   # 3 full pages
        assert c.allocate("a", 12)
        c.insert_prefix("a", toks)
        c.free("a")
        # cached pages are evictable, so the whole pool stays allocatable
        assert c.num_free_pages == 16
        assert c.prefix_stats()["cached_pages"] == 3
        c.check_integrity()
        # partial-prefix hit: 3 shared pages + 1 fresh for the tail
        m = c.allocate_prefixed("b", toks + [99, 98], chunk_tokens=4)
        assert m == 12
        shared = c.page_table("b")[:3]
        c.check_integrity()
        # full-prompt hit: matched is capped at len-1 and the final
        # page is COPIED, not shared — writes never land on shared pages
        m = c.allocate_prefixed("cw", toks, chunk_tokens=4)
        assert m == 11
        cow_table = c.page_table("cw")[:3]
        assert cow_table[:2] == shared[:2]       # prefix shared
        assert cow_table[2] != shared[2]         # final page is a copy
        np.testing.assert_array_equal(
            np.asarray(c.k_pages[:, cow_table[2]]),
            np.asarray(c.k_pages[:, shared[2]]))
        c.check_integrity()
        # free decrements; double-free impossible, cache intact
        c.free("b")
        c.free("cw")
        c.check_integrity()
        assert c.prefix_stats()["cached_pages"] == 3
        assert c.num_free_pages == 16

    def test_miss_returns_cold_and_shortage_rolls_back(self):
        c = PagedKVCache(num_layers=1, num_heads=1, head_dim=2,
                         num_pages=4, page_size=4, max_seq_len=16)
        assert c.allocate_prefixed("a", list(range(9)), 4) == 0  # cold
        # pool exhausted even after eviction: None, nothing moved
        assert c.allocate_prefixed("b", list(range(20, 36)), 16) is None
        assert "b" not in c.seq_ids()
        c.check_integrity()

    def test_pressure_eviction_never_reclaims_the_matched_chain(self):
        """Regression (review): allocation-pressure eviction used to
        run BEFORE the matched chain's refcounts were bumped, so a
        zero-ref matched page could be LRU-evicted and handed straight
        back as a "fresh" page for the SAME sequence — one physical
        page at two logical table positions (refcounts still
        consistent, so check_integrity alone missed it) and prefill
        writes corrupting what attention reads as the cached prefix.
        The chain is pinned first now; when the pinned match starves
        its own admission the match shrinks instead of corrupting."""
        c = PagedKVCache(num_layers=1, num_heads=1, head_dim=2,
                         num_pages=3, page_size=4, max_seq_len=16)
        toks = list(range(12))                   # 3 full pages
        assert c.allocate("a", 12)
        c.insert_prefix("a", toks)
        c.free("a")
        assert c.num_free_pages == 3             # pool = zero-ref cache
        # a 16-token prompt matching all 12 cached tokens needs 4
        # pages: the pool can only admit it by giving back part of the
        # match — never by evicting a page it is about to attach
        m = c.allocate_prefixed("b", toks + [99, 98, 97, 96],
                                chunk_tokens=8)
        assert m == 4                            # shrunk hit, not a dup
        table = c.page_table("b")[:3]
        assert len(set(table)) == len(table)     # no page twice
        c.check_integrity()

    def test_cow_source_pinned_and_shrunk_under_pressure(self):
        """Fully-cached prompt under total pool pressure: the COW
        source is pinned through the fresh-page take (it used to be
        evictable in the same window), and the admission falls back to
        a shorter shared prefix rather than failing or self-copying."""
        c = PagedKVCache(num_layers=1, num_heads=1, head_dim=2,
                         num_pages=3, page_size=4, max_seq_len=16)
        toks = list(range(12))
        assert c.allocate("a", 12)
        c.insert_prefix("a", toks)
        c.free("a")
        m = c.allocate_prefixed("cw", toks, chunk_tokens=4)
        # full COW needs matched-chain + copy page = 4 pages on a
        # 3-page pool: the deepest cached page is dropped, the first
        # two stay shared, the tail prefills into the reclaimed page
        assert m == 8
        table = c.page_table("cw")[:3]
        assert len(set(table)) == len(table)
        c.check_integrity()

    # ---- engine parity --------------------------------------------------
    def test_cache_hit_greedy_parity_and_metrics(self, tiny_model):
        """A request sharing a finished request's prefix prefills only
        its tail, and its greedy output equals a cold run's."""
        cfg, params = tiny_model
        system, tails = self._prompts(cfg)
        eng = Engine(cfg, params, page_size=4, num_pages=64,
                     max_batch_size=2, chunk_len=4)
        sp = SamplingParams(max_new_tokens=6)
        a = eng.add_request(system + tails[0], sp)
        while eng.has_work():
            eng.step()
        assert a.output == naive_generate(cfg, params, system + tails[0], 6)
        chunks_cold = eng.metrics.prefill_chunks.value
        b = eng.add_request(system + tails[1], sp)
        while eng.has_work():
            eng.step()
        assert b.output == naive_generate(cfg, params, system + tails[1], 6)
        snap = eng.metrics.snapshot()["prefix_cache"]
        assert snap["hits"] == 1
        assert snap["hit_tokens"] >= len(system) - eng.cache.page_size
        assert snap["cached_pages"] > 0
        # the hit skipped prefill work: fewer chunks than the cold run
        assert eng.metrics.prefill_chunks.value - chunks_cold < chunks_cold
        eng.cache.check_integrity()

    def test_full_prompt_hit_cow_parity(self, tiny_model):
        """An identical page-aligned prompt re-runs exactly one token
        through a copied final page — and decodes identically, without
        corrupting the original's cached pages for a third request."""
        cfg, params = tiny_model
        system, _ = self._prompts(cfg, sys_len=16, seed=43)  # 4 pages
        ref = naive_generate(cfg, params, system, 6)
        eng = Engine(cfg, params, page_size=4, num_pages=64,
                     max_batch_size=2, chunk_len=4)
        sp = SamplingParams(max_new_tokens=6)
        outs = [eng.generate([system], sp)[0] for _ in range(3)]
        assert outs == [ref, ref, ref]
        stats = eng.cache.prefix_stats()
        assert stats["hits"] == 2
        assert stats["hit_tokens"] == 2 * (len(system) - 1)  # COW cap
        eng.cache.check_integrity()

    def test_hit_mid_chunk_parity(self, tiny_model):
        """A cached prefix whose end is NOT a chunk boundary: prefill
        resumes mid-chunk at the first uncached token."""
        cfg, params = tiny_model
        # page 4, chunk 8: a 12-token cached prefix starts the tail
        # chunk at offset 12 % 8 == 4 — mid-chunk
        system, tails = self._prompts(cfg, sys_len=12, tail_len=9,
                                      seed=47)
        eng = Engine(cfg, params, page_size=4, num_pages=64,
                     max_batch_size=2, chunk_len=8)
        sp = SamplingParams(max_new_tokens=6)
        eng.generate([system + tails[0]], sp)
        b = eng.add_request(system + tails[1], sp)
        eng.step()
        assert b.prompt_pos > 12            # resumed past the cached part
        while eng.has_work():
            eng.step()
        assert b.output == naive_generate(cfg, params,
                                          system + tails[1], 6)
        assert eng.cache.prefix_stats()["hits"] == 1

    def test_prefix_cache_off_is_cold(self, tiny_model):
        cfg, params = tiny_model
        system, tails = self._prompts(cfg)
        eng = Engine(cfg, params, page_size=4, num_pages=64,
                     max_batch_size=2, chunk_len=4, prefix_cache=False)
        sp = SamplingParams(max_new_tokens=4)
        eng.generate([system + tails[0], system + tails[1]], sp)
        stats = eng.cache.prefix_stats()
        assert stats["hits"] == 0 and stats["cached_pages"] == 0
        assert eng.health()["prefix_cache"]["enabled"] is False

    # ---- eviction / watermark integration ------------------------------
    def test_lru_eviction_under_pressure_never_sheds(self, tiny_model):
        """A pool full of zero-ref cached prefixes must neither trip
        the occupancy watermark (no RETRY_AFTER storm from a warm
        cache) nor block admission: allocation LRU-evicts."""
        cfg, params = tiny_model
        rng = np.random.RandomState(53)
        eng = Engine(cfg, params, page_size=4, num_pages=8,
                     max_batch_size=1, chunk_len=8,
                     shed_occupancy_high=0.5)
        sp = SamplingParams(max_new_tokens=2)
        # two 16-token prompts fill all 8 pages with cached prefixes
        for _ in range(2):
            p = [int(t) for t in rng.randint(0, cfg.vocab_size, 15)]
            eng.generate([p], sp)
        assert eng.cache.prefix_stats()["cached_pages"] >= 6
        assert eng.cache.occupancy() == 0.0      # all evictable = free
        fresh = [int(t) for t in rng.randint(0, cfg.vocab_size, 15)]
        req = eng.add_request(fresh, sp)
        assert req.state == RequestState.QUEUED  # NOT shed
        while eng.has_work():
            eng.step()
        assert req.state == RequestState.FINISHED
        assert req.output == naive_generate(cfg, params, fresh, 2)
        assert eng.metrics.snapshot()["prefix_cache"]["evictions"] > 0
        assert eng.metrics.requests_shed.value == 0
        eng.cache.check_integrity()

    def test_mid_prefill_deadline_eviction_decrements_shared_pages(
            self, tiny_model):
        """The PR 7 eviction regression, extended: a request evicted
        mid-prefill whose already-written chunks include SHARED cached
        pages must DECREMENT them (the cache and its other users
        survive), not force-free them."""
        cfg, params = tiny_model
        system, tails = self._prompts(cfg, sys_len=12, tail_len=10,
                                      seed=59)
        clk = _ManualClock()
        eng = Engine(cfg, params, page_size=4, num_pages=32,
                     max_batch_size=2, chunk_len=4, clock=clk)
        sp = SamplingParams(max_new_tokens=4)
        a = eng.add_request(system + tails[0], sp)
        while eng.has_work():
            eng.step()
        cached = eng.cache.prefix_stats()["cached_pages"]
        assert cached > 0
        # B rides the cached prefix, then dies mid-prefill
        b = eng.add_request(system + tails[1],
                            SamplingParams(max_new_tokens=4, ttl_s=5.0))
        clk.advance(1.0)
        eng.step()
        assert b.prompt_pos > 12 and b.prompt_pos < len(b.prompt)
        clk.advance(10.0)
        done = eng.step()
        assert b in done and b.state == RequestState.EVICTED
        # shared pages survived the eviction: no double-free, cache
        # intact, and a third request still hits it with exact parity
        eng.cache.check_integrity()
        assert eng.cache.prefix_stats()["cached_pages"] >= cached
        assert eng.cache.num_free_pages == eng.cache.num_pages
        c = eng.add_request(system + tails[0], sp)
        while eng.has_work():
            eng.step()
        assert c.output == a.output
        assert eng.cache.prefix_stats()["hits"] >= 2
        eng.cache.check_integrity()

    # ---- defrag (satellite) --------------------------------------------
    def test_defrag_with_shared_prefix_decodes_token_identically(
            self, tiny_model):
        """Refcount-aware defrag: a page shared by two page tables (and
        the radix tree) relocates ONCE with every referencing table
        updated — both sequences keep decoding token-identically."""
        cfg, params = tiny_model
        system, tails = self._prompts(cfg, sys_len=12, tail_len=6,
                                      seed=61)
        eng = Engine(cfg, params, page_size=4, num_pages=64,
                     max_batch_size=2, chunk_len=16)
        sp = SamplingParams(max_new_tokens=10)
        # a placeholder allocation pins the low-index pages, so the
        # cached prefix and both sequences land above it — freeing it
        # later leaves the hole defrag must compact over
        eng.cache.allocate("hole", 16)
        eng.generate([system + [7, 7, 7]], SamplingParams(max_new_tokens=2))
        # two live sequences sharing the cached system prefix
        b = eng.add_request(system + tails[0], sp)
        c = eng.add_request(system + tails[1], sp)
        for _ in range(3):
            eng.step()
        assert b.output and c.output           # both mid-decode
        tb = eng.cache.page_table(b.id)[:3]
        assert tb[:3] == eng.cache.page_table(c.id)[:3]  # 2-way shared
        eng.cache.free("hole")                 # hole below everything
        moved = eng.cache.defrag()
        assert moved > 0
        assert eng.cache.page_table(b.id)[:3] != tb  # shared pages moved
        eng.cache.check_integrity()
        # the shared prefix relocated once: tables still agree
        assert eng.cache.page_table(b.id)[:3] == \
            eng.cache.page_table(c.id)[:3]
        while eng.has_work():
            eng.step()
        assert b.output == naive_generate(cfg, params, system + tails[0],
                                          10)
        assert c.output == naive_generate(cfg, params, system + tails[1],
                                          10)
        eng.cache.check_integrity()

    # ---- gossip surface -------------------------------------------------
    def test_prefix_summary_bounded_and_hashes_roundtrip(self, tiny_model):
        """The bounded radix summary names exactly the prefixes that
        prefix_hashes() computes client-side — the gossip protocol's
        two halves agree."""
        from paddle_tpu.serving import prefix_hashes

        cfg, params = tiny_model
        system, tails = self._prompts(cfg, sys_len=16, seed=67)
        eng = Engine(cfg, params, page_size=4, num_pages=64,
                     max_batch_size=2, chunk_len=8)
        eng.generate([system + tails[0]], SamplingParams(max_new_tokens=2))
        assert len(eng.prefix_summary(max_entries=3)["entries"]) <= 3
        summary = eng.prefix_summary()
        assert summary["enabled"] is True
        assert summary["stats"]["cached_pages"] > 0
        hashes = prefix_hashes(system + tails[1], summary["page_size"])
        depths = [(i + 1) * summary["page_size"]
                  for i, h in enumerate(hashes)
                  if h in summary["entries"]]
        assert depths and max(depths) >= 16      # the shared system part
        for h, depth in summary["entries"].items():
            assert depth % summary["page_size"] == 0


# --------------------------------------------------- satellite regressions


class TestRoiAlignEdge:
    def test_sample_exactly_at_image_edge_is_clamped_not_dropped(self):
        """A sampling point at exactly y == H (or x == W) must clamp onto
        the edge pixel (reference roi_align_op.cc zeroes only beyond ±1
        past the edge), not contribute zero."""
        from paddle_tpu.vision.detection_ops import roi_align

        feat = np.ones((1, 1, 4, 4), np.float32)
        # aligned: box (3.5, 3.5)-(4.5, 4.5) - 0.5 => y1=x1=3, y2=x2=4;
        # output 1x1, sampling_ratio 1 => single sample at (3.5+0.5)=4.0
        boxes = np.asarray([[3.5, 3.5, 4.5, 4.5]], np.float32)
        out = roi_align(feat, boxes, output_size=1, sampling_ratio=1,
                        aligned=True)
        assert float(np.asarray(out)[0, 0, 0, 0]) == pytest.approx(1.0)

    def test_sample_beyond_edge_still_zero(self):
        from paddle_tpu.vision.detection_ops import roi_align

        feat = np.ones((1, 1, 4, 4), np.float32)
        # sample lands at 5.5 > H + 1: stays invalid
        boxes = np.asarray([[5.0, 5.0, 6.0, 6.0]], np.float32)
        out = roi_align(feat, boxes, output_size=1, sampling_ratio=1,
                        aligned=True)
        assert float(np.asarray(out)[0, 0, 0, 0]) == 0.0


class TestConll05Guard:
    def _emit(self, sent, cols):
        from paddle_tpu.text import Conll05

        ds = object.__new__(Conll05)
        ds.samples = []
        ds.word_dict = ds.label_dict = None
        ds._emit(sent, cols)
        return ds.samples

    def test_well_formed_rows_parse(self):
        samples = self._emit(
            ["the", "cat", "sat"],
            [["-", "(A0*"], ["-", "*)"], ["sat", "(V*)"]])
        assert len(samples) == 1
        words, pred, labels = samples[0]
        assert pred == "sat"
        assert labels == ["B-A0", "I-A0", "B-V"]

    def test_malformed_short_row_raises_descriptive_error(self):
        with pytest.raises(ValueError, match="malformed props row"):
            self._emit(["the", "cat", "sat"],
                       [["-", "(A0*"], ["-"], ["sat", "(V*)"]])

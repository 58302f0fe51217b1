"""The ragged batch (``paddle_tpu/models/ragged.py``): the format between
the serving scheduler and every served model's step, and the view a step
reads it through."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import GPT_CONFIGS, gpt_init
from paddle_tpu.models.ragged import (RaggedBatch, RaggedView, batch_shapes,
                                      pending_token, resolve_pending,
                                      empty_batch)
from paddle_tpu.serving import Engine, SamplingParams

B, T, Q, PS, MAX_PAGES, NUM_PAGES, MAX_SEQ = 4, 12, 5, 4, 6, 32, 24


def _mixed():
    """Row 0 a 5-token chunk at positions 2..6 (it crosses the page
    boundary at 4), rows 1 and 3 decode rows at contexts 9 and 1, row 2
    idle; 7 packed tokens and 5 padding slots.  Written out by hand, not
    through ``empty_batch``."""
    qlens = np.array([5, 1, 0, 1], np.int32)
    ctxs = np.array([7, 9, 0, 1], np.int32)
    rows = np.array([0, 0, 0, 0, 0, 1, 3] + [B] * 5, np.int32)
    slots = np.array([0, 1, 2, 3, 4, 0, 0] + [0] * 5, np.int32)
    tokens = np.arange(100, 100 + T, dtype=np.int32)
    tables = (np.arange(B * MAX_PAGES, dtype=np.int32)[::-1]
              .reshape(B, MAX_PAGES).copy())           # all distinct
    return RaggedBatch(tokens, rows, slots, qlens, ctxs, tables)


def _view(batch, max_q=Q):
    return RaggedView(RaggedBatch(*map(jnp.asarray, batch)), max_q=max_q,
                      max_seq_len=MAX_SEQ, num_pages=NUM_PAGES, page_size=PS)


N_VALID = 7


def test_view_names_row_validity_and_position():
    v = _view(_mixed())
    assert (v.B, v.T, v.Q) == (B, T, Q)
    assert np.asarray(v.valid).tolist() == [True] * N_VALID + [False] * 5
    assert np.asarray(v.row)[:N_VALID].tolist() == [0, 0, 0, 0, 0, 1, 3]
    assert np.asarray(v.row).max() < B              # padding is clamped
    assert np.asarray(v.pos)[:N_VALID].tolist() == [2, 3, 4, 5, 6, 8, 0]
    # only the row whose chunk starts at position 0 (and the idle row)
    assert np.asarray(v.fresh).tolist() == [False, False, True, True]


@pytest.mark.parametrize("trailing,dtype,fill", [
    ((), jnp.int32, -1),
    ((3,), jnp.float32, 0),
    ((2, 3), jnp.bool_, False),
    ((2, 3), jnp.float32, 7.5),
], ids=["flat-int", "vec-zero", "mat-bool", "mat-fill"])
def test_unpad_inverts_pad_on_valid_tokens(trailing, dtype, fill):
    batch = _mixed()
    v = _view(batch)
    rng = np.random.RandomState(0)
    a = rng.randint(1, 50, (T,) + trailing)
    a = jnp.asarray(a > 25 if dtype == jnp.bool_ else a, dtype)
    padded = v.pad(a, fill)
    assert padded.shape == (B, Q) + trailing and padded.dtype == a.dtype
    back = np.asarray(v.unpad(padded))
    np.testing.assert_array_equal(back[:N_VALID], np.asarray(a)[:N_VALID])
    # everywhere no valid token landed, `fill` and nothing else
    landed = np.zeros((B, Q), bool)
    landed[batch.rows[:N_VALID], batch.slots[:N_VALID]] = True
    assert landed.sum() == N_VALID
    np.testing.assert_array_equal(
        np.asarray(padded)[~landed],
        np.full((B * Q - N_VALID,) + trailing, fill, padded.dtype))


def test_unpad_reads_a_head_major_output_in_place():
    v = _view(_mixed())
    a = jnp.asarray(np.random.RandomState(1).randn(T, 2, 3), jnp.float32)
    head_major = v.pad(a).transpose(0, 2, 1, 3)            # [B, H, Q, hd]
    np.testing.assert_array_equal(
        np.asarray(v.unpad(head_major, q_axis=2))[:N_VALID],
        np.asarray(a)[:N_VALID])


def test_max_q_none_pads_to_the_packed_width():
    v = _view(_mixed(), max_q=None)
    assert v.Q == T
    assert v.pad(jnp.zeros((T, 2))).shape == (B, T, 2)


def test_last_picks_each_live_rows_last_packed_token():
    batch = _mixed()
    v = _view(batch)
    x = jnp.asarray(batch.tokens)[:, None] * jnp.ones((1, 2), jnp.int32)
    got = np.asarray(v.last(x))
    assert got.shape == (B, 2)
    # row 0's chunk ends at packed index 4, row 1 at 5, row 3 at 6; the
    # idle row 2 reads some in-range token (garbage the engine ignores)
    assert got[[0, 1, 3], 0].tolist() == [104, 105, 106]
    assert 100 <= got[2, 0] < 100 + T


def test_scatter_target_crosses_a_page_boundary_and_drops_masked_tokens():
    batch = _mixed()
    v = _view(batch)
    page, slot = np.asarray(v.page), np.asarray(v.slot_in_page)
    t = batch.page_tables
    # row 0: positions 2, 3 on its logical page 0, then 4, 5, 6 on page 1
    assert page[:5].tolist() == [t[0, 0], t[0, 0], t[0, 1], t[0, 1], t[0, 1]]
    assert slot[:5].tolist() == [2, 3, 0, 1, 2]
    # the decode rows: position 8 is slot 0 of logical page 2; position 0
    assert (page[5], slot[5]) == (t[1, 2], 0)
    assert (page[6], slot[6]) == (t[3, 0], 0)
    # every masked token is routed out of the pool's range
    assert (page[N_VALID:] == NUM_PAGES).all()
    pool = jnp.zeros((NUM_PAGES, PS), jnp.int32).at[
        v.page, v.slot_in_page].set(jnp.asarray(batch.tokens), mode="drop")
    assert int((np.asarray(pool) != 0).sum()) == N_VALID
    assert np.asarray(pool)[t[0, 1], :3].tolist() == [102, 103, 104]


def test_a_slot_past_the_rows_chunk_is_masked():
    """``valid`` also needs ``slots < query_lens[row]``: a token naming a
    live row beyond its chunk is dropped like a padding slot."""
    batch = _mixed()
    batch.slots[5] = 1                      # row 1 has one query token
    v = _view(batch)
    assert not bool(v.valid[5])
    assert int(v.page[5]) == NUM_PAGES


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(GPT_CONFIGS["tiny"], dtype="float32")
    eng = Engine(cfg, gpt_init(cfg, jax.random.key(0), dtype=jnp.float32),
                 page_size=4, num_pages=32, max_batch_size=4, chunk_len=6)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (3, 2, 15)]
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=8))
            for p in prompts]
    # one step, its 9-token budget shared 3 + 2 + 4: the two short prompts
    # complete (decode rows from now on), the long one is mid-prefill
    eng.step()
    assert [r.prompt_pos for r in reqs] == [3, 2, 4]
    return eng, reqs


def test_pack_yields_the_documented_contract(engine):
    eng, reqs = engine
    plan = eng._plan_rows()
    assert plan == {0: 1, 1: 1, 2: 6}
    batch, sched = eng._pack(plan)
    assert isinstance(batch, RaggedBatch)
    Bm, Tm = eng.max_batch_size, eng.token_budget
    n = 8
    # rows in ascending slot order, each row's tokens contiguous and in order
    assert batch.rows.tolist() == [0, 1] + [2] * 6 + [Bm] * (Tm - n)
    assert batch.slots.tolist() == [0, 0, 0, 1, 2, 3, 4, 5] + [0] * (Tm - n)
    # the two decode rows' newest tokens are still on the device (the one
    # step so far is in flight): each sends the marker that names its slot
    assert [r._pending for r in reqs] == [1, 1, 0]
    assert batch.tokens[:n].tolist() == (
        [pending_token(0), pending_token(1)] + reqs[2].prompt[4:10])
    assert [pending_token(0), pending_token(1)] == [-1, -2]
    assert (batch.tokens[n:] == 0).all()
    assert batch.query_lens.tolist() == [1, 1, 6, 0]
    # context_lens counts this step's tokens in: a decode row's whole
    # sequence, the chunk row's prompt position after the chunk
    assert batch.context_lens.tolist() == [4, 3, 10, 0]
    for i, r in enumerate(reqs):
        assert batch.page_tables[i].tolist() == list(
            eng.cache.page_table(r.id))
    assert (batch.page_tables[3] == 0).all()
    assert [(i, r.id, q, ctx) for i, r, q, ctx in sched] == [
        (0, reqs[0].id, 1, 4), (1, reqs[1].id, 1, 3), (2, reqs[2].id, 6, 10)]


def test_batch_shapes_are_what_pack_produces(engine):
    eng, _ = engine
    dims = eng.batch_dims
    assert dims == (eng.max_batch_size, eng.token_budget,
                    eng.cache.max_pages_per_seq)
    batch, _ = eng._pack(eng._plan_rows())
    shapes = batch_shapes(*dims)
    assert type(shapes) is type(batch) is RaggedBatch
    assert shapes._fields == ("tokens", "rows", "slots", "query_lens",
                              "context_lens", "page_tables")
    for name, s, a, e in zip(shapes._fields, shapes, batch,
                             empty_batch(*dims)):
        assert (s.shape, s.dtype) == (a.shape, a.dtype) == (e.shape, e.dtype)
        assert a.dtype == np.int32, name
    # the jitted step takes exactly these six operands, in this order,
    # and after them the sampling table by batch slot and the ids the
    # step before chose
    lowered = eng._step_fn.lower(*eng.step_args())
    flat = jax.tree_util.tree_leaves(lowered.in_avals)
    assert [(a.shape, a.dtype) for a in flat[-8:-2]] == [
        (s.shape, s.dtype) for s in shapes]
    assert (flat[-2].shape, flat[-2].dtype) == ((dims[0], 6), np.uint32)
    assert (flat[-1].shape, flat[-1].dtype) == ((dims[0],), np.int32)


def test_a_pending_token_is_resolved_from_the_previous_ids(engine):
    """What the engine's jitted wrapper does before the model's step: a
    negative entry names a batch slot of the previous step's ids; every
    other entry, padding included, passes through."""
    eng, reqs = engine
    batch, _ = eng._pack(eng._plan_rows())
    prev = np.array([900, 901, 902, 903], np.int32)
    out = resolve_pending(RaggedBatch(*map(jnp.asarray, batch)),
                          jnp.asarray(prev))
    assert out.tokens.tolist() == [900, 901] + batch.tokens[2:].tolist()
    for name in RaggedBatch._fields[1:]:
        assert (np.asarray(getattr(out, name))
                == getattr(batch, name)).all(), name
    # once the step in flight is committed the host holds the tokens, and
    # the same rows send the tokens themselves
    eng._drain("test")
    assert [r._pending for r in reqs] == [0, 0, 0]
    again, _ = eng._pack(eng._plan_rows())
    assert again.tokens[:2].tolist() == [reqs[0].tokens[-1],
                                         reqs[1].tokens[-1]]
    assert (again.context_lens == batch.context_lens).all()


def test_empty_batch_is_all_idle_rows_and_padding_slots():
    batch = empty_batch(B, T, MAX_PAGES)
    assert (batch.rows == B).all()
    for name in ("tokens", "slots", "query_lens", "context_lens",
                 "page_tables"):
        assert not getattr(batch, name).any(), name
    assert not bool(_view(batch).valid.any())


# ------------------------------------------------- tiles and window tables


@pytest.mark.parametrize("n", [2, 4, 8])
def test_tiles_hold_one_rows_query_slots_each_and_unpad_inverts(n):
    """Row 0's chunk of 5 in tiles of ``n`` slots, one tile for each decode
    row, none for the idle row; ``B + T / n`` tiles in all, the rest
    unused (row ``B``)."""
    batch = _mixed()
    v = _view(batch)
    rows, index = (np.asarray(a) for a in v.tiles(n))
    per_row = -(-batch.query_lens // n)
    assert len(rows) == B + -(-T // n)
    want_rows = np.repeat(np.arange(B), per_row)
    assert rows[: per_row.sum()].tolist() == want_rows.tolist()
    assert (rows[per_row.sum():] == B).all()
    assert index[: per_row.sum()].tolist() == [
        t for k in per_row for t in range(k)]
    a = jnp.asarray(np.arange(1, T + 1) * 10, jnp.float32)
    tiles = v.pad_tiles(a, n, fill=-1.0)
    assert tiles.shape == (len(rows), n)
    np.testing.assert_array_equal(np.asarray(v.unpad_tiles(tiles))[:N_VALID],
                                  np.asarray(a)[:N_VALID])
    # slot s of a row's chunk sits in the row's tile s // n, lane s % n
    first = np.cumsum(per_row) - per_row
    for t in range(N_VALID):
        r, s = int(batch.rows[t]), int(batch.slots[t])
        assert float(tiles[first[r] + s // n, s % n]) == float(a[t])
    assert int((np.asarray(tiles) != -1.0).sum()) == N_VALID


def test_a_window_batch_carries_a_second_table_and_a_second_target():
    from paddle_tpu.models.ragged import WindowRaggedBatch

    plain = empty_batch(B, T, MAX_PAGES)
    assert type(plain) is RaggedBatch and len(plain) == 6
    batch = empty_batch(B, T, MAX_PAGES, window_tables=True)
    assert type(batch) is WindowRaggedBatch
    assert batch._fields == RaggedBatch._fields + ("window_page_tables",)
    assert batch.window_page_tables.shape == (B, MAX_PAGES)
    shapes = batch_shapes(B, T, MAX_PAGES, window_tables=True)
    assert [s.shape for s in shapes] == [a.shape for a in batch]
    mixed = _mixed()
    other = (np.arange(B * MAX_PAGES, dtype=np.int32) % 7).reshape(
        B, MAX_PAGES)
    v = RaggedView(WindowRaggedBatch(*map(jnp.asarray, mixed),
                                     jnp.asarray(other)),
                   max_q=Q, max_seq_len=MAX_SEQ, num_pages=NUM_PAGES,
                   page_size=PS, num_window_pages=7)
    pos = np.asarray(v.pos)[:N_VALID]
    rows = np.asarray(v.row)[:N_VALID]
    assert np.asarray(v.window_page)[:N_VALID].tolist() == \
        other[rows, pos // PS].tolist()
    assert np.asarray(v.page)[:N_VALID].tolist() == \
        mixed.page_tables[rows, pos // PS].tolist()
    # a masked token's target is out of either pool's range
    assert (np.asarray(v.window_page)[N_VALID:] == 7).all()
    assert (np.asarray(v.page)[N_VALID:] == NUM_PAGES).all()
    assert not hasattr(_view(mixed), "window_page")
    # a pending token is resolved in either kind of batch
    out = resolve_pending(v.batch._replace(
        tokens=v.batch.tokens.at[5].set(pending_token(1))),
        jnp.asarray([7, 8, 9, 10], jnp.int32))
    assert type(out) is WindowRaggedBatch and int(out.tokens[5]) == 8

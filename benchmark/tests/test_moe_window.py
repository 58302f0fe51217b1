"""CPU tests of what the sparse-expert cell adds to the harness: the
operation and byte counts against hand counts, the reference's own pieces
(YaRN's frequencies, the routed sum by expert against a token-by-token
loop, blocked attention and head), and that ``correct`` can come out false —
the control (the reference in float8 in the program's place) and the two
planted faults (a window one position short, a token's last choice left
out) each fail a limit at the rehearsal size.

    python -m pytest benchmark/tests/test_moe_window.py -q
"""
import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reference_moe_window as rm  # noqa: E402
from benchmark import roofline_moe_window as roof  # noqa: E402
from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]
         if bench.load_json(bench.HERE, "workloads", w["name"]
                            + ".json")["runner"] == "serve_moe_window"]
SEED = 2147483693
NEW_METRICS = ("moe_serve_step_mfu", "expert_matmul_roofline",
               "expert_matmul_device_share", "window_attention_roofline",
               "window_page_share")


# ------------------------------------------------- operations and bytes


class _Small:
    D, F, Fe, Fs, Vp, E, Hkv, hd, held = 8, 32, 4, 6, 100, 16, 2, 4, 4
    kinds = (rm.FULL, rm.SLIDING, rm.SLIDING)
    mlps = (rm.DENSE, rm.SPARSE, rm.SPARSE)
    heads = (4, 6, 6)
    count, heads_of = rm.Sizes.count, rm.Sizes.heads_of
    attention_params, expert_params = (rm.Sizes.attention_params,
                                       rm.Sizes.expert_params)
    sparse_shared_params, dense_params, head_params = (
        rm.Sizes.sparse_shared_params, rm.Sizes.dense_params,
        rm.Sizes.head_params)


def test_matrix_parameters_against_a_hand_count():
    s = _Small()
    # full: q 8x16, k and v 8x8, gate 8x4, o 16x8; sliding: q 8x24, gate
    # 8x6, o 24x8
    assert s.attention_params(rm.FULL) == 128 + 64 + 64 + 32 + 128 == 416
    assert s.attention_params(rm.SLIDING) == 192 + 128 + 48 + 192 == 560
    assert s.expert_params() == 3 * 8 * 4 and s.dense_params() == 768
    # the router's 8x16 and the shared expert's 3 x 8x6
    assert s.sparse_shared_params() == 128 + 144
    assert roof.token_params(s) == 416 + 2 * 560 + 768 + 2 * 272 == 2848


def test_step_operations_against_a_hand_count():
    s = _Small()
    # one full layer of 4 heads reads the context, two sliding layers of 6
    # heads their window's share
    assert roof.attention_ops(s, 50, 20) == 4 * 4 * (4 * 50 + 2 * 6 * 20)
    assert roof.expert_ops(s, 7) == 2 * 96 * 7
    # 9 tokens of which 3 rows were owed a token, 7 pairs on held experts
    assert roof.step_flops(s, 9, 3, 50, 20, 7) == (
        2 * 2848 * 9 + 1344 + 2 * 800 * 3 + 7040)


def test_processed_tokens_are_the_prompts_and_the_decode_rows():
    # 5 generated tokens of which 2 came out of a prompt's last chunk
    counts = {"prefill_tokens": 6, "generated_tokens": 5, "first_tokens": 2}
    assert roof.processed(counts) == 9


def test_bytes_against_a_hand_count():
    s = _Small()
    # keys and values, 2 heads of 4 in bf16: 10 positions in the full
    # layer, 6 in each of the two sliding ones
    assert roof.attention_bytes(s, 10, 6) == 2 * 2 * 4 * 2 * (10 + 2 * 6)
    # 3 experts read once (96 parameters each), 7 pairs in and out (8
    # each) and through the SwiGLU's width (4, written and read)
    assert roof.expert_bytes(s, 7, 3) == 2 * (3 * 96 + 7 * (16 + 8))


# -------------------------------------------------- the reference's pieces


@pytest.fixture(scope="module")
def tiny():
    return bench.load_json(bench.HERE, "configs", "tiny-moe-window.json")


def test_sizes_read_every_assumed_item_by_name(tiny):
    s = rm.Sizes(tiny)
    assert (s.E, s.first, s.held, s.top_k, s.window) == (16, 4, 4, 4, 16)
    assert s.heads == (4, 6, 6, 6) and s.kinds[0] == rm.FULL
    assert s.mlps == (rm.DENSE,) + (rm.SPARSE,) * 3
    for gone in ("init_std", "norm_gain_jitter", "router_score",
                 "attention_gate", "shared_expert_gate", "qk_norm",
                 "hidden_act"):
        cut = dict(tiny, assumed={k: v for k, v in tiny["assumed"].items()
                                  if k != gone})
        with pytest.raises(KeyError):
            rm.Sizes(cut)
    # another reading of an assumed item is refused, not run as this one
    for name, other in (("router_score", "sigmoid"),
                        ("shared_expert_gate", "sigmoid"),
                        ("qk_norm", "rms")):
        with pytest.raises(ValueError, match=name):
            rm.Sizes(dict(tiny, assumed=dict(tiny["assumed"],
                                             **{name: other})))
    with pytest.raises(ValueError, match="experts held"):
        rm.Sizes(dict(tiny, num_experts=8))
    with pytest.raises(ValueError, match="mlp_only_layers"):
        rm.Sizes(dict(tiny, mlp_only_layers=[1]))


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]
                                    if "moe_intermediate_size"
                                    in bench.load_json(ROOT, c["file"])])
def test_published_widths_and_the_bytes_the_cut_was_reckoned_at(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    file = bench.load_json(ROOT, entry["file"])
    s = rm.Sizes(file)
    assert (s.D, s.F, s.Fe, s.Fs, s.hd, s.window) == (3072, 12288, 1024,
                                                      1024, 128, 512)
    assert (s.E, s.top_k, s.routed_scale) == (256, 10, 2.5)
    assert (s.held, s.Hkv, s.V, s.L) == (64, 2, 25088, 8)
    assert s.heads == (12, 18, 18, 18) * 2              # groups of 6 and 9
    assert s.kinds == (rm.FULL,) + (rm.SLIDING,) * 3 \
        + (rm.FULL,) + (rm.SLIDING,) * 3
    # the issue's arithmetic: an expert 9.437 M, a sliding layer's
    # attention 15.78 M, a full one's 11.05 M, the dense layer whole
    assert s.expert_params() == 9_437_184
    assert s.attention_params(rm.SLIDING) == 15_783_936
    assert s.attention_params(rm.FULL) == 11_046_912
    assert s.n_params() == 4_683_660_288                # 9.37 GB in bf16
    # every number of the published config that is not named as reduced
    # is the catalog's (the driver compares them again)
    assert file["num_attention_heads"] == 48 and file["head_dim"] == 128
    assert file["published"]["num_experts"] == 256 == file["router_outputs"]
    assert set(entry["reduced"]) >= {"num_experts", "num_key_value_heads",
                                     "vocab_size", "num_hidden_layers"}


def test_yarn_blends_each_frequency_between_its_two_readings(tiny):
    rope = dict(rope_type="yarn", rope_theta=500000, factor=128,
                original_max_position_embeddings=8192, beta_slow=1,
                beta_fast=32, attention_factor=1.4852030263919618,
                partial_rotary_factor=0.5)
    inv, factor, rot = rm.inverse_frequencies(rope, 128)
    assert rot == 64 and inv.shape == (32,) and factor == rope[
        "attention_factor"]
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    # fast dimensions keep their frequency, slow ones are divided by the
    # factor, and the ramp between them is monotone
    assert inv[0] == plain[0] and inv[-1] == pytest.approx(plain[-1] / 128)
    ratio = plain / inv
    assert np.all(np.diff(ratio) >= 0) and 1 < ratio[16] < 128
    # the dimension that turns 32 times in 8192 positions is where it starts
    low = math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    assert ratio[low] == 1 and ratio[low + 1] > 1
    # the program's table is the same numbers
    from paddle_tpu.models.moe_window import (FULL, MOE_WINDOW_CONFIGS,
                                              SLIDING, rotary_table)
    big = MOE_WINDOW_CONFIGS[next(
        c["name"] for c in MANIFEST["configs"]
        if "moe_intermediate_size" in bench.load_json(ROOT, c["file"]))]
    theirs, f, r = rotary_table(big, FULL)
    np.testing.assert_allclose(theirs, inv.astype(np.float32), rtol=1e-6)
    assert (f, r) == (factor, rot)
    plain_inv, one, whole = rm.inverse_frequencies(
        dict(rope_type="default", rope_theta=10000,
             partial_rotary_factor=1), 128)
    theirs, f, r = rotary_table(big, SLIDING)
    np.testing.assert_allclose(theirs, plain_inv.astype(np.float32),
                               rtol=1e-6)
    assert (one, whole, f, r) == (1.0, 128, 1.0, 128)


def test_weights_come_from_the_seed_and_no_mechanism_is_idle(tiny):
    import jax.numpy as jnp

    a = rm.weights(tiny, 5, jnp.float32)
    b = rm.weights(tiny, 5, jnp.float32)
    c = rm.weights(tiny, 6, jnp.bfloat16)
    assert np.array_equal(a["lm_head"], b["lm_head"])
    assert not np.array_equal(a["lm_head"], c["lm_head"])
    assert c[rm.SPARSE]["gate_w"].dtype == jnp.bfloat16
    assert a[rm.SPARSE]["gate_w"].shape == (3, 4, 64, 32)
    gains = np.asarray(a[rm.SLIDING]["ln1"])
    assert abs(gains.mean() - 1) < 0.1 and 0.02 < gains.std() < 0.2
    # the deviations the file assumes: on a normalised input the router's
    # logits, the gate's and the sliding layers' scores deviate by about 1
    u = np.random.default_rng(0).standard_normal((400, 64))
    logits = u @ np.asarray(a[rm.SPARSE]["router_w"][0])
    assert 0.7 < logits.std() < 1.4
    gate = 1 / (1 + np.exp(-(u @ np.asarray(a[rm.SLIDING]["g_w"][0]))))
    assert 0.1 < np.quantile(gate, 0.1) and np.quantile(gate, 0.9) < 0.9
    q = (u @ np.asarray(a[rm.SLIDING]["q_w"][0])).reshape(400, 6, 16)
    k = (u @ np.asarray(a[rm.SLIDING]["k_w"][0])).reshape(400, 2, 16)
    scores = np.einsum("qhd,td->hqt", q, k[:, 0]) / 4
    assert 0.5 < scores.std() < 2


def test_the_routed_sum_by_expert_is_the_sum_token_by_token(tiny):
    """The reference lists the tokens of each held expert on the host and
    adds them back expert by expert; a loop over tokens and their choices
    says the same."""
    import jax.numpy as jnp

    params = rm.weights(tiny, 3, jnp.float32)
    model = rm.Model(tiny)
    s = model.s
    p = rm._at(params[rm.SPARSE], 1)
    h = jnp.asarray(np.random.default_rng(1).standard_normal((40, s.D)),
                    jnp.float32)
    u, shared, w, idx = model.route(p, h)
    rows, wr = model.held_rows(w, idx, 33)       # 7 rows of padding
    assert rows.shape[1] & (rows.shape[1] - 1) == 0    # a power of two
    got = np.asarray(model.routed(p, u, rows, wr))
    assert not got[33:].any()
    u, w, idx = np.asarray(u, np.float64), np.asarray(w), np.asarray(idx)
    np.testing.assert_allclose(w.sum(-1), s.routed_scale, rtol=1e-6)
    want = np.zeros_like(got, dtype=np.float64)
    for t in range(33):
        for we, e in zip(w[t], idx[t]):
            if not s.first <= e < s.first + s.held:
                continue                          # held on another chip
            g, up, down = (np.asarray(p[n][e - s.first], np.float64)
                           for n in ("gate_w", "up_w", "down_w"))
            a = u[t] @ g
            want[t] += we * ((a / (1 + np.exp(-a)) * (u[t] @ up)) @ down)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert np.abs(want[:33]).max() > 1e-3


def test_blocks_of_queries_and_of_positions_tile_the_result(
        tiny, monkeypatch):
    """Attention 8 queries at a time and the head 5 positions at a time
    give what they give in one piece."""
    import jax.numpy as jnp

    params = rm.weights(tiny, 3, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 512, 90)
    whole = np.asarray(rm.Model(tiny).forward_logits(params, toks, 70))
    monkeypatch.setattr(rm, "_HEAD_ROWS", 5)
    monkeypatch.setattr(rm, "_QUERY_ROWS", 8)
    parts = np.asarray(rm.Model(tiny, block=128).forward_logits(
        params, toks, 70))
    assert whole.shape == parts.shape == (21, 512)
    np.testing.assert_allclose(parts, whole, atol=1e-5)    # deviation 1


# ------------------------------------------------------------ the controls


def over(run):
    return {n for n, (v, lim, _) in run["compared"].items() if not v <= lim}


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    return bench.make_context(request.param, SEED, seconds=1.5,
                              rehearse=True)


def test_the_cell_is_the_issues(cell):
    ctx, _ = cell
    t, e = bench.load_json(bench.HERE, "traffic",
                           ctx["entry"]["traffic"] + ".json"), \
        bench.load_json(bench.HERE, "workloads", ctx["name"] + ".json")
    assert (t["clients"], t["cycle"], t["group"]) == (64, 64, 8)
    assert t["prompt"] == {"median": 2048, "sigma": 1.0, "min": 256,
                           "max": 16384}
    assert t["output"] == {"median": 384, "sigma": 0.6, "min": 64,
                           "max": 1536}
    assert t["shared_prefix"]["min_prompt"] > t["prompt"]["max"]
    eng = e["engine"]
    assert (eng["max_batch_size"], eng["chunk_len"]) == (64, 1024)
    # every row at the longest context the file allows, and every row's
    # window pages at their most: no preemption whatever the order
    assert eng["num_pages"] * eng["page_size"] >= 64 * 17920
    assert eng["num_window_pages"] >= 64 * (
        -(-(512 + 1024) // eng["page_size"]) + 1)
    assert set(e["limits"]) == set(e["rehearse"]["limits"]) == {
        "logit_gap_mean", "prefill_logit_err", "decode_logit_err"}


def test_sound_run_is_correct_and_counts_pairs_and_pages(cell):
    ctx, runner = cell
    run = runner.run(dict(ctx, t_start=0.0))
    assert run["correct"], (run["compared"], run["notes"])
    c = run["counts"]
    assert run["notes"]["requests_finished"] > 0
    assert 0 < c["selected_positions"] < c["context_positions"]
    assert c["expert_pairs"] > 0 and c["window_pages_released"] > 0
    # at most every held expert of every sparse layer, a step
    assert 0 < c["experts_read"] <= 3 * 4 * c["steps"]
    assert 0 < c["window_pages_held"] < c["full_pages_held"]
    assert 0 < c["window_rows"] <= c["context_rows"]
    assert c["first_tokens"] > 0 and c["prefix_hit_tokens"] == 0
    # the sampled requests' first and last logits were kept and compared
    assert all(len(rows) == 2 for rows in run["sample_logits"])
    # the readers of the new metrics find what they read (no trace here)
    run.update(config=bench.load_json(bench.HERE, "configs",
                                      ctx["cell"]["config"] + ".json"),
               chips=1, peak={"flops_per_s": 197e12, "bytes_per_s": 819e9})
    read = lambda name: bench.load_module("layers", name).read(run)
    assert 0 < read("moe_serve_step_mfu") < 100
    assert 0 < read("window_page_share") < 100
    for name in ("expert_matmul_roofline", "expert_matmul_device_share",
                 "window_attention_roofline"):
        assert read(name) is None
    run["trace"] = {"busy_s": 2.0, "window_s": 4.0, "ops": {
        "expert_matmul.3[mosaic]": 0.5,
        "ragged_paged_attention.7[mosaic]": 0.25,
        "ragged_paged_attention_window.9[mosaic]": 0.25, "fusion.1": 1.0}}
    assert read("expert_matmul_device_share") == 25.0
    assert read("expert_matmul_roofline") > 0
    assert read("window_attention_roofline") > 0
    # and the accepted serving readers the cell is listed under
    for m in MANIFEST["per_layer"]:
        if ctx["name"] in m.get("workloads", ()) \
                and m["name"] not in NEW_METRICS \
                and m["source"] != "device_trace":
            assert bench.load_module("layers", m["name"]).read(run) \
                is not None, m["name"]


def test_readers_find_nothing_in_another_runners_counts():
    run = {"counts": {"prefill_tokens": 5, "generated_tokens": 5},
           "trace": {"busy_s": 1.0, "window_s": 2.0,
                     "ops": {"fusion.1": 1.0}}}
    for name in NEW_METRICS:
        assert bench.load_module("layers", name).read(run) is None


def test_the_manifest_lists_the_new_metrics_for_the_new_cells_only():
    for name in NEW_METRICS:
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry["workloads"] == CELLS and entry["unit"] == "%"
        assert entry["moves"] == "serve_tokens_per_s"
    tokens = next(m for m in MANIFEST["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert set(CELLS) <= set(tokens["workloads"])


def test_control_in_float8_is_not_correct(cell):
    ctx, runner = cell
    lines = list(runner.readings(dict(ctx), [SEED], "float8", {SEED},
                                 set()))
    control = next(l for l in lines if l["kind"] == "control_float8")
    limits = ctx["cell"]["limits"]
    assert [n for n in limits if control["numbers"][n] > limits[n]], lines
    # the same reference in the configuration's own precision passes
    same = next(l for l in lines if l["kind"] == "control_bfloat16")
    assert not [n for n in limits if same["numbers"][n] > limits[n]], lines


@pytest.mark.parametrize("fault", ["window_511", "drop_pair"])
def test_a_part_of_the_mathematics_changed_is_not_correct(cell, fault):
    ctx, runner = cell
    run = runner.run(dict(ctx, t_start=0.0, fault=fault))
    assert not run["correct"] and over(run), run["compared"]

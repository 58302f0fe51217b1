"""CPU tests of what the state-space cell adds to the harness: the operation
and byte counts against hand counts, the reference's own pieces, and that
``correct`` can come out false — the control (the reference in float8 in
the program's place) and the two planted faults (the decay's dependence on
the token left out, the convolution window not carried) each fail a limit
at the rehearsal size.

    python -m pytest benchmark/tests/test_ssm.py -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reference_ssm, roofline_ssm  # noqa: E402
from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
SSM_CELLS = [w["name"] for w in MANIFEST["workloads"]
             if bench.load_json(bench.HERE, "workloads", w["name"]
                                + ".json")["runner"] == "serve_ssm"]
SEED = 2147483693


# ------------------------------------------------- operations and bytes


class _Small:
    D, F, Vp, L, H, Hkv, hd = 8, 32, 100, 3, 4, 2, 4
    Hs, P, N, G, d_ssm, in_width = 2, 4, 8, 2, 8, 2 * 8 + 2 * 16 + 2
    layer_matmul_params = reference_ssm.Sizes.layer_matmul_params
    head_params = reference_ssm.Sizes.head_params


def test_matrix_parameters_against_a_hand_count():
    s = _Small()
    # attention: q 8x16, k and v 8x8 each, o 16x8; state-space: in 8x50,
    # out 8x8; MLP 3 x 8x32
    assert s.layer_matmul_params() == (128 + 64 + 64 + 128) + (400 + 64) \
        + 768 == 1616
    assert s.head_params() == 800


def test_step_operations_against_a_hand_count():
    s = _Small()
    assert roofline_ssm.attention_ops(s, 50) == 4 * 4 * 4 * 50
    # per token 4 * 2 * 4 * 8 with the state; per pair C.B once a group
    # (2 * 2 * 8) and the weighted input once a head (2 * 2 * 4)
    assert roofline_ssm.scan_ops(s, 8, 23.0) == 256 * 8 + 48 * 23.0
    # 8 tokens of which 3 rows were owed a token
    assert roofline_ssm.step_flops(s, 8, 3, 50, 23.0) == (
        2 * 3 * 1616 * 8 + 2 * 800 * 3 + 3 * 3200 + 3 * (2048 + 1104.0))


def test_processed_tokens_are_the_prompts_and_the_decode_rows():
    counts = {"prefill_tokens": 6, "prefill_chunks": 1,
              "state_rows_decode": 2, "generated_tokens": 3}
    # a chunk of 6 and 2 decode rows: 8 tokens, 6 * 7 / 2 + 2 pairs
    assert roofline_ssm.processed(counts) == (8, 23.0)


def test_bytes_against_a_hand_count():
    s = _Small()
    # keys and values of 10 positions, 2 heads of 4 in bf16
    assert roofline_ssm.attention_bytes(s, 10) == 2 * 10 * 16
    # a float32 [2, 4, 8] state read and written for 3 row-steps
    assert roofline_ssm.scan_bytes(s, 3) == 2 * 3 * 64 * 4


# -------------------------------------------------- the reference's pieces


@pytest.fixture(scope="module")
def tiny():
    return bench.load_json(bench.HERE, "configs", "tiny-ssm.json")


def test_sizes_read_every_assumed_item_by_name(tiny):
    s = reference_ssm.Sizes(tiny)
    assert (s.Hs, s.P, s.N, s.G, s.K) == (4, 16, 16, 2, 4)
    assert (s.d_ssm, s.conv_channels, s.in_width) == (64, 128, 196)
    assert s.ssm_multipliers[3] == 0.5 and s.lm_head_multiplier == 1 / 128
    for gone in ("init_std", "a_range", "dt_range", "conv_range"):
        cut = dict(tiny, assumed={k: v for k, v in tiny["assumed"].items()
                                  if k != gone})
        with pytest.raises(KeyError):
            reference_ssm.Sizes(cut)
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        reference_ssm.Sizes(dict(tiny, mamba_d_ssm=60))


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]
                                    if "mamba_d_ssm" in bench.load_json(
                                        ROOT, c["file"])])
def test_published_widths_and_the_bytes_the_cut_was_reckoned_at(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    s = reference_ssm.Sizes(bench.load_json(ROOT, entry["file"]))
    assert (s.D, s.F, s.H, s.Hkv, s.hd, s.V) == (5120, 21504, 20, 4, 128,
                                                 261120)
    assert (s.Hs, s.P, s.N, s.G, s.K, s.d_ssm) == (32, 128, 256, 2, 4, 4096)
    assert s.in_width == 9248 and s.L == 6
    # a layer's 430.1 M parameters, the whole cut's 5.255 B: 10.51 GB
    assert s.n_params() == 5_254_594_112


def test_weights_come_from_the_seed_and_the_scan_is_neither_0_nor_1(tiny):
    import jax.numpy as jnp

    a = reference_ssm.weights(tiny, 5, jnp.float32)
    b = reference_ssm.weights(tiny, 5, jnp.float32)
    c = reference_ssm.weights(tiny, 6, jnp.bfloat16)
    assert np.array_equal(a["lm_head"], b["lm_head"])
    assert not np.array_equal(a["lm_head"], c["lm_head"])
    blocks = a["blocks"]
    gains = np.asarray(blocks["ssm_norm"])
    assert abs(gains.mean() - 1) < 0.1 and 0.02 < gains.std() < 0.2
    A = np.exp(np.asarray(blocks["A_log"]))
    step = np.log1p(np.exp(np.asarray(blocks["dt_bias"])))
    assert 1 <= A.min() and A.max() <= 16
    assert 1e-3 <= step.min() and step.max() <= 1e-1 * (1 + 1e-5)
    # a step's decay exp(-dt A): strictly between 0 and 1
    decay = np.exp(-step * A)
    assert 0.15 < decay.min() and decay.max() < 0.9995
    assert abs(np.asarray(blocks["conv_w"])).max() <= 0.5
    assert np.asarray(blocks["q_w"]).std() == pytest.approx(1.2, rel=0.05)
    # what sets the decay stays float32 in a bfloat16 model
    assert c["blocks"]["A_log"].dtype == jnp.float32
    assert c["blocks"]["in_w"].dtype == jnp.bfloat16


def test_a_leaf_drawn_in_blocks_has_its_shape_and_deviation(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(reference_ssm, "_DRAW_BYTES", 4 * 96 * 8)
    key = jax.random.key(1)
    w = np.asarray(reference_ssm._normal(key, (384, 8), 0.5, jnp.float32))
    assert w.shape == (384, 8) and w.std() == pytest.approx(0.5, rel=0.05)
    # four blocks of 96 rows, each its own draw
    assert not np.array_equal(w[:96], w[96:192])
    whole = np.asarray(reference_ssm._normal(key, (96, 8), 0.5,
                                             jnp.float32))
    assert whole.shape == (96, 8)


def test_the_heads_blocks_of_positions_and_ids_tile_the_logits(
        tiny, monkeypatch):
    """The head applied 5 positions and 256 ids at a time gives what it
    gives in one piece."""
    import jax.numpy as jnp

    params = reference_ssm.weights(tiny, 3, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 1024, 90)
    whole = np.asarray(reference_ssm.Model(tiny).forward_logits(
        params, toks, 70))
    monkeypatch.setattr(reference_ssm, "_HEAD_ROWS", 5)
    monkeypatch.setattr(reference_ssm, "_HEAD_COLS", 256)
    parts = np.asarray(reference_ssm.Model(tiny, block=128).forward_logits(
        params, toks, 70))
    assert whole.shape == parts.shape == (21, 1024)
    np.testing.assert_allclose(parts, whole, atol=1e-6)


# ------------------------------------------------------------ the controls


def over(run):
    return {n for n, (v, lim, _) in run["compared"].items() if not v <= lim}


@pytest.fixture(scope="module", params=SSM_CELLS)
def cell(request):
    return bench.make_context(request.param, SEED, seconds=1.5,
                              rehearse=True)


def test_sound_run_is_correct_and_counts_its_rows(cell):
    ctx, runner = cell
    run = runner.run(dict(ctx, t_start=0.0))
    assert run["correct"], (run["compared"], run["notes"])
    c = run["counts"]
    # a request that finished in the window may have begun before it
    assert c["state_resets"] > 0 and run["notes"]["requests_finished"] > 0
    assert c["state_rows_chunk"] == c["prefill_chunks"] > 0
    assert c["state_rows_chunk"] + c["state_rows_decode"] == c["rows"]
    assert c["prefix_hit_tokens"] == 0 and c["context_positions"] > 0
    # the readers of the new metrics find what they read (no trace here)
    run.update(config=ctx["config"] if not ctx["rehearse"] else
               bench.load_json(bench.HERE, "configs",
                               ctx["cell"]["config"] + ".json"),
               chips=1, peak={"flops_per_s": 197e12, "bytes_per_s": 819e9})
    mfu = bench.load_module("layers", "ssm_serve_step_mfu").read(run)
    assert 0 < mfu < 100
    for name in ("ssd_scan_roofline", "ssd_scan_device_share",
                 "grouped_attention_roofline"):
        assert bench.load_module("layers", name).read(run) is None
    run["trace"] = {"busy_s": 2.0, "window_s": 4.0, "ops": {
        "ssd_scan.3[mosaic]": 0.5, "ragged_paged_attention.7[mosaic]": 0.25,
        "fusion.1": 1.0}}
    assert bench.load_module("layers", "ssd_scan_device_share").read(run) \
        == 25.0
    assert bench.load_module("layers", "ssd_scan_roofline").read(run) > 0
    assert bench.load_module("layers",
                             "grouped_attention_roofline").read(run) > 0


def test_readers_find_nothing_in_another_runners_counts():
    run = {"counts": {"prefill_tokens": 5, "generated_tokens": 5},
           "trace": {"busy_s": 1.0, "window_s": 2.0,
                     "ops": {"fusion.1": 1.0}}}
    for name in ("ssm_serve_step_mfu", "ssd_scan_roofline",
                 "ssd_scan_device_share", "grouped_attention_roofline"):
        assert bench.load_module("layers", name).read(run) is None


def test_control_in_float8_is_not_correct(cell):
    ctx, runner = cell
    lines = list(runner.readings(dict(ctx), [SEED], "float8", {SEED},
                                 set()))
    control = next(l for l in lines if l["kind"] == "control_float8")
    limits = ctx["cell"]["limits"]
    assert [n for n in limits if control["numbers"][n] > limits[n]], lines


@pytest.mark.parametrize("fault", ["fixed_decay", "no_carry"])
def test_a_part_of_the_mathematics_left_out_is_not_correct(cell, fault):
    ctx, runner = cell
    run = runner.run(dict(ctx, t_start=0.0, fault=fault))
    assert not run["correct"] and over(run), run["compared"]

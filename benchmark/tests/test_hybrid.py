"""CPU tests of what the hybrid cell adds to the harness: the operation and
byte counts against hand counts, the reference's own pieces, and that
``correct`` can come out false — the control (the reference in float8 in
the program's place) and the two planted faults (selection left out, decay
left out) each fail a limit at the rehearsal size.

    python -m pytest benchmark/tests/test_hybrid.py -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reference_hybrid, roofline_hybrid  # noqa: E402
from benchmark import run as bench  # noqa: E402

HYBRID_CELLS = [w["name"] for w in bench.load_json(ROOT, "BENCHMARK.json")[
    "workloads"] if bench.load_json(bench.HERE, "workloads", w["name"]
                                    + ".json")["runner"] == "serve_hybrid"]
SEED = 2147483693


# ------------------------------------------------- operations and bytes


class _Small:
    D, F, Vp, H, Hkv, hd, Hl, hdl = 8, 32, 100, 4, 2, 4, 2, 4
    topk, block_size, dense_len, kernel_size, kernel_stride = 3, 4, 16, 2, 1
    mixers = ("sparse", "lightning", "lightning")
    count = reference_hybrid.Sizes.count
    matmul_params = reference_hybrid.Sizes.matmul_params


def test_matrix_parameters_against_a_hand_count():
    s = _Small()
    # sparse: q 8x16, k and v 8x8 each, gate 8x16, o 16x8, MLP 3 x 8x32
    sparse = 128 + 64 + 64 + 128 + 128 + 768
    # lightning: q, k, v, gate 8x8 each, o 8x8, MLP
    light = 5 * 64 + 768
    assert s.matmul_params() == sparse + 2 * light + 8 * 100 == 4256


def test_step_operations_against_a_hand_count():
    s = _Small()
    # a chunk of 6 tokens and 2 decode tokens: 6*7/2 + 2 pairs
    pairs = roofline_hybrid.chunk_pairs(6, 1, 2)
    assert pairs == 23.0
    assert roofline_hybrid.chunk_pairs(0, 0, 5) == 5.0
    # two chunks of 4 and 8 counted at the mean length 6: a lower bound
    assert roofline_hybrid.chunk_pairs(12, 2, 0) == 42.0 < 10 + 36
    assert roofline_hybrid.sparse_attention_ops(s, 50) == 4 * 4 * 4 * 50
    assert roofline_hybrid.lightning_ops(s, 8, 23.0) == (
        4 * 2 * 16 * 8 + 4 * 2 * 4 * 23.0)
    assert roofline_hybrid.step_flops(s, 8, 50, 23.0) == (
        2 * 4256 * 8 + 1 * 3200 + 2 * (1024 + 736.0))


def test_bytes_and_row_reads_against_a_hand_count():
    s = _Small()
    # keys and values of 10 positions, 2 heads of 4 in bf16, and 7 spans
    assert roofline_hybrid.sparse_attention_bytes(s, 10, 7) == (
        2 * 10 * 16 + 7 * 16)
    # a float32 [2, 4, 4] state read and written for 3 row-steps
    assert roofline_hybrid.lightning_bytes(s, 3) == 2 * 3 * 2 * 16 * 4
    assert roofline_hybrid.row_reads(s, 16) == (16, 0)      # dense
    assert roofline_hybrid.row_reads(s, 17) == (12, 16)     # 3 blocks of 4
    assert roofline_hybrid.row_reads(s, 40) == (12, 39)


# -------------------------------------------------- the reference's pieces


@pytest.fixture(scope="module")
def tiny():
    return bench.load_json(bench.HERE, "configs", "tiny-hybrid.json")


def test_sizes_read_every_assumed_item_by_name(tiny):
    s = reference_hybrid.Sizes(tiny)
    assert (s.kernel_size, s.kernel_stride, s.block_size, s.topk,
            s.init_blocks, s.window_size, s.dense_len) == (2, 1, 4, 6, 1,
                                                           8, 32)
    assert s.mixers == ("sparse", "lightning", "lightning", "sparse")
    assert abs(s.residual - 1.4 / 32 ** 0.5) < 1e-12
    assert s.logit_divisor == 16.0
    for gone in ("topk", "lightning_slope_power", "mup_depth"):
        cut = dict(tiny, assumed={k: v for k, v in tiny["assumed"].items()
                                  if k != gone})
        with pytest.raises(KeyError):
            reference_hybrid.Sizes(cut)


def test_spans_that_overlap_a_block_at_the_published_sizes():
    class S:
        kernel_size, kernel_stride, block_size = 32, 16, 64
    spans = reference_hybrid.overlapping_spans(S, 3)
    # block b: the spans that start at 64 b - 16 .. 64 b + 48
    assert spans.tolist() == [[0, 1, 2, 3, -1], [3, 4, 5, 6, 7],
                              [7, 8, 9, 10, 11]]


def test_weights_come_from_the_seed_with_gains_around_one(tiny):
    import jax.numpy as jnp

    a = reference_hybrid.weights(tiny, 5, jnp.float32)
    b = reference_hybrid.weights(tiny, 5, jnp.float32)
    c = reference_hybrid.weights(tiny, 6, jnp.float32)
    assert np.array_equal(a["lm_head"], b["lm_head"])
    assert not np.array_equal(a["lm_head"], c["lm_head"])
    gains = np.asarray(a["sparse"]["q_norm"])
    assert abs(gains.mean() - 1) < 0.1 and 0.02 < gains.std() < 0.2
    assert a["lightning"]["o_norm"].shape == (2, 16)
    assert "o_norm" not in a["sparse"]


def test_recurrence_in_blocks_equals_the_recurrence_token_by_token(tiny):
    """The lightning layer's masked quadratic form in blocks of 16 and in
    blocks of 64 agree: the state carried between blocks is the
    recurrence's."""
    import jax.numpy as jnp

    params = reference_hybrid.weights(tiny, 3, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 1000, 100)
    small = reference_hybrid.Model(tiny, "float32", block=64)
    large = reference_hybrid.Model(tiny, "float32", block=256)
    a = np.asarray(small.forward_logits(params, toks, 60))
    b = np.asarray(large.forward_logits(params, toks, 60))
    assert a.shape == (41, 1024)
    np.testing.assert_allclose(a, b, atol=2e-5)


# ------------------------------------------------------------ the controls


def over(run):
    return {n for n, (v, lim, _) in run["compared"].items() if not v <= lim}


@pytest.fixture(scope="module", params=HYBRID_CELLS)
def hybrid(request):
    return bench.make_context(request.param, SEED, seconds=1.5,
                              rehearse=True)


def test_sound_hybrid_run_is_correct(hybrid):
    ctx, runner = hybrid
    run = runner.run(dict(ctx, t_start=0.0))
    assert run["correct"], (run["compared"], run["notes"])
    c = run["counts"]
    assert 0 < c["selected_positions"] < c["context_positions"]
    assert c["state_resets"] >= run["notes"]["requests_finished"] > 0
    assert c["prefix_hit_tokens"] == 0 and c["read_rows"] > 0


def test_hybrid_control_in_float8_is_not_correct(hybrid):
    ctx, runner = hybrid
    lines = list(runner.readings(dict(ctx), [SEED], "float8", {SEED},
                                 set()))
    control = next(l for l in lines if l["kind"] == "control_float8")
    limits = ctx["cell"]["limits"]
    assert [n for n in limits if control["numbers"][n] > limits[n]], lines


@pytest.mark.parametrize("fault", ["dense", "no_decay"])
def test_a_part_of_the_mathematics_left_out_is_not_correct(hybrid, fault):
    ctx, runner = hybrid
    run = runner.run(dict(ctx, t_start=0.0, fault=fault))
    assert not run["correct"] and over(run), run["compared"]

"""CPU tests of what the latent-attention cell adds to the harness: the
operation and byte counts against hand counts, the new readers on a canned
run, the manifest's entries, the rehearsal of the cell end to end, and that
``correct`` can come out false — the control (the reference in float8 in
the program's place) and the three planted faults (the indexer's selection
left out, the newest positions in place of the indexer's choice, the
latents' rescale left out) each fail a limit at the rehearsal size.

    python -m pytest benchmark/tests/test_mla_sparse.py -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import reference_mla_sparse as rm  # noqa: E402
from benchmark import roofline_mla_sparse as roof  # noqa: E402
from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
CELL = "serve-dots3-note-prev-5l-closed16-16k"
SEED = 2147483693
NEW_METRICS = ("sparse_mla_serve_step_mfu", "latent_attention_roofline",
               "indexer_device_share", "latent_selection_page_fill")
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
read = lambda name, run: bench.load_module("layers", name).read(run)
BIG = bench.load_json(bench.HERE, "configs", "dots3-note-prev-5l.json")


def test_matrix_parameters_against_a_hand_count():
    s = rm.Sizes(BIG)
    # full: W_qa 5120x1024, W_qb 1024x128x192, W_kva 5120x576, W_UK and
    # W_UV 512x128x128 each, gate 5120x128, W_o 16384x5120
    assert s.attention_params(rm.FULL) == (
        5_242_880 + 25_165_824 + 2_949_120 + 2 * 8_388_608 + 655_360
        + 83_886_080) == 134_676_480
    # sliding: W_kva 5120x1088, W_UK 1024x64x192, W_UV 1024x64x128
    assert s.attention_params(rm.SLIDING) == (
        5_242_880 + 16_777_216 + 5_570_560 + 12_582_912 + 8_388_608
        + 327_680 + 41_943_040) == 90_832_896
    assert s.indexer_params() == 8_388_608 + 655_360 + 327_680
    assert s.expert_params() == 23_592_960
    assert s.n_params() == 4_087_087_104                # 8.17 GB in bf16


def test_step_operations_against_a_hand_count():
    s = rm.Sizes(BIG)
    # a full layer's query: [128, 576] x [576, r] and [128, r] x [r, 512]
    assert roof.row_ops(s, rm.FULL) == 2 * 128 * 1088
    assert roof.row_ops(s, rm.SLIDING) == 2 * 64 * 2112
    # 5 layers summed: 2 full layers of each 1,000 positions scored
    assert roof.indexer_ops(s, 5_000) == 2 * 64 * 128 * 2 * 1000
    one = roof.step_flops(s, 1, 0, 0, 0, 0, 0)
    assert one == 2 * roof.token_params(s)
    assert roof.step_flops(s, 1, 1, 5, 2, 3, 4) == one + (
        2 * s.head_params() + roof.indexer_ops(s, 5)
        + roof.attention_ops(s, 2, 3) + roof.expert_ops(s, 4))
    # rows at 576 and 1088 values in bf16; a query in and out per layer
    assert roof.latent_attention_bytes(s, 1, 10, 20) == 2 * (
        10 * 576 + 2 * 128 * (576 + 512) + 20 * 1088
        + 3 * 64 * (1088 + 1024))


def test_readers_on_a_canned_run_and_without_trace():
    counts = {"prefill_tokens": 512, "generated_tokens": 15,
              "first_tokens": 1, "context_positions": 5 * 527 * 16_000,
              "expert_pairs": 4 * 527 * 1, "experts_read": 128,
              "latent_rows_selected": 2 * 527 * 2048,
              "latent_rows_window": 3 * 527 * 513,
              "latent_row_pages": 2 * 527 * 200, "page_size": 64,
              "elapsed_s": 0.1}
    run = {"counts": counts, "config": BIG, "chips": 1, "peak": PEAK,
           "cell": {"runner": "serve_mla_sparse"}, "notes": {}}
    assert 0 < read("sparse_mla_serve_step_mfu", run) < 100
    assert read("latent_selection_page_fill", run) == pytest.approx(
        100 * 2048 / (200 * 64))
    assert read("latent_attention_roofline", run) is None
    assert read("indexer_device_share", dict(run, trace=None)) is None
    # 512 prompt tokens and 14 decode rows (a first token is a prompt's)
    run["trace"] = {"busy_s": 0.1, "window_s": 0.1, "ops": {
        "latent_attention.3[mosaic]": 0.02, "fusion.5": 0.01}}
    s = rm.Sizes(BIG)
    least = max(roof.attention_ops(s, 2 * 527 * 2048, 3 * 527 * 513)
                / 197e12, roof.latent_attention_bytes(
                    s, 526, 2 * 527 * 2048, 3 * 527 * 513) / 819e9)
    assert read("latent_attention_roofline", run) == pytest.approx(
        100 * least / 0.02)
    # another runner's counts: nothing to read
    other = {"expert_pairs": 5, "prefill_tokens": 5, "page_size": 64}
    for name in ("sparse_mla_serve_step_mfu", "latent_attention_roofline",
                 "latent_selection_page_fill"):
        assert read(name, dict(run, counts=other)) is None


def test_the_manifest_lists_the_new_metrics_and_the_readers_appended():
    for name in NEW_METRICS:
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_tokens_per_s"
    tokens = next(m for m in MANIFEST["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    appended = {m["name"] for m in MANIFEST["per_layer"]
                if CELL in m.get("workloads", ())} - set(NEW_METRICS)
    assert appended == {
        "sparse_selected_share", "serve_batch_occupancy",
        "serve_prefill_token_share", "device_idle_share.serve",
        "ttft_p95_ms", "serve_host_share", "serve_queue_wait_p95_ms",
        "device_scope_coverage.serve", "expert_matmul_device_share",
        "experts_outside_kernel_share", "window_page_share"}
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "closed16-long16k")


# ------------------------------------------------------------ the controls


def over(run):
    return {n for n, (v, lim, _) in run["compared"].items() if not v <= lim}


@pytest.fixture(scope="module")
def cell():
    return bench.make_context(CELL, SEED, seconds=1.5, rehearse=True)


def test_the_cell_is_as_specified(cell):
    e = bench.load_json(bench.HERE, "workloads", CELL + ".json")
    minicpm = bench.load_json(bench.HERE, "workloads",
                              "serve-minicpm-sala-8l-closed16-16k.json")
    assert e["engine"]["chunk_len"] == minicpm["engine"]["chunk_len"] == 512
    assert e["engine"]["max_batch_size"] == 16
    assert e["rehearse"]["config"] == "tiny-mla-sparse"
    assert set(e["limits"]) == set(e["rehearse"]["limits"]) == {
        "logit_gap_mean", "prefill_logit_err", "decode_logit_err"}
    # no preemption whatever the order: every row at the longest context
    eng, t = e["engine"], bench.load_json(bench.HERE, "traffic",
                                          "closed16-long16k.json")
    longest = t["prompt"]["max"] + t["output"]["max"]
    assert eng["num_pages"] * eng["page_size"] >= 16 * longest
    assert rm.Sizes(BIG).max_len >= longest


def test_sound_run_is_correct_and_counts_rows_and_pages(cell):
    ctx, runner = cell
    run = runner.run(dict(ctx, t_start=0.0))
    assert run["correct"], (run["compared"], run["notes"])
    c = run["counts"]
    assert c["expert_pairs"] > 0 and c["latent_rows_window"] > 0
    assert c["latent_rows_selected"] + c["latent_rows_window"] \
        == c["selected_positions"] < c["context_positions"]
    assert c["latent_row_pages"] * c["page_size"] \
        >= c["latent_rows_selected"] > 0
    assert all(len(rows) == 2 for rows in run["sample_logits"])
    run.update(config=bench.load_json(bench.HERE, "configs",
                                      "tiny-mla-sparse.json"),
               chips=1, peak=PEAK)
    for name in ("sparse_mla_serve_step_mfu", "latent_selection_page_fill"):
        assert 0 < read(name, run) <= 100, name
    # the accepted readers the cell is listed under that need no trace
    for m in MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_METRICS \
                and m["source"] != "device_trace":
            assert read(m["name"], run) is not None, m["name"]


def test_control_in_float8_is_not_correct(cell):
    ctx, runner = cell
    lines = list(runner.readings(dict(ctx), [SEED], "float8", {SEED},
                                 set()))
    control = next(l for l in lines if l["kind"] == "control_float8")
    limits = ctx["cell"]["limits"]
    assert [n for n in limits if control["numbers"][n] > limits[n]], lines
    same = next(l for l in lines if l["kind"] == "control_bfloat16")
    assert not [n for n in limits if same["numbers"][n] > limits[n]], lines


def plant(monkeypatch, fault):
    """The program with one of the reference's faults written in: a full
    layer's list of positions replaced by every position of the context
    or by the newest ``index_topk``, or the latents' rescale left out."""
    import jax.numpy as jnp

    from paddle_tpu.models import mla_sparse as ms

    if fault == "no_rescale":
        monkeypatch.setattr(ms.MLASparseConfig, "scales",
                            lambda self, kind: (1.0, 1.0))
        return
    sound = ms._selected

    def selected(scores, valid, pos, k, page_size):
        _, _, pages = sound(scores, valid, pos, k, page_size)
        k = scores.shape[1] if fault == "no_selection" else k
        newest = jnp.maximum(pos[:, None] - jnp.arange(k)[None, :], 0)
        counts = jnp.where(valid, jnp.minimum(pos + 1, k), 0)
        return newest.astype(jnp.int32), counts.astype(jnp.int32), pages

    monkeypatch.setattr(ms, "_selected", selected)


@pytest.mark.parametrize("fault", ["no_selection", "newest", "no_rescale"])
def test_a_part_of_the_mathematics_changed_is_not_correct(cell, fault,
                                                          monkeypatch):
    ctx, runner = cell
    plant(monkeypatch, fault)
    run = runner.run(dict(ctx, t_start=0.0))
    assert not run["correct"] and over(run), run["compared"]


def test_calibration_scores_the_reference_with_each_fault(cell):
    """What ``calibrate.py`` reads on the chip for the faults: the float32
    reference with the fault in the program's place, at the sound run's
    requests; each fails a limit."""
    ctx, runner = cell
    lines = list(runner.readings(dict(ctx), [SEED], "float8", set(),
                                 {SEED}))
    limits = ctx["cell"]["limits"]
    assert [l["kind"] for l in lines[1:]] == [
        "fault_" + f for f in rm.FAULTS]
    for line in lines[1:]:
        assert [n for n in limits if line["numbers"][n] > limits[n]], line

"""CPU tests of the harness's own arithmetic and wiring.

    python -m pytest benchmark/tests -q

Nothing here measures anything: the trace reduction is checked on hand-built
intervals, the operation and byte counts against hand counts, and every cell
in ``BENCHMARK.json`` is resolved to files that exist.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import roofline, trace  # noqa: E402
from benchmark import run as bench  # noqa: E402

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


# ------------------------------------------------------- trace reduction


def test_union_merges_overlaps_and_keeps_holes():
    assert trace.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert trace.union_seconds([(3, 4), (0, 1)]) == 2.0
    assert trace.union_seconds([]) == 0.0


def test_gaps_are_what_the_union_leaves_of_the_window():
    assert trace.gaps([(1, 2), (1.5, 3), (5, 6)], (0, 8)) == [
        (0, 1), (3, 5), (6, 8)]
    assert trace.gaps([(-1, 9)], (0, 8)) == []
    assert trace.gaps([], (0, 8)) == [(0, 8)]


def test_self_seconds_takes_children_out_of_a_loop():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion"),
              (4.0, 6.0, "kernel"), (7.0, 9.0, "fusion"),
              (10.0, 12.0, "copy")]
    assert trace.self_seconds(events) == {
        "while": 3.0, "fusion": 5.0, "kernel": 2.0, "copy": 2.0}


def test_a_gap_is_named_after_the_span_that_covers_most_of_it():
    spans = [(0.0, 2.0, "feed"), (2.0, 9.0, "wait")]
    assert trace.label((1.5, 4.0), spans) == "wait"
    assert trace.label((0.0, 1.0), spans) == "feed"
    assert trace.label((20.0, 21.0), spans) == "between spans"


def test_short_names_keep_the_instruction_and_mark_pallas_calls():
    fusion = "%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %p), kind=kLoop"
    kernel = ('%closed_call.14 = (bf16[128,2048,128]) custom-call(bf16[1] '
              '%x), custom_call_target="tpu_custom_call", frontend={}')
    assert trace.short(fusion) == "fusion.12"
    assert trace.short(kernel) == "closed_call.14[mosaic]"
    assert trace.short("%while.25") == "while.25"


def test_reduce_clips_to_the_window_and_averages_devices():
    dev0 = [(-1.0, 1.0, "a"), (2.0, 3.0, "b"), (9.0, 11.0, "a")]
    dev1 = [(0.0, 10.0, "a")]
    out = trace.reduce([dev0, dev1], [(0.0, 10.0, "step")], (0.0, 10.0))
    assert out["window_s"] == 10.0
    assert out["busy_s"] == (3.0 + 10.0) / 2
    assert out["ops"] == {"a": 12.0, "b": 1.0}
    assert out["idle_by_span"] == [("step", 7.0)]
    assert out["longest_gaps"][0] == ("step", 6.0)


# ------------------------------------------------- operations and bytes


class _Small:
    D, L, H, F, Vp, hd = 8, 2, 2, 32, 100, 4


def test_training_operations_against_a_hand_count():
    s = _Small()
    matrices = 2 * (3 * 64 + 64 + 2 * 8 * 32) + 100 * 8
    assert roofline.matmul_params(s) == matrices == 2336
    # 6 per matrix parameter, and 6 * L * seq * D for causal attention
    assert roofline.train_flops_per_token(s, 16) == 6 * 2336 + 6 * 2 * 16 * 8
    assert roofline.forward_flops_per_token(s, 10) == (
        2 * 2336 + 4 * 2 * 10 * 8)


def test_attention_call_against_a_hand_count():
    call = roofline.causal_attention_call(batch=2, heads=3, seq=8,
                                          head_size=4)
    unit = 2 * 2 * 3 * 32 * 4          # one product over half the square
    array = 2 * 3 * 8 * 4 * 2          # one bf16 [b, h, s, d] array
    assert call["forward"] == (2 * unit, 4 * array)
    assert call["backward"] == (4 * unit, 8 * array)


def test_least_seconds_names_its_bound_and_unknown_kinds_fail():
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.least_seconds(197e12, 1, peak) == (1.0, "operations")
    assert roofline.least_seconds(1, 819e9, peak) == (1.0, "bytes")
    with pytest.raises(SystemExit):
        roofline.peaks("cpu")


# --------------------------------------------------------------- wiring


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_that_exist(cell):
    found = bench.resolve(MANIFEST, cell)
    assert os.path.isfile(os.path.join(
        bench.HERE, "runners", found["cell"]["runner"] + ".py"))
    assert found["config"]["n_embd"] and found["traffic"]["kind"]
    assert "rehearse" in found["traffic"]
    assert {m["name"] for m in found["end_to_end"]} >= {"setup_s"}
    assert len(found["end_to_end"]) >= 2 and found["per_layer"]
    for m in found["per_layer"]:
        assert callable(bench.load_module("layers", m["name"]).read)
        assert m["moves"] in {e["name"] for e in found["end_to_end"]}
    runner = bench.load_module("runners", found["cell"]["runner"])
    assert set(found["cell"]["limits"]) == set(runner.COMPARED)


def test_no_cell_or_configuration_is_named_in_code():
    names = CELLS + [c["name"] for c in MANIFEST["configs"]]
    for folder, _, files in os.walk(bench.HERE):
        if os.path.basename(folder) == "tests":
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(folder, f)).read()
                assert not [n for n in names if n in text], (f, names)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_line_and_no_metric(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(bench.HERE, "run.py"), "--workload",
         cell, "--seed", "2200000123", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["attempted"] > 0
    for name, pair in line["compared"].items():
        assert pair["value"] <= pair["limit"], name
    assert "compared " in proc.stderr.strip().splitlines()[-1]


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(bench.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr

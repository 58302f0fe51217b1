"""CPU tests of the readers that join the reduced trace with the program's
table of its compiled step (``scope_share.py``), on a hand-built trace and a
hand-built table, and once on the table of a real (tiny) serving engine.

    python -m pytest benchmark/tests -q
"""
import io
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402
from benchmark import scope_share  # noqa: E402
from paddle_tpu.observability import compile_watchdog  # noqa: E402
from paddle_tpu.observability.metrics import (Counter,  # noqa: E402
                                              default_registry)

MANIFEST = bench.load_json(ROOT, "BENCHMARK.json")
NEW = ("device_scope_coverage.train", "device_scope_coverage.serve",
       "train_recompute_device_share", "experts_outside_kernel_share",
       "select_device_share", "ssm_outside_kernel_share",
       "sparse_attention_item_fill")
READERS = {name: bench.load_module("layers", name).read for name in NEW}

STEP = "jit(_step)/jit(step)/"
TRAIN = "jit(_step_local)/forward_backward/"
TABLE = {
    # a serving step: an expert layer, a selection, a state-space mixer
    "fusion.1": STEP + "sparse_mlp/experts/gather",
    "expert_matmul.2": STEP + "sparse_mlp/experts/expert_matmul/pallas_call",
    "fusion.3": STEP + "sparse_attn/select/td,de->te/dot_general",
    "sort.4": STEP + "sparse_attn/select/top_k",
    "fusion.5": STEP + "sparse_attn/work_list/cumsum",
    "fusion.6": STEP + "while/body/closed_call/ssm/td,de->te/dot_general",
    "copy.7": STEP + "while/body/closed_call/ssm/state_write/transpose",
    "ssd_scan.8": STEP + "while/body/closed_call/ssm/state_write/ssd_scan/"
                         "pallas_call",
    "fusion.9": STEP + "while/body/dynamic_slice",        # no scope on it
    "copy.10": "",                                        # no metadata
    # a train step: one layer's attention in its three passes
    "flash_fwd.16": TRAIN + "jvp()/while/body/closed_call/attn/flash_fwd/"
                            "pallas_call",
    "flash_fwd.17": TRAIN + "transpose(jvp())/while/body/closed_call/"
                            "checkpoint/rematted_computation/attn/flash_fwd/"
                            "pallas_call",
    "fusion.18": TRAIN + "transpose(jvp())/while/body/closed_call/"
                         "checkpoint/rematted_computation/mlp/bsd,df->bsf/"
                         "dot_general",
    "fusion.19": TRAIN + "transpose(jvp())/while/body/closed_call/"
                         "checkpoint/mlp/bsd,df->bsf/dot_general",
}
OPS = {"fusion.1": 2.0, "expert_matmul.2[mosaic]": 3.0, "fusion.3": 1.0,
       "sort.4": 0.5, "fusion.5": 0.25, "fusion.6": 1.5, "copy.7": 0.5,
       "ssd_scan.8[mosaic]": 4.0, "fusion.9": 0.75, "copy.10": 0.5,
       "flash_fwd.16[mosaic]": 1.0, "flash_fwd.17[mosaic]": 1.0,
       "fusion.18": 2.0, "fusion.19": 3.0,
       "fusion.77": 1.0}                 # another program's: not in the table
BUSY = sum(OPS.values())                 # 22.0


def _run(runner="serve", ops=OPS):
    trace = None if ops is None else {"ops": dict(ops), "busy_s": BUSY,
                                      "window_s": BUSY + 1.0}
    return {"cell": {"runner": runner}, "trace": trace, "counts": {},
            "notes": {}}


@pytest.fixture
def table(monkeypatch):
    """The program's ``instruction_table`` answering with ``TABLE`` for
    both step names, and counting its requests."""
    asked = []

    def fake(name):
        asked.append(name)
        return dict(TABLE)

    monkeypatch.setattr(compile_watchdog, "instruction_table", fake)
    return asked


def test_seconds_filters_by_scope_kernel_and_pass(table):
    run = _run()
    sec = scope_share.seconds
    assert sec(run) == BUSY - 1.0            # all but the stranger
    assert sec(run, under=("experts",)) == 5.0
    assert sec(run, under=("experts",), mosaic=False) == 2.0
    assert sec(run, under=("experts",), mosaic=True) == 3.0
    assert sec(run, under=("select",)) == 1.5
    assert sec(run, under=("work_list",)) == 0.25
    assert sec(run, under=("ssm",), mosaic=False) == 2.0
    assert sec(run, under=("select", "work_list")) == 1.75
    assert sec(run, passes=("recompute",)) == 3.0
    assert sec(run, passes=("recompute",), mosaic=True) == 1.0
    assert sec(run, passes=("forward",)) == 1.0
    assert sec(run, passes=("backward", "recompute")) == 6.0
    # without a scope, without metadata and not in the table: coverage only
    assert sec(run, under=scope_share.ANY) == BUSY - 1.0 - 0.75 - 0.5
    assert table == [scope_share.SERVE_STEP]         # asked once a run
    assert scope_share.share(run, under=("select",)) == 100 * 1.5 / BUSY


def test_each_new_reader_on_the_made_up_run(table, capsys):
    serve, train = _run("serve_moe_window"), _run("train")
    covered = 100 * (BUSY - 2.25) / BUSY
    assert READERS["device_scope_coverage.serve"](serve) == covered
    assert READERS["device_scope_coverage.train"](train) == covered
    assert READERS["train_recompute_device_share"](train) == 100 * 3 / BUSY
    assert READERS["experts_outside_kernel_share"](serve) == 100 * 2 / BUSY
    assert READERS["select_device_share"](serve) == 100 * 1.5 / BUSY
    assert READERS["ssm_outside_kernel_share"](serve) == 100 * 2 / BUSY
    assert table == [scope_share.SERVE_STEP, scope_share.TRAIN_STEP]
    err = capsys.readouterr().err
    assert "scope_share: table of 'serving::unified_step': 14 instr" in err
    assert ("scope_share: sparse_mlp/experts | - | 5.0000 | 22.73 | 3.0000 |"
            in err)
    assert "scope_share: unnamed fusion.77 1.0000 s (not in the table)" in err


def test_by_scope_splits_mosaic_products_and_other(table):
    grouped = scope_share.by_scope(_run("train"))
    assert grouped[("sparse_mlp/experts", None)] == [3.0, 0.0, 2.0]
    assert grouped[("sparse_attn/select", None)] == [0.0, 1.0, 0.5]
    assert grouped[("forward_backward/attn", "recompute")] == [1.0, 0.0, 0.0]
    assert grouped[("ssm/state_write", None)] == [4.0, 0.0, 0.5]
    assert grouped[("forward_backward/mlp", "recompute")] == [0.0, 2.0, 0.0]
    assert grouped[("forward_backward/mlp", "backward")] == [0.0, 3.0, 0.0]
    assert grouped[("(no scope)", None)] == [0.0, 0.0, 1.25]
    assert grouped[("(not in the table)", None)] == [0.0, 0.0, 1.0]
    assert sum(sum(v) for v in grouped.values()) == BUSY
    out = io.StringIO()
    scope_share.report(_run("train"), top=3, file=out)
    assert len(out.getvalue().splitlines()) == 1 + 3 + 3


def test_nothing_without_trace_or_table(monkeypatch, table):
    for name in NEW[:-1]:
        assert READERS[name](_run(ops=None)) is None, name
    # a program whose step nobody described, and one from before the table
    monkeypatch.setattr(compile_watchdog, "instruction_table",
                        lambda name: None)
    assert scope_share.seconds(_run()) is None
    for name in NEW[:-1]:
        assert READERS[name](_run()) is None, name
    monkeypatch.delattr(compile_watchdog, "instruction_table")
    assert scope_share.table(_run()) is None
    assert READERS["select_device_share"](_run()) is None


def test_item_fill_is_pages_over_items_of_the_process():
    reg = default_registry()
    names = ("serving_attention_items_total",
             "serving_attention_item_pages_total")
    before = {n: reg.get(n) for n in names}
    try:
        for n in names:
            reg.unregister(n)
        assert READERS["sparse_attention_item_fill"](_run()) is None
        items = reg.register(Counter(names[0]))
        pages = reg.register(Counter(names[1]))
        assert READERS["sparse_attention_item_fill"](_run()) is None
        items.inc(712)
        pages.inc(5588)
        assert READERS["sparse_attention_item_fill"](_run()) == 5588 / 712
    finally:
        for n, old in before.items():
            reg.unregister(n)
            if old is not None:
                reg.register(old)


def test_the_seven_are_listed_with_their_cells_and_files():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-7:] == list(NEW)
    for name in NEW:
        m = listed[name]
        assert set(m["workloads"]) <= set(cells)
        runners = {bench.resolve(MANIFEST, w)["cell"]["runner"]
                   for w in m["workloads"]}
        assert ("train" in runners) == (m["moves"] == "train_tokens_per_s")
        assert len(runners) == len(m["workloads"]) or name.startswith(
            ("device_scope_coverage", "train_recompute"))


def test_a_real_engines_table_joins_a_trace_of_its_own_names():
    """The tiny GPT engine's table against a trace in which every one of
    its instructions took a second: coverage is the share of instructions
    that lie under a scope, and the attention scope is in it."""
    import jax

    from paddle_tpu.models.gpt import GPT_CONFIGS
    from paddle_tpu.serving import Engine

    eng = Engine(GPT_CONFIGS["tiny"], page_size=4, num_pages=16,
                 max_batch_size=2, chunk_len=4)
    del eng
    run = _run(ops={})
    names = scope_share.table(run)
    assert names and "scope_table" in run
    run["trace"]["ops"] = dict.fromkeys(names, 1.0)
    run["trace"]["busy_s"] = float(len(names))
    covered = READERS["device_scope_coverage.serve"](run)
    scoped = sum(bool(compile_watchdog.named_scopes(p))
                 for p in names.values())
    assert covered == 100.0 * scoped / len(names) and 0 < covered < 100
    assert scope_share.seconds(run, under=("attn",)) >= 10
    assert scope_share.seconds(run, under=("kv_write",)) < \
        scope_share.seconds(run, under=("attn",))
    assert jax.default_backend() == "cpu"


def test_an_untraced_rehearsal_never_asks_for_a_lowering():
    """``--trace 0``: no reader runs, and neither the engine nor the runner
    lowers anything ahead of time (``jit(...).lower`` raises here)."""
    code = (
        "import sys, jax\n"
        "from unittest import mock\n"
        "sys.path.insert(0, %r)\n"
        "from benchmark import run as bench\n"
        "def boom(*a, **k):\n"
        "    raise AssertionError('a lowering was requested')\n"
        "with mock.patch.object(jax.stages.Traced, 'lower', boom):\n"
        "    sys.exit(bench.main(['--workload', %r, '--seed', '2200000123',"
        " '--seconds', '1', '--trace', '0', '--rehearse']))\n"
        % (ROOT, next(w["name"] for w in MANIFEST["workloads"]
                      if w["name"].startswith("serve"))))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "scope_share" not in proc.stderr

#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It resolves the cell named in ``BENCHMARK.json`` to data files — the cell's
own (``workloads/<cell>.json``: the runner and how the program is set up),
its configuration (``configs/``) and its traffic mix (``traffic/``) — loads
the runner (``runners/<runner>.py``), lets it set up, measure for
``--seconds`` and compare what the timed path produced with the plain
reference, and prints one JSON object as the last line of standard output.
With ``--trace 1`` the window is traced, the trace reduced (``trace.py``) and
each per-layer metric the manifest lists for the cell read by its own file
(``layers/<metric>.py``).

One process, on the machine it is started on.  It fails, printing no result,
unless jax finds a TPU with the chips the cell asks for and a device kind
that ``roofline.py`` has peaks for.  ``--rehearse`` swaps in the tiny
configuration and the traffic file's rehearsal sizes and allows the CPU: it
checks control flow only and prints no metric at all.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # the process's start, for the notes

import argparse
import importlib.util
import json
import logging
import os
import re
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileLog(logging.Handler):
    """Every program jax hands to the compiler, by name, from the log of
    its persistent cache (a hit and a miss are both a compile request; the
    program's own watchdog keys on shapes and misses recompiles)."""

    _PAT = re.compile(r"(cache hit|CACHE MISS) for '([^']+)'")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.hits, self.misses = [], []
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def emit(self, record):
        m = self._PAT.search(record.getMessage())
        if m:
            (self.hits if m.group(1) == "cache hit"
             else self.misses).append(m.group(2))

    def count(self):
        return len(self.hits) + len(self.misses)


def resolve(manifest, workload):
    """The cell's entry, its files and the metrics it reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: BENCHMARK.json has no workload "
                         f"{workload!r} (it has {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    here = lambda m: "workloads" not in m or workload in m["workloads"]
    return {
        "entry": entry,
        "cell": load_json(HERE, "workloads", workload + ".json"),
        "config": load_json(ROOT, configs[entry["config"]]["file"]),
        "traffic": load_json(HERE, "traffic", entry["traffic"] + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"] if here(m)],
        "per_layer": [m for m in manifest["per_layer"] if here(m)],
    }


def find_devices(chips, rehearse):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if rehearse:
        return devices[:chips], None
    if d0.platform != "tpu":
        raise SystemExit(f"benchmark: jax found {devices}: no TPU, no "
                         f"numbers (this command never measures a CPU)")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips and "
                         f"jax found {len(devices)}")
    from benchmark import roofline

    return devices[:chips], roofline.peaks(d0.device_kind)


def make_context(workload, seed, seconds=None, trace_dir=None,
                 rehearse=False):
    """``(ctx, runner)``: everything a runner needs for one run of a cell."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    found = resolve(manifest, workload)
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        found["config"] = load_json(HERE, "configs", "tiny.json")
        found["traffic"] = dict(found["traffic"],
                                **found["traffic"]["rehearse"])
        found["cell"] = dict(found["cell"],
                             **found["cell"].get("rehearse", {}))
    runner = load_module("runners", found["cell"]["runner"])
    devices, peak = find_devices(found["entry"]["chips"], rehearse)
    # Set-up is counted from here: the interpreter, jax and the chip's
    # runtime are up, and neither the benchmark nor the program has a hand
    # in how long that took (7 to 10 s of runtime alone, swinging by 3 s on
    # one machine: PERF.md).  What follows is theirs: the program's import,
    # weights, engine, first steps or warm-up, compile or cache read.
    t_setup = time.perf_counter()
    try:
        from paddle_tpu.core.compile_cache import use_compile_cache
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not beside the "
                         f"benchmark ({e}); nothing to measure")
    cache_dir = use_compile_cache()
    # the program keeps only programs that took half a second to compile;
    # a run builds some thirty smaller ones, and set-up is paid by every
    # run of every check: keep them all
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_log = CompileLog()
    ctx = {
        **found, "name": workload, "seed": seed,
        "seconds": (seconds if seconds is not None
                    else manifest["run_seconds"]),
        "trace_dir": trace_dir, "rehearse": rehearse, "devices": devices,
        "peak": peak, "chips": found["entry"]["chips"], "t_start": t_setup,
        "platform_up_s": t_setup - T_START,
        "compile_log": compile_log, "cache_dir": cache_dir,
    }
    return ctx, runner


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb there, for a "
                         "look by hand (python benchmark/trace.py FILE)")
    args = ap.parse_args(argv)

    trace_dir = tempfile.mkdtemp(prefix="trace-") if args.trace else None
    ctx, runner = make_context(args.workload, args.seed, args.seconds,
                               trace_dir, args.rehearse)
    devices = ctx["devices"]
    try:
        run = runner.run(ctx)
        if trace_dir is not None:
            from benchmark import trace

            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(trace.find_xplane(trace_dir), args.keep_trace)
            events, spans = trace.load_xplane(
                trace.find_xplane(trace_dir),
                set(runner.SPANS) | {runner.WINDOW_SPAN})
            window = [(s, e) for s, e, n in spans
                      if n == runner.WINDOW_SPAN]
            if not window:
                raise SystemExit("benchmark: the trace holds no window span")
            run["trace"] = trace.reduce(
                events, [sp for sp in spans if sp[2] != runner.WINDOW_SPAN],
                window[0])
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run.update({k: ctx[k] for k in ("peak", "chips", "config", "traffic",
                                    "cell")})

    metrics = {}
    if not args.rehearse:
        if args.trace:
            for m in ctx["per_layer"]:
                value = load_module("layers", m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in ctx["end_to_end"]:
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                      "unit": m["unit"]}

    import jax

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    compared = {n: {"value": v, "limit": lim}
                for n, (v, lim, _) in run["compared"].items()}
    result = {"correct": bool(run["correct"]),
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        tr = run["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_by_span"]}
    result["notes"] = dict(run.get("notes", {}),
                           platform_up_s=ctx["platform_up_s"])
    result["compared"] = compared
    for n, (v, lim, detail) in run["compared"].items():
        print(f"compared {n} {v:.6g} limit {lim:.6g} "
              f"{'ok' if v <= lim else 'OVER'} ({detail})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process on the
chip: for each seed the program's numbers against the reference, and for
the seeds asked the control's (the reference in the program's place, in a
lower precision) and each planted fault's.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control float8 --control-seeds 1,2,3 --fault-seeds 1,2,3 \\
        --out chiprun_out/readings.jsonl

Prints one JSON line per reading; the runner of the cell decides what a
reading is (``readings(ctx, seed, controls, faults)``).  The benchmark's own
runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench  # noqa: E402


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control", default="float8")
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    ctx, runner = bench.make_context(args.workload, None, args.seconds,
                                     rehearse=args.rehearse)
    out = open(args.out, "a") if args.out else None
    try:
        for line in runner.readings(ctx, args.seeds, args.control,
                                    set(args.control_seeds),
                                    set(args.fault_seeds)):
            line["workload"] = args.workload
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

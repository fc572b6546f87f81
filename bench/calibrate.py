#!/usr/bin/env python3
"""Readings a cell's limits are set from: the program's and the control's.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \
        [--control-seeds 3] [--seconds 1] [--out file.jsonl]

For each seed, one process runs the cell's window (short: at least the
window's minimum of work) and compares what it produced with the
float64 reference, as a benchmark run does; these are the lower
readings.  For the first ``--control-seeds`` seeds it also compares the
control, the same reference computed in bfloat16, on the same inputs;
these are the upper readings.  The benchmark's own runs never run the
control.  Set-up is paid once: later seeds reuse the compiled programs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402

harness.add_paths()


def seed_list(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def readings(files, seeds, control_seeds: int, seconds: float, *,
             emit=print):
    from ref.precision import BF16
    harness.enable_compile_cache()
    harness.devices(files["entry"]["chips"])
    drv = harness.driver(files["cell"])
    span = harness.span_factory(False)
    for i, seed in enumerate(seeds):
        sess = drv.Session(files["config"], files["cell"], seed)
        if i == 0:
            sess.setup(span)
        t0 = time.perf_counter()
        sess.window(seconds, span)
        sess.release()
        row = {"seed": seed, "window_s": time.perf_counter() - t0,
               "program": {c["name"]: c["value"] for c in sess.check()}}
        if i < control_seeds:
            t1 = time.perf_counter()
            row["control"] = {c["name"]: c["value"]
                              for c in sess.check(q=BF16)}
            row["control_s"] = time.perf_counter() - t1
        emit(json.dumps(row))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    files = harness.cell_files(args.workload)
    fh = open(args.out, "a") if args.out else None

    def emit(line):
        print(line, flush=True)
        if fh:
            fh.write(line + "\n")
            fh.flush()

    try:
        readings(files, seed_list(args.seeds), args.control_seeds,
                 args.seconds, emit=emit)
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    finally:
        if fh:
            fh.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

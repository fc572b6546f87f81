#!/usr/bin/env python3
"""The benchmark: one cell, one seed, one window, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, the chip, the compile cache, the cell's inputs from the
seed, one warm-up pass over every shape the window uses) ends where the
window starts; ``setup_s`` runs from the process's start to there.  The
window then drives the cell for ``--seconds``, with the persistent
compile cache closed: whatever it compiles, it compiles in every run.
With ``--trace 1`` a window of the cell's ``trace_seconds`` (at most
``--seconds``) runs under ``jax.profiler`` and the result carries the
cell's per-layer metrics, read from the trace, and a breakdown;
otherwise it carries the end-to-end metrics.  After the window the
program's answers are compared with the plain float64 reference
(``ref/``); each number compared and its limit are printed last on
standard error and, under ``checks``, last in the result line, which is
the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402

harness.add_paths()


class Context:
    """What a per-layer reader sees: the reduced trace, the driver's
    counts of the window, the chip's peaks, and notes to print."""

    def __init__(self, trace, stats, peak):
        self.trace, self.stats, self.peak = trace, stats, peak
        self.notes = {}

    def program_s(self, program: str) -> float:
        return 0.0 if self.trace is None else self.trace.program_s.get(
            program, 0.0)

    def kernel_s(self, program: str, pattern: str) -> float:
        return 0.0 if self.trace is None else self.trace.ops_in(program,
                                                                pattern)

    def note(self, key, value) -> None:
        self.notes[key] = value


def run_cell(files, seed: int, seconds: float, trace: bool, *,
             t_start: float = None, log=print) -> dict:
    """One run of a cell; returns the result line's object."""
    t_start = time.time() if t_start is None else t_start
    harness.enable_compile_cache()
    import jax
    devs = harness.devices(files["entry"]["chips"])
    import costs
    peak = costs.peaks(devs[0].device_kind)
    meter = harness.CompileMeter()
    drv = harness.driver(files["cell"])
    sess = drv.Session(files["config"], files["cell"], seed)
    span = harness.span_factory(trace)
    sess.setup(span)
    harness.drain()
    setup = meter.take()
    setup_s = time.time() - t_start
    harness.close_compile_cache()
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # a traced window of its own, long enough for the per-layer
        # readers' averages and short enough to export and read in time
        seconds = min(seconds, files["cell"]["trace_seconds"])
    timing = {}
    try:
        if trace:
            # no Python function tracer: it records every call and would
            # bloat the trace a hundredfold; the harness's spans remain
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(logdir, profiler_options=opts)
        with span("bench.window"):
            e2e = sess.window(seconds, span)
            harness.drain()
        in_window = meter.take()
        if trace:
            t0 = time.time()
            jax.profiler.stop_trace()
            timing["trace_stop_s"] = time.time() - t0
        log(f"# compiles inside the window: {in_window['compiles']} "
            f"({in_window['compile_s']:.3f} s, {in_window['cache_hits']} of "
            f"them loaded from the compile cache); set-up: "
            f"{setup['compiles']} ({setup['compile_s']:.3f} s, "
            f"{setup['cache_hits']} from the cache)")
        device = harness.device_info(devs)
        bench = files["bench"]
        name = files["entry"]["name"]
        out = {"correct": None, "attempted": e2e["attempted"],
               "failed": e2e["failed"]}
        if trace:
            import trace_reduce
            t0 = time.time()
            loaded = trace_reduce.load(trace_reduce.find_xplane(logdir))
            timing["trace_load_s"] = time.time() - t0
            red = trace_reduce.reduce(loaded)
            timing["trace_reduce_s"] = time.time() - t0 - timing[
                "trace_load_s"]
            ctx = Context(red, sess.context(), peak)
            out["metrics"] = harness.read_per_layer(bench, name, ctx)
            device.update(busy_s=red.busy_s, window_s=red.window_s)
            out["breakdown"] = trace_reduce.breakdown(red)
            if ctx.notes:
                out["notes"] = ctx.notes
        else:
            values = dict(e2e, setup_s=setup_s)
            out["metrics"] = {
                m["name"]: {"value": float(values[m["name"]]),
                            "unit": m["unit"]}
                for m in harness.cell_metrics(bench, name, "end_to_end")}
        out["device"] = device
        out["window"] = dict(sess.context(), compiles_in_window=in_window,
                             setup_compiles=setup, **timing)
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    sess.release()
    checks = sess.check()
    out["correct"] = harness.checks_pass(checks)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main() -> int:
    t_start = harness.process_start_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        files = harness.cell_files(args.workload)
        out = run_cell(files, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

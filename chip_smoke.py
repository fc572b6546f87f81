#!/usr/bin/env python3
"""Chip smoke: drive the power study's main paths once on one TPU.

    python3 chip_smoke.py [--seed N]     # monitor, sweep, serve, control
    python3 chip_smoke.py --mesh4        # sweep on a 4-chip scenario mesh

Phases, each through the entry points a user calls and each checked
against something that does not run on the chip:

* monitor — ``sliding_monitor_fused`` and ``sliding_bin_power`` over a
  1e6-sample trace (1 ms, 5e8 W DC, a 1e5 W 9 Hz line, noise) at
  ``win=8000``, against the float64 oracle ``sliding_bin_power_ref``
  within the tier-1 bound; the line escalates, the same trace without
  it never does.
* sweep — ``Study.run(stream=512)`` over the 10^4-scenario grid of
  ``benchmarks/sweep_bench.py`` plus one ``TelemetryBackstop`` config, so
  the monitor kernel runs inside the vmapped engine.  The compiled
  backstop chunk program must hold the Mosaic kernel; the first chunk of
  each mitigation group is rerun on the host CPU (backstop on its jnp
  mirror) and must give the same spec verdicts, metrics within
  ``RTOL``.
* serve — ``PowerComplianceService.handle`` answers the README's query,
  the CI CLI's query and a tight-spec query that needs the design
  fallback; a repeat is a cache hit.  A CPU-pinned service finds the
  same catalog verdicts, and the designed config passes the spec when
  re-validated on the CPU.
* control — ``watch_trace`` on ``synthesize_ramp(dt=0.002)`` with the
  compiled fused detector reports a positive detection lead; the
  counterfactual breach agrees with the float64 oracle.

``--mesh4`` runs only the sweep on a ``ScenarioShardPlan`` over four
chips and compares it with the same Study on one chip in this process.

This is a smoke run, not a benchmark: the wall times it prints are one
sample each on the host clock.  Without a TPU it exits non-zero before
any phase; it never falls back to the CPU and never interprets a
kernel.  The last line of stdout is ``{"ok": true, "device": ...}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from repro.compile_cache import enable_compile_cache  # noqa: E402

#: relative tolerance for chip-vs-CPU sweep metrics (f32 reductions,
#: FFTs and transcendental functions round differently on the two);
#: near-zero values get an absolute floor of RTOL x the column's scale
RTOL = 1e-3
#: sliding monitor vs float64 oracle: the tier-1 bound
#: (tests/test_kernels.py::test_sliding_pallas_matches_f64_ref)
LINE_W = 1e5
MON_ATOL, MON_RTOL = 2e-3 * LINE_W, 2e-3
SWEEP_CHUNK = 512


def log(phase: str, **fields) -> None:
    print(f"smoke {phase}: " + json.dumps(fields, default=float), flush=True)


def timed(fn):
    """(result, seconds) of ``fn()`` with every output on the device
    finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def cold_warm(fn):
    """Run ``fn`` twice: the first call compiles (or loads from the
    compile cache), the second is steady state."""
    out, cold = timed(fn)
    out, warm = timed(fn)
    return out, {"first_call_s": cold, "steady_s": warm}


class CompileMeter:
    """Seconds spent in XLA compilation, or in loading executables from
    the persistent compilation cache instead, and the cache's hits and
    writes, since the last ``take``."""

    def __init__(self):
        import jax
        self.secs, self.hits, self.writes = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def take(self) -> dict:
        out = {"compile_s": self.secs, "cache_hits": self.hits,
               "cache_writes": self.writes}
        self.secs, self.hits, self.writes = 0.0, 0, 0
        return out


# ---------------------------------------------------------------------------
# monitor
# ---------------------------------------------------------------------------

def phase_monitor(seed: int, meter: CompileMeter) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core.smoothing.backstop import TelemetryBackstop
    from repro.kernels.goertzel.ops import (sliding_bin_power,
                                            sliding_monitor_fused)
    from repro.kernels.goertzel.ref import sliding_bin_power_ref

    dt, n, win = 1e-3, 1_000_000, 8000
    freqs = TelemetryBackstop().critical_hz      # 0.5, 1, 2, 9 Hz
    rng = np.random.default_rng(seed)
    t = np.arange(n) * dt
    quiet = 5e8 + rng.normal(0.0, 1e4, n)
    line = (quiet + LINE_W * np.sin(2 * np.pi * 9.0 * t)).astype(np.float32)
    quiet = quiet.astype(np.float32)
    kw = dict(win=win, threshold=0.5 * LINE_W, sustain_n=2000, cool_n=4000)

    x = jnp.asarray(line)
    (worst, levels, detect, _), t_fused = cold_warm(
        lambda: sliding_monitor_fused(x, dt, freqs, **kw))
    amps, t_amps = cold_warm(
        lambda: sliding_bin_power(x, dt, freqs, win=win))
    _, q_levels, q_detect, _ = sliding_monitor_fused(jnp.asarray(quiet), dt,
                                                     freqs, **kw)
    ref = sliding_bin_power_ref(line.astype(np.float64), dt,
                                np.asarray(freqs), win)
    amps = np.asarray(amps)
    worst = np.asarray(worst)
    np.testing.assert_allclose(amps, ref, atol=MON_ATOL, rtol=MON_RTOL)
    np.testing.assert_allclose(worst, ref.max(axis=1), atol=MON_ATOL,
                               rtol=MON_RTOL)
    levels, q_levels = np.asarray(levels), np.asarray(q_levels)
    assert int(levels.max()) >= 1 and int(detect) >= win - 1, \
        f"the 9 Hz line did not escalate (detect={int(detect)})"
    assert int(q_levels.max()) == 0 and int(q_detect) == -1, \
        "the quiet trace escalated"
    log("monitor", fused=t_fused, amps=t_amps, **meter.take(),
        max_abs_dev_amps_w=float(np.abs(amps - ref).max()),
        max_abs_dev_worst_w=float(np.abs(worst - ref.max(axis=1)).max()),
        bound_w=f"{MON_ATOL} + {MON_RTOL} x |ref|",
        detect_sample=int(detect), max_level=int(levels.max()),
        quiet_max_level=int(q_levels.max()))


# ---------------------------------------------------------------------------
# sweep
# ---------------------------------------------------------------------------

BACKSTOP = dict(window_s=4.0, sustain_s=1.0, cooldown_s=2.0,
                amp_threshold_w=1e4)


def sweep_study(plan=None):
    """The sweep_bench 10^4-scenario grid plus one backstop config."""
    import repro.core as core
    from benchmarks.sweep_bench import N_CHIPS, scale_matrix
    workloads, configs, cfg, spec, seeds = scale_matrix(10_000)
    configs = list(configs) + [(None, core.TelemetryBackstop(**BACKSTOP))]
    return core.Study(workloads, fleets=[N_CHIPS], configs=configs,
                      specs=spec, seeds=seeds, wave_cfg=cfg, key=None,
                      padding="pad", plan=plan)


def _flat(rec):
    out = {k: v for k, v in rec.items() if k != "metrics"}
    out.update({f"metrics.{k}": v for k, v in rec["metrics"].items()})
    return out


def compare_records(got, want, what: str) -> float:
    """Identical verdicts and violations; every float within RTOL of the
    reference, or within RTOL of its column's largest magnitude.  Returns
    the largest deviation seen, relative to that bound's scale."""
    import numpy as np
    assert len(got) == len(want), (what, len(got), len(want))
    got = [_flat(r) for r in got]
    want = [_flat(r) for r in want]
    for i, (a, b) in enumerate(zip(got, want)):
        for k in ("workload", "config", "seed", "spec_ok", "violations"):
            assert a[k] == b[k], f"{what}: row {i} {k}: {a[k]!r} != {b[k]!r}"
    worst = 0.0
    for k, v in got[0].items():
        if not isinstance(v, float):
            continue
        a = np.asarray([r[k] for r in got], np.float64)
        b = np.asarray([r[k] for r in want], np.float64)
        floor = RTOL * max(float(np.abs(b).max()), 1e-30)
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=floor,
                                   err_msg=f"{what}: {k}")
        worst = max(worst, float((np.abs(a - b) * RTOL
                                  / (floor + RTOL * np.abs(b))).max()))
    return worst


def run_timed(study) -> tuple:
    """``study.run(stream=SWEEP_CHUNK)`` once, with its wall time and the
    median time between chunks (the first chunk of each mitigation group
    also compiles)."""
    import numpy as np
    stamps = []
    res, wall = timed(lambda: study.run(
        stream=SWEEP_CHUNK,
        on_chunk=lambda done, total, el: stamps.append(el)).to_records())
    gaps = np.diff([0.0] + stamps)
    return res, {"wall_s": wall, "chunks": len(stamps),
                 "first_chunk_s": float(gaps[0]),
                 "median_chunk_s": float(np.median(gaps))}


def phase_sweep(meter: CompileMeter) -> None:
    import jax
    from repro.core import engine
    from repro.core.smoothing.backstop import TelemetryBackstop
    from repro.core.study import run_rows

    study = sweep_study()
    # keep the arguments of the backstop group's first chunk, to check
    # below that its compiled program holds the kernel
    calls = []
    mitigate = engine._mitigate_vmapped

    def recording(*args, **kwargs):
        if not calls and isinstance(args[6], TelemetryBackstop):
            calls.append((args, kwargs))
        return mitigate(*args, **kwargs)

    engine._mitigate_vmapped = recording
    try:
        res, t_sweep = run_timed(study)
    finally:
        engine._mitigate_vmapped = mitigate
    c_sweep = meter.take()
    assert calls, "no backstop chunk reached the engine"
    args, kwargs = calls[0]
    text = mitigate.lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in text, \
        "the backstop chunk program holds no Mosaic kernel"

    # the first chunk of each mitigation group, rerun on the host CPU
    rows = study.rows()
    bs_rows = [r for r, (_, _, c, _) in enumerate(rows)
               if isinstance(c.rack, TelemetryBackstop)]
    sel = [r for r in range(len(rows)) if r not in set(bs_rows)][
        :SWEEP_CHUNK] + bs_rows[:SWEEP_CHUNK]
    mirror = []
    for r in sel:
        w, n, c, s = rows[r]
        if isinstance(c.rack, TelemetryBackstop):
            c = dataclasses.replace(
                c, rack=dataclasses.replace(c.rack, use_pallas=False))
        mirror.append((w, n, c, s))
    with jax.default_device(jax.devices("cpu")[0]):
        (cpu, t_cpu) = timed(lambda: run_rows(
            study.workloads, mirror, study.specs, wave_cfg=study.wave_cfg,
            padding="pad", stream=SWEEP_CHUNK).to_records())
    dev = compare_records([res[r] for r in sel], cpu, "sweep chip vs CPU")
    log("sweep", scenarios=len(res), chunk=SWEEP_CHUNK, **t_sweep,
        **c_sweep, cpu_rerun_rows=len(sel), cpu_rerun_s=t_cpu,
        backstop_rows=len(bs_rows), max_dev_over_bound=dev, rtol=RTOL,
        passing=sum(r["spec_ok"] for r in res),
        tpu_custom_call=True)


def phase_mesh4(meter: CompileMeter) -> None:
    import jax
    from repro.parallel.sharding import ScenarioShardPlan
    plan = ScenarioShardPlan.make(jax.devices())
    assert plan.n_shards == 4, f"--mesh4 needs 4 chips, got {plan.n_shards}"
    mesh, t_mesh = run_timed(sweep_study(plan))
    c_mesh = meter.take()
    one, t_one = run_timed(sweep_study())
    c_one = meter.take()
    dev = compare_records(mesh, one, "4-chip vs 1-chip sweep")
    log("mesh4", scenarios=len(mesh), chips=plan.n_shards,
        four_chips=dict(t_mesh, **c_mesh), one_chip=dict(t_one, **c_one),
        max_dev_over_bound=dev, rtol=RTOL,
        passing=sum(r["spec_ok"] for r in mesh))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

QUERIES = [
    # the README's query
    {"workload": {"period_s": 2.0, "comm_frac": 0.25}, "n_chips": 512,
     "spec": "moderate"},
    # the CI CLI's query
    {"workload": {"period_s": 1.0, "comm_frac": 0.3}, "n_chips": 256,
     "spec": "moderate"},
    # no catalog config passes: the service designs one
    {"workload": {"period_s": 8.0, "comm_frac": 0.5}, "n_chips": 512,
     "spec": "tight"},
]


def phase_serve(meter: CompileMeter) -> None:
    import jax
    from repro.core import engine
    from repro.core.spec import example_specs
    from repro.serve.power import PowerComplianceService

    svc = PowerComplianceService()
    answers, times = [], []
    for q in QUERIES:
        a, dt_s = timed(lambda: svc.handle(q))
        assert "error" not in a, a
        answers.append(a)
        times.append(dt_s)
    hits = svc.stats["hits"]
    again, t_hit = timed(lambda: svc.handle(QUERIES[0]))
    assert svc.stats["hits"] == hits + 1 and again == answers[0], \
        "the repeated query was not a cache hit"
    assert answers[2]["designed"] is not None, \
        "the tight query did not reach the design fallback"

    with jax.default_device(jax.devices("cpu")[0]):
        # the catalog verdicts; the design solver itself is not rerun
        ref_svc = PowerComplianceService(design_fallback=False)
        refs = [ref_svc.handle(q) for q in QUERIES]
        for a, r in zip(answers, refs):
            if a["designed"] is None:
                assert a["recommended"] == r["recommended"], (a, r)
                assert ([p["config"] for p in a["passing"]]
                        == [p["config"] for p in r["passing"]]), (a, r)
            else:
                assert not r["compliant"], (a, r)
        # the chip's designed config, hard re-validated on the CPU
        d, q = answers[2]["designed"], QUERIES[2]
        tl, _ = ref_svc._parse_workload(q["workload"])
        fs = ref_svc._fleet_state(tl, q["n_chips"])
        spec = example_specs(job_mw=fs["mean_mw"])[q["spec"]]
        _, ok, _, _, _ = engine._eval_candidates(
            spec, fs["w"], ref_svc.wave_cfg.dt, q["n_chips"],
            [(d["mpf_frac"], d["battery_capacity_j"])], swing=fs["swing"],
            hw=ref_svc.hw)
        assert bool(ok[0]), f"designed config fails on the CPU: {d}"
    log("serve", first_calls_s=times, cache_hit_s=t_hit,
        recommended=[a["recommended"] for a in answers],
        designed={k: d[k] for k in ("config", "mpf_frac",
                                    "battery_capacity_j",
                                    "energy_overhead")},
        stats=svc.stats, **meter.take())


# ---------------------------------------------------------------------------
# control
# ---------------------------------------------------------------------------

def phase_control(meter: CompileMeter) -> None:
    import numpy as np
    from repro.control import synthesize_ramp, watch_trace
    from repro.core.spec import example_specs
    from repro.core.spectrum import GRID_CRITICAL_HZ
    from repro.kernels.goertzel.ref import sliding_bin_power_ref

    dt, window_s = 0.002, 4.0
    w = synthesize_ramp(dt=dt)
    spec = example_specs(job_mw=500.0)["moderate"]
    log_, t_loop = cold_warm(lambda: watch_trace(
        w, dt, spec=spec, n_chips=512, window_s=window_s))
    summary = log_.summary()
    lead = summary["detection_lead_s"]
    assert lead is not None and lead > 0, f"no detection lead: {summary}"

    breach_w = (spec.freq.max_bin_amplitude_w
                if spec.freq.max_bin_amplitude_w is not None
                else 0.5 * spec.time.dynamic_range_w)
    ref = sliding_bin_power_ref(w.astype(np.float64), dt,
                                np.asarray(GRID_CRITICAL_HZ),
                                max(int(window_s / dt), 8))
    over = np.nonzero(ref.max(axis=1) > breach_w)[0]
    assert len(over), "the f64 oracle never sees the breach"
    ref_t = float(over[0] * dt)
    got_t = log_.counterfactual_breach_t_s
    assert got_t is not None and abs(got_t - ref_t) <= 2 * dt, \
        f"counterfactual breach {got_t} s vs f64 oracle {ref_t} s"
    log("control", wall=t_loop, detection_lead_s=lead,
        counterfactual_breach_t_s=got_t, oracle_breach_t_s=ref_t,
        n_ticks=summary["n_ticks"],
        dispatch_latency_s=summary["dispatch_latency_s"], **meter.take())


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the monitor phase's trace noise")
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the sweep, on a 4-chip scenario mesh, "
                         "against the same Study on one chip")
    args = ap.parse_args()
    if not __debug__:
        print("chip_smoke: its checks are asserts; run it without -O",
              file=sys.stderr)
        return 2

    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              "this script runs only on the chip", file=sys.stderr)
        return 1
    from repro.kernels.goertzel.ops import interpret_default
    assert not interpret_default(), "kernels would run in interpret mode"
    print(f"# smoke run, not a benchmark: {dev.device_kind} x "
          f"{len(devices)}, compile cache {cache}", flush=True)
    meter = CompileMeter()
    phases = ([phase_mesh4] if args.mesh4 else
              [functools.partial(phase_monitor, args.seed), phase_sweep,
               phase_serve, phase_control])
    for phase in phases:
        meter.take()
        phase(meter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

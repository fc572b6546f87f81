"""Serial vs bucketed vs padded scenario-sweep wall-clock — BENCH_sweep.json.

The workload is the operator's pre-dispatch question: across a matrix of
workloads and (MPF, battery) configurations, which pass the utility spec
and at what energy overhead?  Three ways to answer it:

  serial    one ``simulate`` call per scenario (the pre-engine architecture);
  bucketed  ``engine.sweep`` — one jit/vmap call per workload *length*
            (PR 1's batched engine path, 4 compiled pipelines here);
  padded    ``Study(padding="pad").run()`` — mixed-length workloads
            edge-padded + masked into ONE fused pipeline call (the
            declarative Study API's scale lever), frequency/spec analysis
            per true length afterwards.

  PYTHONPATH=src python -m benchmarks.sweep_bench [--smoke | --scale]

Reported timings: ``*_warm_s`` are steady-state sweeps (compiled functions
cached — the regime every sweep after the first runs in); ``*_cold_s``
include compilation.  ``--smoke`` runs a small matrix for CI: it checks
three-way verdict parity plus chunked-vs-one-shot streaming bit-parity
and skips the JSON artifact.

``--scale`` is the streaming-executor section: a 10^4-scenario grid
(4 workloads x 25 configs x 100 seeds) run twice in *subprocess
isolation* — once materializing (``Study.run()``: every scenario's
waveforms resident at once) and once streaming
(``Study.run(stream=512)``: fixed O(chunk) waveform memory) — recording
wall-clock and peak RSS per process into the ``scale`` section of
BENCH_sweep.json.  Verdict counts must agree between the two runs.
``--scale`` also writes the ``distributed`` section: the same grid run
under the 2-process ``jax.distributed`` scenario mesh (per-process RSS,
scaling efficiency vs the single-process streaming wall) plus resume
overhead — a checkpointed run and a complete-restore pass against the
plain streaming wall, per chunk.

``--resume-smoke`` is the CI kill-and-resume check: a 500-scenario
resumable streamed run is SIGKILLed at a chunk boundary in a worker
subprocess, resumed in a second worker, and the resumed records must be
bit-identical to an uninterrupted in-process reference.

``--million`` runs the 10^6-scenario grid to completion on a single
host via resumable streaming (``Study.run(stream=512, resume=...)``)
and records wall / peak RSS into ``scale.million``; the acceptance
budget is peak RSS within 1.5x the 10^4 streaming figure.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import repro.core as core
from benchmarks.common import emit

N_CHIPS = 512
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_sweep.json")
SCALE_N = 10_000
SCALE_CHUNK = 512


def scenario_matrix(smoke: bool = False):
    """4 workloads x 25 (MPF x battery) configs — the acceptance grid
    (2 x 4 under ``--smoke``)."""
    workloads = {
        "dense_2s": core.synthetic_timeline(period_s=2.0, comm_frac=0.19),
        "dense_1s": core.synthetic_timeline(period_s=1.0, comm_frac=0.30),
        "moe_3s": core.synthetic_timeline(period_s=3.0, comm_frac=0.25,
                                          moe_notch=True),
        "ckpt_heavy": core.synthetic_timeline(period_s=1.5, comm_frac=0.40),
    }
    mpfs, caps = (0.5, 0.65, 0.8, 0.85, 0.9), (0.25, 0.5, 1.0, 2.0, 4.0)
    if smoke:
        workloads = {k: workloads[k] for k in ("dense_1s", "moe_3s")}
        mpfs, caps = (0.65, 0.9), (0.5, 2.0)
    cfg = core.WaveformConfig(dt=0.002, steps=12 if not smoke else 6,
                              jitter_s=0.002)
    # swing scale for battery sizing: one representative aggregate
    w = core.aggregate(core.chip_waveform(next(iter(workloads.values())), cfg),
                       N_CHIPS, cfg)
    swing = float(w.max() - w.min())
    configs = []
    for mpf in mpfs:
        for cap_f in caps:
            gpu = core.GpuPowerSmoothing(mpf_frac=mpf, ramp_up_w_per_s=2000,
                                         ramp_down_w_per_s=2000,
                                         stop_delay_s=1.0)
            bat = core.RackBattery(capacity_j=cap_f * swing,
                                   max_discharge_w=swing, max_charge_w=swing,
                                   target_tau_s=10.0)
            configs.append((gpu, bat))
    spec = core.example_specs(job_mw=w.mean() / 1e6)["moderate"]
    return workloads, configs, cfg, spec


def run_serial(workloads, configs, cfg, spec):
    records = []
    for name, tl in workloads.items():
        for gpu, bat in configs:
            res = core.simulate(tl, N_CHIPS, cfg, device_mitigation=gpu,
                                rack_mitigation=bat, spec=spec)
            records.append((name, res.spec_report.ok, res.energy_overhead))
    return records


def run_bucketed(workloads, configs, cfg, spec):
    recs = core.sweep(workloads, [N_CHIPS], configs, cfg, spec=spec)
    return [(r["workload"], r["spec_ok"], r["energy_overhead"]) for r in recs]


def make_study(workloads, configs, cfg, spec) -> core.Study:
    # key=None: the serial reference above has no keyed randomness
    return core.Study(workloads, fleets=[N_CHIPS], configs=list(configs),
                      specs=spec, wave_cfg=cfg, key=None, padding="pad")


def run_padded(study):
    res = study.run()
    return [(r["workload"], r["spec_ok"], r["energy_overhead"])
            for r in res.records]


def _agreement(a, b):
    return sum(int(x[1] == y[1]) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# --scale: 10^4-scenario streaming vs materializing (subprocess-isolated)
# ---------------------------------------------------------------------------

def scale_matrix(n_target: int):
    """The --scale grid: the 4-workload x 25-config acceptance matrix
    crossed with enough jitter seeds to reach ``n_target`` scenarios, on
    a shorter waveform config (dt=4 ms, 6 iterations) so the
    *materializing* reference stays runnable at 10^4 rows."""
    workloads = {
        "dense_2s": core.synthetic_timeline(period_s=2.0, comm_frac=0.19),
        "dense_1s": core.synthetic_timeline(period_s=1.0, comm_frac=0.30),
        "moe_3s": core.synthetic_timeline(period_s=3.0, comm_frac=0.25,
                                          moe_notch=True),
        "ckpt_heavy": core.synthetic_timeline(period_s=1.5, comm_frac=0.40),
    }
    cfg = core.WaveformConfig(dt=0.004, steps=6, jitter_s=0.004)
    w = core.aggregate(core.chip_waveform(next(iter(workloads.values())), cfg),
                       N_CHIPS, cfg)
    swing = float(w.max() - w.min())
    configs = []
    for mpf in (0.5, 0.65, 0.8, 0.85, 0.9):
        for cap_f in (0.25, 0.5, 1.0, 2.0, 4.0):
            gpu = core.GpuPowerSmoothing(mpf_frac=mpf, ramp_up_w_per_s=2000,
                                         ramp_down_w_per_s=2000,
                                         stop_delay_s=1.0)
            bat = core.RackBattery(capacity_j=cap_f * swing,
                                   max_discharge_w=swing, max_charge_w=swing,
                                   target_tau_s=10.0)
            configs.append((gpu, bat))
    seeds = list(range(max(1, n_target // (len(workloads) * len(configs)))))
    spec = core.example_specs(job_mw=w.mean() / 1e6)["moderate"]
    return workloads, configs, cfg, spec, seeds


def run_scale_worker(mode: str, n_target: int, chunk: int) -> None:
    """One measured run in this process: build the scale grid, run it
    streaming or materializing, print a JSON result line.  Peak RSS is
    meaningful because each mode runs in its own subprocess."""
    import resource

    workloads, configs, cfg, spec, seeds = scale_matrix(n_target)
    study = core.Study(workloads, fleets=[N_CHIPS], configs=list(configs),
                       specs=spec, seeds=seeds, wave_cfg=cfg, key=None,
                       padding="pad")
    last = [0.0]

    def progress(done: int, total: int, elapsed: float) -> None:
        if done == total or elapsed - last[0] > 10.0:
            last[0] = elapsed
            print(f"# {mode}: {done}/{total} scenarios in {elapsed:.0f}s",
                  file=sys.stderr, flush=True)

    t0 = time.perf_counter()
    res = study.run(stream=chunk if mode == "streaming" else None,
                    on_chunk=progress)
    wall = time.perf_counter() - t0
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({
        "mode": mode,
        "n_scenarios": study.n_rows,
        "chunk": chunk if mode == "streaming" else None,
        "wall_s": round(wall, 2),
        "peak_rss_mb": round(peak_mb, 1),
        "n_pass": len(res.passing()),
    }))


def _scale_study(n_target: int) -> core.Study:
    workloads, configs, cfg, spec, seeds = scale_matrix(n_target)
    return core.Study(workloads, fleets=[N_CHIPS], configs=list(configs),
                      specs=spec, seeds=seeds, wave_cfg=cfg, key=None,
                      padding="pad")


def run_resume_worker(n_target: int, chunk: int, resume_dir: str,
                      out_path: str | None, die_after: int | None) -> None:
    """Resumable streamed run in this process.  With ``die_after=k`` the
    worker SIGKILLs *itself* at the k-th chunk boundary — a real kill -9,
    no teardown, the checkpoint directory is all that survives."""
    import resource

    study = _scale_study(n_target)
    emits: list = []
    t0 = time.perf_counter()

    def progress(done: int, total: int, elapsed: float) -> None:
        emits.append((done, time.perf_counter() - t0))
        if die_after is not None and done >= die_after * chunk:
            os.kill(os.getpid(), signal.SIGKILL)
        if done == total or len(emits) % 50 == 0:
            print(f"# resume-worker: {done}/{total} scenarios "
                  f"in {elapsed:.0f}s", file=sys.stderr, flush=True)

    res = study.run(stream=chunk, resume=resume_dir, on_chunk=progress)
    wall = time.perf_counter() - t0
    if out_path:
        res.to_json(out_path)
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({
        "mode": "resume",
        "n_scenarios": study.n_rows,
        "chunk": chunk,
        "wall_s": round(wall, 2),
        "peak_rss_mb": round(peak_mb, 1),
        "n_pass": len(res.passing()),
        # the first emission covers the whole restored prefix in one jump;
        # its timestamp is the cost of restoring that many chunks from disk
        "first_emit_rows": emits[0][0] if emits else 0,
        "first_emit_s": round(emits[0][1], 3) if emits else None,
        "n_emits": len(emits),
    }))


def run_dist_worker(n_target: int, chunk: int) -> None:
    """One process of the 2-process distributed scale run (launched under
    the REPRO_DIST_* env contract).  Each process prints its own JSON
    line: per-process RSS is meaningful, wall is the synchronized sweep."""
    import resource

    from repro.parallel import distributed as D

    assert D.initialize(), "REPRO_DIST_* contract missing"
    study = _scale_study(n_target)
    study.plan = D.distributed_plan()
    last = [0.0]

    def progress(done: int, total: int, elapsed: float) -> None:
        if done == total or elapsed - last[0] > 10.0:
            last[0] = elapsed
            print(f"# dist p{D.process_index()}: {done}/{total} scenarios "
                  f"in {elapsed:.0f}s", file=sys.stderr, flush=True)

    t0 = time.perf_counter()
    res = study.run(stream=chunk, on_chunk=progress)
    wall = time.perf_counter() - t0
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({
        "mode": "distributed",
        "process": D.process_index(),
        "n_processes": D.process_count(),
        "n_scenarios": study.n_rows,
        "chunk": chunk,
        "wall_s": round(wall, 2),
        "peak_rss_mb": round(peak_mb, 1),
        # the merged result is replicated: every process can count passes
        "n_pass": len(res.passing()),
    }), flush=True)


def _worker_json(cmd: list, **kwargs) -> dict:
    """Run a bench worker subprocess, return its JSON result line
    (stderr inherits the terminal so heartbeats stay visible)."""
    out = subprocess.run(cmd, stdout=subprocess.PIPE, text=True, **kwargs)
    assert out.returncode == 0, f"worker {cmd} exited {out.returncode}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def _resume_cmd(n_target: int, chunk: int, resume_dir: str,
                out_path: str | None = None,
                die_after: int | None = None) -> list:
    cmd = [sys.executable, "-m", "benchmarks.sweep_bench",
           "--resume-worker", "--scale-n", str(n_target),
           "--scale-chunk", str(chunk), "--resume-dir", resume_dir]
    if out_path:
        cmd += ["--out", out_path]
    if die_after is not None:
        cmd += ["--die-after", str(die_after)]
    return cmd


def run_scale(n_target: int, chunk: int) -> None:
    """Drive both --scale-worker modes in subprocesses and merge the
    section into BENCH_sweep.json."""
    results = {}
    for mode in ("materializing", "streaming"):
        cmd = [sys.executable, "-m", "benchmarks.sweep_bench",
               "--scale-worker", mode, "--scale-n", str(n_target),
               "--scale-chunk", str(chunk)]
        print(f"# running {mode} worker ({n_target} scenarios)...",
              flush=True)
        # stderr inherits the terminal so the worker's progress heartbeats
        # stay visible during the multi-minute run; only stdout (the JSON
        # result line) is captured
        out = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        assert out.returncode == 0, f"{mode} worker exited {out.returncode}"
        results[mode] = json.loads(out.stdout.strip().splitlines()[-1])
    st, mat = results["streaming"], results["materializing"]
    assert st["n_pass"] == mat["n_pass"], \
        f"streaming/materializing verdicts disagree: {st} vs {mat}"
    section = {
        "n_scenarios": st["n_scenarios"],
        "chunk": st["chunk"],
        "streaming_wall_s": st["wall_s"],
        "streaming_peak_rss_mb": st["peak_rss_mb"],
        "materializing_wall_s": mat["wall_s"],
        "materializing_peak_rss_mb": mat["peak_rss_mb"],
        "rss_ratio": round(mat["peak_rss_mb"] / st["peak_rss_mb"], 2),
        "wall_ratio": round(mat["wall_s"] / st["wall_s"], 2),
        "n_pass": st["n_pass"],
        "verdict_agreement": f'{st["n_pass"]}=={mat["n_pass"]}',
    }
    n_chunks = (n_target + chunk - 1) // chunk
    chunk_wall = st["wall_s"] / n_chunks

    # -- resume overhead: checkpointed run + complete-restore pass -----------
    ck = tempfile.mkdtemp(prefix="sweep_resume_bench_")
    print(f"# running checkpointed streaming worker (resume={ck})...",
          flush=True)
    ckpt = _worker_json(_resume_cmd(n_target, chunk, ck))
    print("# running complete-restore worker (recomputes nothing)...",
          flush=True)
    restored = _worker_json(_resume_cmd(n_target, chunk, ck))
    assert restored["n_pass"] == st["n_pass"], \
        f"restored verdicts disagree: {restored} vs {st}"
    assert restored["first_emit_rows"] == n_target, \
        f"complete restore recomputed rows: {restored}"
    write_ovh = max(0.0, ckpt["wall_s"] - st["wall_s"]) / n_chunks
    restore_per_chunk = restored["first_emit_s"] / n_chunks
    resume = {
        "n_chunks": n_chunks,
        "chunk_wall_s": round(chunk_wall, 3),
        "checkpointed_wall_s": ckpt["wall_s"],
        "checkpoint_overhead_per_chunk_s": round(write_ovh, 4),
        "restore_wall_s": restored["first_emit_s"],
        "restore_per_chunk_s": round(restore_per_chunk, 4),
        # steady-state cost of running with resume= on, per chunk computed
        "overhead_ratio": round(write_ovh / chunk_wall, 4),
        # cost of restoring a chunk relative to recomputing it
        "restore_ratio": round(restore_per_chunk / chunk_wall, 4),
    }

    # -- 2-process scenario mesh: per-process RSS, scaling efficiency --------
    from repro.parallel import distributed as D

    print("# running 2-process distributed workers...", flush=True)
    done = D.launch_workers(
        [sys.executable, "-m", "benchmarks.sweep_bench", "--dist-worker",
         "--scale-n", str(n_target), "--scale-chunk", str(chunk)],
        num_processes=2, timeout=3600)
    per_proc = sorted((json.loads(r.stdout.strip().splitlines()[-1])
                       for r in done), key=lambda d: d["process"])
    assert all(p["n_pass"] == st["n_pass"] for p in per_proc), \
        f"distributed verdicts disagree: {per_proc} vs {st}"
    dist_wall = max(p["wall_s"] for p in per_proc)
    distributed = {
        "n_scenarios": n_target,
        "chunk": chunk,
        "n_processes": 2,
        "wall_s": dist_wall,
        "per_process_wall_s": [p["wall_s"] for p in per_proc],
        "per_process_rss_mb": [p["peak_rss_mb"] for p in per_proc],
        "single_process_wall_s": st["wall_s"],
        # speedup / n_processes; bounded by physical cores — on a 1-core
        # host two processes time-share and ~0.5 is the ceiling
        "scaling_efficiency": round(st["wall_s"] / (2 * dist_wall), 3),
        "host_cpu_count": os.cpu_count(),
        "n_pass": per_proc[0]["n_pass"],
        "verdict_agreement": f'{per_proc[0]["n_pass"]}=={st["n_pass"]}',
        "resume": resume,
    }

    data = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as fh:
            data = json.load(fh)
    data["scale"] = dict(section, million=data.get("scale", {}).get("million"))
    if data["scale"]["million"] is None:
        del data["scale"]["million"]
    data["distributed"] = distributed
    with open(OUT_PATH, "w") as fh:
        json.dump(data, fh, indent=2)
        fh.write("\n")
    emit("sweep/scale_streaming", st["wall_s"] * 1e6 / st["n_scenarios"],
         {"peak_rss_mb": st["peak_rss_mb"], "rss_ratio": section["rss_ratio"]})
    emit("sweep/distributed_2proc", dist_wall * 1e6 / n_target,
         {"scaling_efficiency": distributed["scaling_efficiency"],
          "resume_overhead_ratio": resume["overhead_ratio"]})
    print("wrote scale + distributed sections to", os.path.abspath(OUT_PATH))
    print(json.dumps({"scale": data["scale"], "distributed": distributed},
                     indent=2))


# ---------------------------------------------------------------------------
# --resume-smoke: kill-and-resume bit-parity (CI)
# ---------------------------------------------------------------------------

def run_resume_smoke(n_target: int = 500, chunk: int = 100) -> None:
    """SIGKILL a resumable streamed run at a chunk boundary in a worker
    subprocess, resume it in a second worker, and require the resumed
    records to be bit-identical to an uninterrupted in-process run.  The
    parent and both workers are pinned to the CPU: the check compares
    like with like, and no worker waits for a chip the parent holds."""
    import glob

    from repro.parallel import distributed as D

    D.pin_cpu()
    cpu_env = dict(os.environ, JAX_PLATFORMS="cpu")
    study = _scale_study(n_target)
    ref = study.run(stream=chunk).to_records()

    ck = tempfile.mkdtemp(prefix="sweep_resume_smoke_")
    out_path = os.path.join(ck, "records.json")
    die_after = 2
    kill = subprocess.run(_resume_cmd(n_target, chunk, ck,
                                      die_after=die_after),
                          stdout=subprocess.PIPE, text=True, timeout=600,
                          env=cpu_env)
    assert kill.returncode == -signal.SIGKILL, \
        f"worker survived its own SIGKILL: rc={kill.returncode}"
    survivors = glob.glob(os.path.join(ck, "chunks", "*", "chunk_*"))
    assert len(survivors) >= die_after, \
        f"kill before checkpoints were written: {survivors}"

    res = _worker_json(_resume_cmd(n_target, chunk, ck, out_path=out_path),
                       timeout=600, env=cpu_env)
    with open(out_path) as fh:
        got = json.load(fh)
    assert got == ref, \
        "resumed records differ from the uninterrupted reference"
    assert res["first_emit_rows"] >= die_after * chunk, res
    print(f"RESUME_SMOKE_OK: killed at chunk {die_after}/"
          f"{(n_target + chunk - 1) // chunk}, resumed bit-identical "
          f"({len(got)} records, {res['first_emit_rows']} rows restored "
          f"from checkpoint in {res['first_emit_s']}s)")


# ---------------------------------------------------------------------------
# --million: 10^6 scenarios, single host, resumable streaming
# ---------------------------------------------------------------------------

def run_million(n_target: int, chunk: int) -> None:
    """Complete a 10^6-scenario grid on one host via resumable streaming
    and record wall / peak RSS into ``scale.million``.  The RSS budget is
    1.5x the 10^4 streaming figure: O(chunk) waveform memory means only
    the columnar metric store grows with the grid."""
    data = {}
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as fh:
            data = json.load(fh)
    base_rss = data.get("scale", {}).get("streaming_peak_rss_mb", 1294.4)
    budget = round(1.5 * base_rss, 1)

    ck = tempfile.mkdtemp(prefix="sweep_million_")
    print(f"# running 10^6-scenario resumable streaming worker "
          f"(resume={ck}, rss budget {budget} MB)...", flush=True)
    res = _worker_json(_resume_cmd(n_target, chunk, ck))
    million = {
        "n_scenarios": res["n_scenarios"],
        "chunk": chunk,
        "wall_s": res["wall_s"],
        "scenarios_per_s": round(res["n_scenarios"] / res["wall_s"], 1),
        "peak_rss_mb": res["peak_rss_mb"],
        "rss_budget_mb": budget,
        "within_budget": res["peak_rss_mb"] <= budget,
        "n_pass": res["n_pass"],
        "n_chunks": (res["n_scenarios"] + chunk - 1) // chunk,
    }
    data.setdefault("scale", {})["million"] = million
    with open(OUT_PATH, "w") as fh:
        json.dump(data, fh, indent=2)
        fh.write("\n")
    emit("sweep/million_streaming", res["wall_s"] * 1e6 / res["n_scenarios"],
         {"peak_rss_mb": res["peak_rss_mb"], "rss_budget_mb": budget})
    assert million["within_budget"], \
        f"10^6-scenario peak RSS {res['peak_rss_mb']} MB over {budget} MB"
    print("wrote scale.million to", os.path.abspath(OUT_PATH))
    print(json.dumps(million, indent=2))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small matrix, parity checks only, no JSON artifact")
    ap.add_argument("--scale", action="store_true",
                    help="10^4-scenario streaming-vs-materializing section "
                         "(subprocess-isolated wall-clock + peak RSS)")
    ap.add_argument("--resume-smoke", action="store_true",
                    help="CI kill-and-resume check: SIGKILL a resumable "
                         "streamed run mid-sweep, resume, assert bit-parity")
    ap.add_argument("--million", action="store_true",
                    help="10^6-scenario single-host resumable streaming run "
                         "(writes scale.million; multi-hour on small hosts)")
    ap.add_argument("--million-n", type=int, default=1_000_000)
    ap.add_argument("--scale-n", type=int, default=SCALE_N)
    ap.add_argument("--scale-chunk", type=int, default=SCALE_CHUNK)
    ap.add_argument("--scale-worker", choices=("streaming", "materializing"),
                    default=None, help=argparse.SUPPRESS)
    ap.add_argument("--resume-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--dist-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--resume-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--die-after", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.scale_worker:
        run_scale_worker(args.scale_worker, args.scale_n, args.scale_chunk)
        return
    if args.resume_worker:
        run_resume_worker(args.scale_n, args.scale_chunk, args.resume_dir,
                          args.out, args.die_after)
        return
    if args.dist_worker:
        run_dist_worker(args.scale_n, args.scale_chunk)
        return
    if args.resume_smoke:
        run_resume_smoke()
        return
    if args.million:
        run_million(args.million_n, args.scale_chunk)
        return
    if args.scale:
        run_scale(args.scale_n, args.scale_chunk)
        return

    workloads, configs, cfg, spec = scenario_matrix(args.smoke)
    study = make_study(workloads, configs, cfg, spec)
    n_scen = len(workloads) * len(configs)

    if args.smoke:
        serial = run_serial(workloads, configs, cfg, spec)
        bucketed = run_bucketed(workloads, configs, cfg, spec)
        padded = run_padded(study)
        assert _agreement(serial, bucketed) == n_scen, \
            "bucketed verdicts disagree with serial"
        assert _agreement(serial, padded) == n_scen, \
            "padded verdicts disagree with serial"
        # streaming executor: a chunked run (chunk smaller than the grid,
        # splitting dedup prefix groups) must be bit-identical to one-shot
        chunks = []
        chunked = study.run(stream=3,
                            on_chunk=lambda d, t, e: chunks.append((d, t)))
        oneshot = study.run()
        assert chunked.records == oneshot.records, \
            "chunked records differ from one-shot"
        assert chunks and chunks[-1][0] == chunks[-1][1] == study.n_rows
        print(f"smoke OK: {n_scen} scenarios, serial == bucketed == padded "
              "spec verdicts; chunked stream bit-identical to one-shot "
              f"({len(chunks)} chunks)")
        return

    # warm the per-shape scan/FFT caches for EVERY workload length (they
    # compile separately) so the serial loop is measured in its own steady
    # state, symmetric with the batched warm timings
    run_serial(workloads, configs[:1], cfg, spec)
    t0 = time.perf_counter()
    serial = run_serial(workloads, configs, cfg, spec)
    serial_s = time.perf_counter() - t0

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    def best_of(fn, n=3):
        # warm timings are noise-prone at this scale; best-of-n is the
        # steady-state number (both paths measured identically)
        out, best = timed(fn)
        for _ in range(n - 1):
            out, t = timed(fn)
            best = min(best, t)
        return out, best

    _, bucketed_cold_s = timed(
        lambda: run_bucketed(workloads, configs, cfg, spec))
    bucketed, bucketed_warm_s = best_of(
        lambda: run_bucketed(workloads, configs, cfg, spec))

    _, padded_cold_s = timed(lambda: run_padded(study))
    padded, padded_warm_s = best_of(lambda: run_padded(study))

    # verdict parity: same pass/fail for every scenario, all three paths
    agree_b = _agreement(serial, bucketed)
    agree_p = _agreement(serial, padded)
    result = {
        "n_scenarios": n_scen,
        "n_workloads": len(workloads),
        "n_configs": len(configs),
        "serial_s": round(serial_s, 3),
        "bucketed_cold_s": round(bucketed_cold_s, 3),
        "bucketed_warm_s": round(bucketed_warm_s, 3),
        "padded_cold_s": round(padded_cold_s, 3),
        "padded_warm_s": round(padded_warm_s, 3),
        "speedup_warm_bucketed": round(serial_s / bucketed_warm_s, 1),
        "speedup_warm_padded": round(serial_s / padded_warm_s, 1),
        "padded_vs_bucketed_warm": round(bucketed_warm_s / padded_warm_s, 2),
        "padded_vs_bucketed_cold": round(bucketed_cold_s / padded_cold_s, 2),
        "verdict_agreement_bucketed": f"{agree_b}/{n_scen}",
        "verdict_agreement_padded": f"{agree_p}/{n_scen}",
        "passing_configs": sum(int(ok) for _, ok, _ in padded),
    }
    with open(OUT_PATH, "w") as fh:
        json.dump(result, fh, indent=2)
        fh.write("\n")
    emit("sweep/serial", serial_s * 1e6 / n_scen, {"total_s": round(serial_s, 2)})
    emit("sweep/bucketed_warm", bucketed_warm_s * 1e6 / n_scen,
         {"total_s": round(bucketed_warm_s, 2),
          "speedup": result["speedup_warm_bucketed"]})
    emit("sweep/padded_warm", padded_warm_s * 1e6 / n_scen,
         {"total_s": round(padded_warm_s, 2),
          "speedup": result["speedup_warm_padded"],
          "vs_bucketed": result["padded_vs_bucketed_warm"]})
    assert agree_b == n_scen, "serial and bucketed spec verdicts disagree"
    assert agree_p == n_scen, "serial and padded spec verdicts disagree"
    # the speedup targets are advisory (wall-clock is environment-dependent);
    # correctness (verdict parity) is the hard invariant above
    if serial_s / padded_warm_s < 5.0:
        print(f"# WARNING: padded sweep only {serial_s / padded_warm_s:.1f}x "
              "serial on this machine (target >=5x)")
    if padded_warm_s > 1.1 * bucketed_warm_s:
        print(f"# WARNING: padded single-bucket path "
              f"{padded_warm_s / bucketed_warm_s:.2f}x slower than "
              "per-length buckets on this machine (target: parity; "
              "the fusion win is compile amortization, see *_cold_s)")
    print("wrote", os.path.abspath(OUT_PATH))


if __name__ == "__main__":
    main()

"""Multi-host driver for the scenario mesh: ``jax.distributed`` init,
process-local launch helpers, and the 2-process CI smoke.

The streaming engine is embarrassingly parallel along its scenario axis;
``ScenarioShardPlan`` already expresses the 1-D "scenario" mesh and the
per-process row slice (``local_rows``).  This module supplies the part
nothing drove before:

* ``initialize()`` — idempotent ``jax.distributed.initialize`` from an
  explicit coordinator or the ``REPRO_DIST_*`` env contract.  On CPU it
  switches the collectives implementation to gloo *first* — without
  that, any computation over a cross-process global array fails with
  "Multiprocess computations aren't implemented on the CPU backend".
* ``distributed_plan()`` — the ``ScenarioShardPlan`` over *all* (global)
  devices, built after init so every process sees the same mesh.
* ``launch_workers()`` / ``worker_env()`` / ``free_port()`` — the
  subprocess-simulated multi-process harness (2 CPU processes are
  sufficient proof; the same env contract drives real multi-host).
  ``launch_workers`` pins its workers to the CPU: a chip belongs to one
  process, so simulated hosts never ask for it.
* ``python -m repro.parallel.distributed --smoke`` — CI entry: runs a
  small Study single-process, re-runs it under 2 ``jax.distributed``
  processes on the scenario mesh, and asserts the two ``StudyResult``
  record streams are bit-identical.  The parent and its workers are all
  pinned to the CPU (``pin_cpu``), so the comparison is CPU against CPU
  and no process holds a chip another one needs.

Process identity (``process_index``/``process_count``) is a *host-side*
constant: compute it outside jit and pass values in.  Baking it into
traced code or pytree data fields makes results differ per process —
repro-lint rule RPR007 flags exactly that.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional, Sequence

ENV_COORD = "REPRO_DIST_COORD"
ENV_NPROCS = "REPRO_DIST_NPROCS"
ENV_PID = "REPRO_DIST_PID"

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Idempotent ``jax.distributed.initialize`` for the scenario mesh.

    Arguments default to the ``REPRO_DIST_COORD`` / ``REPRO_DIST_NPROCS``
    / ``REPRO_DIST_PID`` environment contract (what ``launch_workers``
    sets); with neither arguments nor env present this is a no-op so the
    same driver code runs single-process unchanged.  Returns True when
    the distributed runtime is (now) up.

    Must run before any other JAX call touches the backend: on CPU the
    collectives implementation is switched to gloo here, which only
    takes effect before backend initialization.
    """
    global _initialized
    if _initialized:
        return True
    coord = coordinator_address or os.environ.get(ENV_COORD)
    if coord is None:
        return False
    nproc = int(num_processes if num_processes is not None
                else os.environ[ENV_NPROCS])
    pid = int(process_id if process_id is not None
              else os.environ[ENV_PID])
    if nproc <= 1:
        return False
    import jax
    # CPU multiprocess collectives need gloo; harmless on other backends
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    _initialized = True
    return True


def pin_cpu() -> None:
    """Keep this process's JAX on the CPU backend.  For the parent of a
    simulated multi-host run: it must call this before its first JAX
    computation, since a backend, once started, stays."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "JAX already started on a non-CPU backend; the simulated "
            "multi-host harness needs this process on the CPU")


def process_index() -> int:
    import jax
    return int(jax.process_index())


def process_count() -> int:
    import jax
    return int(jax.process_count())


def is_primary() -> bool:
    """True on the process that owns side effects (progress callbacks,
    checkpoint writes, result export).  Always True single-process."""
    return process_index() == 0


def distributed_plan(*, axis: str = "scenario"):
    """The ``ScenarioShardPlan`` over all global devices — every process
    builds the same mesh, so the same jit call is one SPMD program."""
    import jax
    from repro.parallel.sharding import ScenarioShardPlan
    return ScenarioShardPlan.make(jax.devices(), axis=axis)


# ---------------------------------------------------------------------------
# subprocess-simulated multi-process launch
# ---------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def worker_env(base_env: Optional[Dict[str, str]] = None, *,
               coordinator: str, num_processes: int,
               process_id: int) -> Dict[str, str]:
    """The env one worker subprocess needs: the ``REPRO_DIST_*`` contract
    plus a src/ ``PYTHONPATH`` entry (mirroring the test-suite pattern)."""
    env = dict(os.environ if base_env is None else base_env)
    env[ENV_COORD] = coordinator
    env[ENV_NPROCS] = str(num_processes)
    env[ENV_PID] = str(process_id)
    src = os.path.join(os.path.dirname(__file__), "..", "..")
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return env


def launch_workers(argv: Sequence[str], num_processes: int = 2, *,
                   env: Optional[Dict[str, str]] = None,
                   timeout: float = 900.0
                   ) -> List[subprocess.CompletedProcess]:
    """Run ``num_processes`` copies of ``argv`` as one ``jax.distributed``
    job (shared fresh coordinator port, per-process id) and wait for all.
    Every worker runs on the CPU backend (``JAX_PLATFORMS=cpu``).
    Raises if any worker exits non-zero, with that worker's stderr tail.
    """
    coord = f"localhost:{free_port()}"
    envs = [worker_env(env, coordinator=coord, num_processes=num_processes,
                       process_id=pid) for pid in range(num_processes)]
    for e in envs:
        e["JAX_PLATFORMS"] = "cpu"
    procs = [subprocess.Popen(list(argv), env=e, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for e in envs]
    done = []
    for pid, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        done.append(subprocess.CompletedProcess(p.args, p.returncode,
                                                out, err))
    for pid, r in enumerate(done):
        if r.returncode != 0:
            raise RuntimeError(
                f"distributed worker {pid} exited {r.returncode}:\n"
                f"{r.stderr[-3000:]}")
    return done


# ---------------------------------------------------------------------------
# CI smoke: 2-process records bit-parity against single-process
# ---------------------------------------------------------------------------

def _smoke_study():
    import repro.core as core
    tl = core.synthetic_timeline(1.0, 0.3)
    tl2 = core.synthetic_timeline(2.0, 0.25, moe_notch=True)
    cfg = core.WaveformConfig(dt=0.002, steps=3, jitter_s=0.002)
    gpu = lambda m: core.GpuPowerSmoothing(
        mpf_frac=m, ramp_up_w_per_s=2000, ramp_down_w_per_s=2000,
        stop_delay_s=1.0)
    spec = core.example_specs(job_mw=0.05)["moderate"]
    return core.Study(
        {"w": tl, "w2": tl2}, fleets=[128, 256],
        configs={"none": None, "a": (gpu(0.8), None), "b": (gpu(0.65), None)},
        specs=spec, wave_cfg=cfg, key=0)


def _smoke_worker(out_path: str, stream: int) -> None:
    """One distributed worker: init, run the smoke Study on the global
    scenario mesh, write records JSON from the primary process."""
    assert initialize(), "worker launched without the REPRO_DIST_* contract"
    import repro.core as core  # noqa: F401  (backend now initialized)
    study = _smoke_study()
    study.plan = distributed_plan()
    res = study.run(stream=stream)
    if is_primary():
        res.to_json(out_path)
    print(f"worker {process_index()}/{process_count()} done", flush=True)


def run_smoke(num_processes: int = 2, stream: int = 5) -> None:
    pin_cpu()
    ref = _smoke_study().run(stream=stream)
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "dist_records.json")
        launch_workers(
            [sys.executable, "-m", "repro.parallel.distributed",
             "--smoke-worker", "--out", out, "--stream", str(stream)],
            num_processes=num_processes)
        with open(out) as fh:
            got = json.load(fh)
    want = ref.to_records()
    assert got == want, (
        f"{num_processes}-process records differ from single-process "
        f"({sum(a != b for a, b in zip(got, want))}/{len(want)} records)")
    print(f"DISTRIBUTED_SMOKE_OK: {num_processes}-process run bit-identical "
          f"to single-process ({len(want)} records)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="2-process CPU smoke: records bit-parity vs "
                         "single-process")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--stream", type=int, default=5)
    ap.add_argument("--smoke-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.smoke_worker:
        _smoke_worker(args.out, args.stream)
        return
    if args.smoke:
        run_smoke(args.processes, args.stream)
        return
    ap.print_help()


if __name__ == "__main__":
    main()

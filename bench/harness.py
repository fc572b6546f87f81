"""What every cell's run shares: files found by name, the compile meter,
the device, the per-layer readers and the result line.

Nothing here knows a cell.  A cell is ``workloads/<name>.json`` (its
configuration, its traffic and why it exists); a configuration is
``configs/<name>.json``; the traffic's ``driver`` names a module in
``drivers/``; a per-layer metric is ``metrics/<name>.py``.  Adding any of
them adds files only.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


def process_start_s() -> float:
    """Seconds since the epoch at which this process started (Linux),
    so that ``setup_s`` counts the interpreter's own start too."""
    with open("/proc/self/stat") as fh:
        fields = fh.read().rsplit(")", 1)[1].split()
    ticks = float(fields[19])                     # starttime, field 22
    with open("/proc/uptime") as fh:
        uptime = float(fh.read().split()[0])
    hz = os.sysconf(os.sysconf_names["SC_CLK_TCK"])
    return time.time() - uptime + ticks / hz


def load_json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> Dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def cell_files(name: str, bench: Optional[Dict] = None) -> Dict:
    """The cell ``name``: its BENCHMARK.json entry, its workload file and
    its configuration file, checked against each other."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(os.path.join(BENCH, "workloads", f"{name}.json"))
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, conf["file"]))
    if cell["config"] != entry["config"] or config["name"] != conf["name"]:
        raise BenchError(f"{name}: workload and configuration files disagree")
    return {"entry": entry, "cell": cell, "config": config, "bench": bench}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Dict):
    name = cell["traffic"]["driver"]
    return load_module(os.path.join(BENCH, "drivers", f"{name}.py"),
                       f"bench_driver_{name}")


def cell_metrics(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, or that list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_per_layer(bench: Dict, cell: str, ctx) -> Dict[str, Dict]:
    """Run each per-layer reader of the cell; a reader that finds nothing
    returns None and its metric is left out."""
    out = {}
    for m in cell_metrics(bench, cell, "per_layer"):
        mod = load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is cached, however quickly it compiled.  Call before JAX is
    imported anywhere."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(ROOT, ".jax_cache"))
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _use_compile_cache(True)
    return path


def close_compile_cache() -> None:
    """From here on every program compiles anew, and nothing is read from
    or written to the persistent cache.  Called where the window starts:
    a program built inside the window (one whose constants come from
    what the window saw) then costs every run the same, and a second run
    of a seed does not find the first run's programs."""
    _use_compile_cache(False)


def _use_compile_cache(on: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", on)
    # JAX decides once per process whether the cache is used; make it
    # decide again at the next compile
    compilation_cache.reset_cache()


def add_paths() -> None:
    for p in (os.path.join(ROOT, "src"), BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)


class CompileMeter:
    """XLA compilations (and loads from the persistent cache) since the
    last ``take``: how many, their seconds, and the cache's hits."""

    def __init__(self):
        import jax
        self.n, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> Dict:
        out = {"compiles": self.n, "compile_s": self.secs,
               "cache_hits": self.hits}
        self.n, self.secs, self.hits = 0, 0.0, 0
        return out


def devices(chips: int):
    """The chips this run uses; a run without a TPU, or with fewer chips
    than the cell asks for, stops here."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}; this "
                         "benchmark runs only on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> Dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def span_factory(tracing: bool):
    """``span(name)``: a profiler annotation when tracing, else nothing."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)


def drain() -> None:
    """Wait until the device has run everything enqueued so far."""
    import jax
    import jax.numpy as jnp
    jax.effects_barrier()
    for d in jax.devices():
        jax.block_until_ready(jax.device_put(jnp.zeros(()), d) + 1)


def checks_pass(checks: List[Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)

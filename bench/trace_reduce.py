"""From a profiler trace to the numbers the per-layer readers take.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Each chip
is a plane named ``/device:TPU:<n>``; its ``XLA Modules`` line holds one
event per program run (named after the jitted function, e.g.
``jit__mitigate_vmapped(42)``) and its ``XLA Ops`` line one event per
operation.  The Python thread's line (``python3``) holds the harness's spans
(``bench.*``) and JAX's own dispatch events.  All share one clock.

``reduce`` keeps, inside the harness's ``bench.window`` span:

* busy seconds per chip: the union of the chip's operation intervals;
* seconds per program, summed over its runs;
* seconds per operation name, and the operations of each program;
* idle gaps: the stretches with no operation on the chip, each named by
  the innermost host event that covers its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit__mitigate_vmapped(42)`` -> ``_mitigate_vmapped``."""
    return _PROGRAM.match(module).group(1)


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds on the trace's clock
    end: float
    program: str = ""       # the program an operation ran in

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    """The parts of one trace the reduction reads."""
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> {modules, ops}
    host: List[Event]                            # python-thread events


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[512,17988]{...} fusion(...)`` -> ``fusion.3
    f32[512,17988]``: the instruction and the shape of its result."""
    name, _, rest = hlo.partition(" = ")
    shape = ("tuple" if rest.startswith("(")
             else rest.split("{", 1)[0].split(" ", 1)[0])
    return f"{name.lstrip('%')} {shape}".strip()


def _attribute(ops: List[Event], modules: List[Event]) -> None:
    """Name each operation's program: the program run whose interval
    holds the operation's start (a chip runs one program at a time)."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < mods[i].end:
            op.program = program_name(mods[i].name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    """The parts of a ``jax.profiler.ProfileData`` the reduction reads."""
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                name = op_name if key == "ops" else str
                lines[key].extend(
                    Event(name(e.name), e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            _attribute(lines["ops"], lines["modules"])
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            # the Python thread, where the harness's spans and JAX's
            # dispatches are recorded
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend(Event(e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events)
    return Trace(devices=devices, host=host)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    return [dataclasses.replace(e, start=max(e.start, t0), end=min(e.end, t1))
            for e in events if e.end > t0 and e.start < t1]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over chips
    program_s: Dict[str, float]        # mean over chips
    op_s: Dict[str, float]             # mean over chips, by operation name
    gaps: List[Tuple[str, float]]      # longest idle gaps, named
    chips: int

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_in(self, program: str, pattern: str) -> float:
        """Seconds (mean over chips) of operations of ``program`` whose
        name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for (p, n), s in self._prog_ops.items()
                   if p == program and rx.search(n))

    _prog_ops: Dict[Tuple[str, str], float] = dataclasses.field(
        default_factory=dict)


def _gap_name(host: Sequence[Event], t: float) -> str:
    """What the host was doing at ``t``: the harness span, then the
    innermost event, that covers it."""
    cover = [e for e in host if e.start <= t < e.end]
    if not cover:
        return "(no host event)"
    inner = min(cover, key=lambda e: e.dur)
    spans = [e for e in cover if e.name.startswith("bench.")]
    span = min(spans, key=lambda e: e.dur).name if spans else ""
    return inner.name if inner.name == span or not span else \
        f"{span} > {inner.name}"


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None,
           n_gaps: int = 10) -> Reduced:
    """Reduce ``trace`` over ``window`` (default: the host's
    ``bench.window`` span)."""
    if window is None:
        spans = [e for e in trace.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        window = (spans[0].start, spans[0].end)
    t0, t1 = window
    if not trace.devices:
        raise ValueError("the trace has no TPU device plane")
    busy, progs, ops, prog_ops = 0.0, {}, {}, {}
    gaps: List[Tuple[str, float]] = []
    for lines in trace.devices.values():
        op_ev = _clip(lines["ops"], t0, t1)
        busy_iv = union([(e.start, e.end) for e in op_ev])
        busy += sum(e - s for s, e in busy_iv)
        for e in _clip(lines["modules"], t0, t1):
            p = program_name(e.name)
            progs[p] = progs.get(p, 0.0) + e.dur
        for e in op_ev:
            ops[e.name] = ops.get(e.name, 0.0) + e.dur
            k = (e.program, e.name)
            prog_ops[k] = prog_ops.get(k, 0.0) + e.dur
        edges = [t0] + [x for iv in busy_iv for x in iv] + [t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_gap_name(trace.host, 0.5 * (s + e)), e - s))
    n = len(trace.devices)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=t1 - t0, busy_s=busy / n,
                   program_s={k: v / n for k, v in progs.items()},
                   op_s={k: v / n for k, v in ops.items()},
                   gaps=gaps[:n_gaps], chips=n,
                   _prog_ops={k: v / n for k, v in prog_ops.items()})


def breakdown(red: Reduced, n: int = 10) -> Dict:
    """The ``breakdown`` of a traced run's result line: the operations
    that took most device time, as ``program/operation``, and the
    longest idle gaps."""
    top = sorted(red._prog_ops.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[f"{p}/{o}", v] for (p, o), v in top],
            "idle_gaps": [[k, v] for k, v in red.gaps[:n]]}

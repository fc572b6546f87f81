"""Sweep cells: the planner's scenario grid, as ``Study.run(stream=...)``.

The traffic file declares the grid: the configuration's workloads and
fleet, a device and a rack mitigation stage whose listed fields are the
grid's axes (a value ``{"x_swing": v}`` or ``{"x_mean": v}`` scales the
sizing aggregate's swing or mean power), and the jitter seeds per Study.
Every Study has the same rows in the same order; only the seed block
changes, so one warm-up Study compiles every shape the window runs.

Window: Studies back to back, each on the next seed block.  It starts
when the first timed Study starts and ends at the first chunk completion
at or after ``--seconds`` (at least two), where the Study in flight is
stopped.  The rows checked are drawn from the Studies that finished.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Dict, List

import numpy as np

from ref import sweep as ref_sweep
from ref.power import phase_list
from ref.precision import F64

#: seeds of one run: block j of Study seeds starts at seed * SEED_STRIDE +
#: j * seeds_per_study; block 0 is the warm-up
SEED_STRIDE = 1_000_000


class _Stop(Exception):
    """Raised from ``on_chunk`` to end the window."""


def _scaled(v, scale: Dict[str, float]):
    """A value or list of values as the file gives it; ``{"x_swing": v}``
    (or ``x_mean``) multiplies by the sizing aggregate's swing (or mean)."""
    if not isinstance(v, dict):
        return v
    (unit, x), = v.items()
    return ([e * scale[unit] for e in x] if isinstance(x, list)
            else x * scale[unit])


def grid(stage: Dict, scale: Dict[str, float]) -> List[Dict]:
    """The stage's grid points as full parameter dicts, axes in file
    order, the last varying fastest."""
    if stage is None:
        return [None]
    fixed = {k: _scaled(v, scale) for k, v in stage["fixed"].items()}
    names = list(stage["grid"])
    values = [_scaled(v, scale) for v in stage["grid"].values()]
    return [{"class": stage["class"],
             "params": dict(fixed, **dict(zip(names, combo)))}
            for combo in itertools.product(*values)]


class Session:
    """One run of a sweep cell."""

    def __init__(self, config: Dict, cell: Dict, seed: int):
        import repro.core as core
        from repro.core.hardware import DEFAULT_HW
        from repro.core.phases import IterationTimeline, Phase
        from repro.core.spec import (FrequencyDomainSpec, TimeDomainSpec,
                                     UtilitySpec)
        from repro.core.waveform import WaveformConfig

        self.config, self.cell, self.seed = config, cell, int(seed)
        traffic = cell["traffic"]
        self.chunk = traffic["stream"]
        self.seeds_per_study = traffic["seeds_per_study"]
        _check_hardware(config["hardware"], DEFAULT_HW)
        size = ref_sweep.sizing(config)
        self.scale = {"x_swing": size["swing_w"], "x_mean": size["mean_w"]}
        self.spec = ref_sweep.spec_limits(config, size["mean_w"])
        s = self.spec
        self._spec = UtilitySpec(
            config["spec"]["tier"],
            TimeDomainSpec(ramp_up_w_per_s=s["ramp_up_w_per_s"],
                           ramp_down_w_per_s=s["ramp_down_w_per_s"],
                           dynamic_range_w=s["dynamic_range_w"],
                           window_s=s["window_s"],
                           ramp_window_s=s["ramp_window_s"]),
            FrequencyDomainSpec(band_hz=s["band_hz"],
                                max_energy_fraction=s["max_energy_fraction"],
                                min_ac_rms_frac=s["min_ac_rms_frac"]))
        wc = config["waveform"]
        self._wave = WaveformConfig(dt=wc["dt"], steps=wc["steps"],
                                    jitter_s=wc["jitter_s"],
                                    edp_spikes=wc["edp_spikes"])
        self.workloads = list(config["workloads"])
        self._timelines = {
            name: IterationTimeline(tuple(
                Phase(f"p{i}", dur, mode) for i, (dur, mode) in enumerate(
                    phase_list(w["period_s"], w["comm_frac"],
                               w["moe_notch"]))))
            for name, w in config["workloads"].items()}
        self.points = [(d, r) for d in grid(traffic["device"], self.scale)
                       for r in grid(traffic["rack"], self.scale)]
        # each row's real length, in the Study's row order (workload, then
        # grid point, then seed)
        per_workload = len(self.points) * self.seeds_per_study
        self.row_lengths = [_length(config, w) for w in self.workloads
                            for _ in range(per_workload)]
        self._configs = {f"c{i:03d}": (_build(core, d), _build(core, r))
                         for i, (d, r) in enumerate(self.points)}
        self._core = core
        self.finished: List = []        # (seed block, StudyResult)
        self.stats: Dict = {}

    # -- program ------------------------------------------------------------

    def seeds(self, block: int) -> List[int]:
        base = self.seed * SEED_STRIDE + block * self.seeds_per_study
        return list(range(base, base + self.seeds_per_study))

    def study(self, block: int):
        return self._core.Study(
            self._timelines, fleets=[self.config["n_chips"]],
            configs=self._configs, specs={self._spec.name: self._spec},
            seeds=self.seeds(block), wave_cfg=self._wave, key=None,
            padding="pad", sample_chips=self.config["sample_chips"])

    def setup(self, span) -> None:
        """One Study of the window's exact row layout compiles every
        program and shape the window runs."""
        with span("bench.warmup"):
            self.study(0).run(stream=self.chunk)

    def window(self, seconds: float, span) -> Dict:
        done_rows, completions, in_flight = 0, 0, 0
        dispatched = []                 # rows each Study handed the program
        block = 1
        t0 = time.perf_counter()
        t_end = None
        while t_end is None:
            state = {"done": 0}

            def on_chunk(done, total, _elapsed):
                nonlocal done_rows, completions, in_flight, t_end
                now = time.perf_counter()
                done_rows += done - state["done"]
                state["done"] = done
                completions += 1
                if now - t0 >= seconds and completions >= 2:
                    t_end = now
                    in_flight = min(self.chunk, total - done)
                    if in_flight:
                        raise _Stop

            try:
                with span("bench.study"):
                    res = self.study(block).run(stream=self.chunk,
                                                on_chunk=on_chunk)
                self.finished.append((block, res))
                dispatched.append(len(self.row_lengths))
            except _Stop:
                dispatched.append(state["done"] + in_flight)
            block += 1
        elapsed = t_end - t0
        self.stats = {"scenarios": done_rows, "chunks": completions,
                      "rows_dispatched": done_rows + in_flight,
                      "rows_by_length": rows_by_length(self.row_lengths,
                                                       dispatched),
                      "window_s": elapsed, "studies": len(self.finished)}
        return {"scenarios_per_s": done_rows / elapsed,
                "attempted": done_rows, "failed": 0}

    def release(self) -> None:
        """The check runs on the host from the finished records; nothing
        the program computed stays on the device."""
        self._configs = None

    # -- check --------------------------------------------------------------

    def sample(self) -> List[Dict]:
        """One finished row per (workload, mitigation setting), its Study
        and seed drawn from the run's seed."""
        rng = np.random.default_rng([self.seed, 17])
        S, P = self.seeds_per_study, len(self.points)
        items = []
        for wi, wname in enumerate(self.workloads):
            for pi, (dev, rack) in enumerate(self.points):
                block, res = self.finished[rng.integers(len(self.finished))]
                si = int(rng.integers(S))
                rec = res[(wi * P + pi) * S + si]
                items.append({"workload": wname, "seed": self.seeds(block)[si],
                              "device": dev, "rack": rack, "record": rec})
        return items

    def check(self, q=F64, items=None) -> List[Dict]:
        limits = self.cell["check"]
        items = items or self.sample()
        for it in items:
            rec = it["record"]
            if (rec["workload"], rec["seed"]) != (it["workload"], it["seed"]):
                raise AssertionError("record order differs from the grid's")
        want = ref_sweep.rows(self.config, self.spec, items)
        got = (ref_sweep.rows(self.config, self.spec, items, q)
               if q is not F64 else [it["record"] for it in items])
        devs = ref_sweep.compare(got, want, limits["verdict_band"])
        return [{"name": f"{k}_dev", "value": devs[k],
                 "limit": limits[f"{k}_dev"]} for k in ("synth", "mitig",
                                                        "analysis")]

    # -- what the per-layer readers see --------------------------------------

    def context(self) -> Dict:
        out = dict(self.stats)
        rack = self.cell["traffic"]["rack"]
        if rack is not None and rack["class"] == "TelemetryBackstop":
            out["monitor"] = {"bins": len(rack["fixed"]["critical_hz"])}
        return out


def rows_by_length(row_lengths: List[int], dispatched: List[int]
                   ) -> Dict[str, int]:
    """The rows handed to the program, counted by their real length
    (padding left out): ``dispatched`` holds, for each Study, how many of
    its first rows in ``row_lengths``'s order were dispatched."""
    count = collections.Counter()
    for n in dispatched:
        count.update(row_lengths[:n])
    return {str(length): rows for length, rows in sorted(count.items())}


def _length(config: Dict, workload: str) -> int:
    return len(ref_sweep.chip_trace(config, workload))


def _build(core, point):
    if point is None:
        return None
    return getattr(core, point["class"])(**point["params"])


def _check_hardware(hw: Dict, program_hw) -> None:
    """The program's power model must be the configuration's."""
    got = {"tdp_w": program_hw.chip.tdp_w, "comm_w": program_hw.chip.comm_w,
           "edp_factor": program_hw.chip.edp_factor,
           "edp_window_s": program_hw.chip.edp_window_s,
           "distribution_loss": program_hw.topo.distribution_loss}
    for k, v in got.items():
        if abs(v - hw[k]) > 1e-12 * max(abs(v), 1.0):
            raise ValueError(f"the program's {k} is {v}, the configuration "
                             f"states {hw[k]}")

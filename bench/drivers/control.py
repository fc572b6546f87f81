"""Control cells: the closed loop over replayed telemetry, tick by tick.

Each replay streams one ramp trace (``ref.control.ramp``, its noise from
the run's seed and the replay's index, or, in set-up, from a fixed key)
through a fresh ``ControlLoop`` assembled as ``watch_trace`` assembles
it, with the configuration's rules.  The harness drives the loop one tick at a time and times every
tick on the host clock from outside the program; after a replay's last
tick it runs the offline monitor on the raw trace for the counterfactual
breach, as ``watch_trace`` does.

Set-up streams the same warm-up replays in every run, telemetry that no
window sees; the window then streams its own replays back to back and
ends at the first tick that ends at or after ``--seconds``.  The replays checked are
drawn from those that finished.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from ref import control as ref_control
from ref.precision import F64

#: the warm-up's replays draw their noise from ``WARMUP + [index]``, the
#: same in every run; a window's from ``[seed, index]``, which numpy never
#: reads as the same words
WARMUP = [0, 0]


class Session:
    """One run of a control cell."""

    def __init__(self, config: Dict, cell: Dict, seed: int):
        from repro.core.spec import (FrequencyDomainSpec, TimeDomainSpec,
                                     UtilitySpec)
        self.config, self.cell, self.seed = config, cell, int(seed)
        self.rules = ref_control.Rules(config)
        s = self.rules.spec
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
        self.finished: List[Dict] = []
        self.stats: Dict = {}

    # -- program ------------------------------------------------------------

    def trace(self, replay: int, warmup: bool = False) -> np.ndarray:
        key = WARMUP + [replay] if warmup else [self.seed, replay]
        return ref_control.ramp(self.config["trace"], key)

    def loop(self, w: np.ndarray):
        """The loop ``watch_trace`` builds, over the replay ``w``."""
        from repro.control.controller import ControllerConfig, GridController
        from repro.control.detector import OnlineGoertzelDetector
        from repro.control.interventions import InterventionLadder
        from repro.control.loop import ControlLoop
        from repro.control.stream import ReplaySource
        from repro.kernels.goertzel.ops import trace_mean
        c, d, lad = (self.config["controller"], self.config["detector"],
                     self.config["ladder"])
        r = self.rules
        source = ReplaySource(w, r.dt, tick_s=d["tick_s"])
        ccfg = ControllerConfig(
            breach_w=r.breach_w, trigger_frac=c["trigger_frac"],
            release_frac=c["release_frac"], lead_s=c["lead_s"],
            sustain_ticks=c["sustain_ticks"],
            release_ticks=c["release_ticks"], max_level=c["max_level"])
        detector = OnlineGoertzelDetector(
            r.dt, r.freqs, window_s=d["window_s"],
            mean=float(trace_mean(w)), slope_window_s=d["slope_window_s"],
            threshold_w=ccfg.trigger_w, release_w=ccfg.release_w,
            sustain_s=c["sustain_ticks"] * d["tick_s"],
            cooldown_s=c["release_ticks"] * d["tick_s"])
        ladder = InterventionLadder(
            spec=self._spec, n_chips=self.config["n_chips"], dt=r.dt,
            release_amp_w=ccfg.release_w, design_method=lad["design_method"],
            headroom=lad["headroom"], stagger_groups=lad["stagger_groups"])
        return source, ControlLoop(
            source, detector, GridController(ccfg, r.freqs, detector.win),
            ladder, dispatch_ticks=lad["dispatch_ticks"],
            history_s=lad["history_s"])

    def counterfactual(self, raw: np.ndarray) -> int:
        """The offline monitor's first breach sample on the raw replay."""
        from repro.kernels.goertzel.ops import sliding_bin_power
        r = self.rules
        amps = np.asarray(sliding_bin_power(raw, r.dt, r.freqs, win=r.win))
        over = np.nonzero(amps.max(axis=1) > r.breach_w)[0]
        return int(over[0]) if len(over) else -1

    def replay(self, index: int, span, ticks: List[float],
               deadline: float = None, warmup: bool = False) -> bool:
        """Stream replay ``index`` tick by tick, appending each tick's
        seconds to ``ticks``; stop early at the first tick that ends at
        or after ``deadline``.  True if the replay finished."""
        source, loop = self.loop(self.trace(index, warmup))
        while source.cursor < source.n:
            t0 = time.perf_counter()
            with span("bench.tick"):
                loop.run(max_ticks=1)
            t1 = time.perf_counter()
            ticks.append(t1 - t0)
            if deadline is not None and t1 >= deadline:
                self._partial = loop.log
                return False
        with span("bench.counterfactual"):
            breach = self.counterfactual(source.raw)
        self.finished.append({
            "index": index, "raw": source.raw.copy(),
            "observed": source.observed().copy(),
            "series": loop.log.series,
            "records": [dataclasses.asdict(r) for r in loop.log.records],
            "breach": breach})
        return True

    def setup(self, span) -> None:
        """Stream the warm-up replays, which compile every shape the loop
        uses.  Each dispatch also builds programs of its own (the
        redesign's transforms close over the dispatched design and the
        length left in the trace); the window's dispatches see other
        telemetry and run with the persistent cache closed, so every
        window compiles its own, as a live monitor does."""
        with span("bench.warmup"):
            for index in range(1, self.cell["traffic"]["warmup_replays"] + 1):
                self.replay(index, span, [], warmup=True)
        self.finished.clear()

    def window(self, seconds: float, span) -> Dict:
        ticks: List[float] = []
        t0 = time.perf_counter()
        index = 1
        while True:
            with span("bench.replay"):
                done = self.replay(index, span, ticks, t0 + seconds)
            if not done:
                break
            index += 1
        elapsed = time.perf_counter() - t0
        lat = [r["latency_s"] for rep in self.finished
               for r in rep["records"] if r["action"].startswith("dispatch:")]
        lat += [r.latency_s for r in self._partial.records
                if r.action.startswith("dispatch:")]
        ms = 1e3 * np.asarray(ticks)
        self.stats = {"ticks": len(ticks), "replays": len(self.finished),
                      "window_s": elapsed, "dispatch_latencies_s": lat,
                      "tick_ms_p50": float(np.percentile(ms, 50)),
                      "tick_ms_p99": float(np.percentile(ms, 99)),
                      "tick_ms_max": float(ms.max()),
                      "ticks_over_budget": int(
                          (ms > 1e3 * self.config["detector"]["tick_s"])
                          .sum())}
        return {"tick_ms_p98": float(np.percentile(ms, 98)),
                "attempted": len(ticks), "failed": 0}

    def release(self) -> None:
        self._partial = None

    # -- check --------------------------------------------------------------

    def sample(self) -> List[Dict]:
        rng = np.random.default_rng([self.seed, 23])
        k = min(self.cell["check"]["replays"], len(self.finished))
        pick = sorted(rng.choice(len(self.finished), size=k, replace=False))
        return [self.finished[i] for i in pick]

    def check(self, q=F64, reps=None) -> List[Dict]:
        limits = self.cell["check"]
        reps = reps or self.sample()
        if not reps:
            raise AssertionError("no replay finished inside the window")
        band = limits["ambiguity_x_amp"] * limits["amp_dev"]
        want = [ref_control.replay_answers(r, self.rules, band_rel=band)
                for r in reps]
        got = ([ref_control.replay_answers(r, self.rules, q) for r in reps]
               if q is not F64 else
               [ref_control.program_answers(r, self.rules) for r in reps])
        nums = ref_control.compare(got, want, reps, self.rules, limits)
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in nums.items()]

    def context(self) -> Dict:
        return dict(self.stats)

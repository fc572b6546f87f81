"""Online sliding-Goertzel detector: the offline monitor, run per tick.

``OnlineGoertzelDetector`` runs the *fused* v2 monitor kernel by default
(``fused=True``): each ``step(chunk)`` consumes one control tick of
samples through ``sliding_monitor_fused(..., carry=)`` — the lane-major
Pallas kernel reduces per-bin amplitudes to the per-sample worst bin and
its escalation class in VMEM, the blocked
``core.telemetry.escalation_scan`` advances the shared escalation
machine, and the per-bin amplitudes the controller consumes are
recombined in O(K) from the kernel's streamed prefix state — no
``[m, K]`` amplitude block is ever materialized.  The per-sample worst
stream and escalation level ride along in the frame as extra telemetry.

``fused=False`` selects the amps-materializing path on the same v2
kernel (``sliding_bin_power(..., carry=)``): every per-sample per-bin
amplitude is emitted (``frame.tick_amps``), *bit-identical* to one
offline ``sliding_bin_power`` call on the concatenated trace (the parity
test in ``tests/test_control.py`` asserts this across uneven tick
boundaries) — the replay/counterfactual path.

On top of the amplitudes the detector maintains per-bin trend slopes
over a short trailing horizon — the signal the controller's slope-based
early warning projects forward to act *before* a breach.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.kernels.goertzel.ops import (monitor_carry_init, sliding_bin_power,
                                        sliding_carry_init,
                                        sliding_monitor_fused)


@dataclasses.dataclass
class DetectorFrame:
    """One tick of detector output, consumed by ``GridController``."""
    tick: int
    t_s: float                 # time of the tick's last sample
    sample_idx: int            # global index of the tick's last sample
    amps: np.ndarray           # [K] bin amplitudes at the last sample
    slopes: np.ndarray         # [K] amplitude trend, W/s
    warm: bool                 # one full window has streamed
    # amps-materializing path (fused=False) only:
    tick_amps: Optional[np.ndarray] = None   # [m, K] per-sample amplitudes
    # fused path (fused=True) only:
    tick_worst: Optional[np.ndarray] = None  # [m] per-sample worst-bin amp
    level: int = 0             # shared escalation machine's level after tick


class OnlineGoertzelDetector:
    """Incremental per-bin amplitude monitor with trend estimation.

    ``mean`` is the DC operating point removed before accumulation
    (see ``sliding_carry_init``); ``slope_window_s`` bounds the trailing
    horizon the per-bin slope is estimated over (endpoint difference of
    tick-end amplitudes — cheap and robust for the controller's
    project-forward early warning).

    ``fused=True`` (default) runs the fused monitor kernel (worst bin +
    escalation class in VMEM; see module docstring); ``threshold_w`` /
    ``release_w`` / ``sustain_s`` / ``cooldown_s`` configure its shared
    escalation machine (default threshold ``+inf``: the machine idles
    and the fused path is a pure fast monitor).  ``fused=False`` keeps
    the amps-materializing path with full ``tick_amps``.
    """

    def __init__(self, dt: float, freqs: Sequence[float], *,
                 window_s: float = 4.0, mean: float = 0.0,
                 slope_window_s: Optional[float] = None,
                 fused: bool = True, threshold_w: Optional[float] = None,
                 release_w: Optional[float] = None,
                 sustain_s: float = 1.0, cooldown_s: float = 2.0,
                 max_level: int = 3):
        self.dt = float(dt)
        self.freqs = tuple(float(f) for f in freqs)
        self.win = max(int(window_s / dt), 8)
        self.fused = bool(fused)
        self.threshold_w = float(threshold_w if threshold_w is not None
                                 else np.inf)
        self.release_w = float(release_w if release_w is not None
                               else self.threshold_w)
        self.sustain_n = max(int(sustain_s / dt), 1)
        self.cool_n = max(int(cooldown_s / dt), 1)
        self.max_level = int(max_level)
        if self.fused:
            self.carry = monitor_carry_init(self.dt, self.freqs,
                                            win=self.win, mean=mean)
        else:
            self.carry = sliding_carry_init(self.dt, self.freqs,
                                            win=self.win, mean=mean)
        horizon = slope_window_s if slope_window_s is not None else window_s / 2
        self._hist: Deque[Tuple[float, np.ndarray]] = collections.deque()
        self._horizon_s = max(float(horizon), self.dt)
        self._tick = 0

    @property
    def n_bins(self) -> int:
        return len(self.freqs)

    def step(self, chunk: np.ndarray) -> DetectorFrame:
        with obs.span("repro.detector.step"):
            tick_amps = tick_worst = None
            level = 0
            if self.fused:
                worst, levels, latest, self.carry = sliding_monitor_fused(
                    chunk, self.dt, self.freqs, win=self.win,
                    threshold=self.threshold_w, release=self.release_w,
                    sustain_n=self.sustain_n, cool_n=self.cool_n,
                    max_level=self.max_level, carry=self.carry)
                tick_worst = np.asarray(worst, np.float32)
                level = (int(levels[-1]) if len(levels)
                         else int(self.carry.esc[0]))
                offset = int(self.carry.sliding.offset)
            else:
                amps, self.carry = sliding_bin_power(
                    chunk, self.dt, self.freqs, win=self.win,
                    carry=self.carry)
                tick_amps = np.asarray(amps, np.float32)
                latest = (amps[-1] if len(amps)
                          else np.zeros(self.n_bins, np.float32))
                offset = int(self.carry.offset)
            last_idx = offset - 1
            t_s = last_idx * self.dt
            self._hist.append((t_s, latest))
            while (len(self._hist) > 2
                   and t_s - self._hist[0][0] > self._horizon_s):
                self._hist.popleft()
            t0, a0 = self._hist[0]
            span = t_s - t0
            slopes = ((latest - a0) / span if span > 0
                      else np.zeros(self.n_bins, np.float32))
            frame = DetectorFrame(
                tick=self._tick, t_s=t_s, sample_idx=last_idx,
                amps=np.asarray(latest, np.float32),
                slopes=np.asarray(slopes, np.float32),
                warm=last_idx >= self.win - 1,
                tick_amps=tick_amps, tick_worst=tick_worst, level=level)
            self._tick += 1
            return frame

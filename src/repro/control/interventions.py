"""The intervention ladder: what the controller dispatches, per level.

Each rung produces an ``Intervention`` — a named, parameterized
transform over the fleet's *future* power trace (what a ``ReplaySource``
applies to its not-yet-streamed suffix; on a live fleet the same three
knobs are config pushes):

  level 1  redesign   — re-run the warm-started ``design()`` path on the
                        recent observed history (scaled by a headroom
                        factor so the config covers where the trend is
                        going) and apply the resulting device + rack
                        mitigation pair exactly as the design engine
                        evaluates candidates.
  level 2  power cap  — clamp the aggregate into a band around the
                        operating point tight enough that the residual
                        bin amplitude sits below the release-hysteresis
                        level; the trough side is backed by a Firefly
                        ballast sized via ``ballast_gflops_for_floor``.
  level 3  stagger    — phase-stagger job groups with a ``1/(G*f)`` comb
                        of start offsets (a ``core.stagger``
                        ``StaggerSchedule``), which nulls the offending
                        bin: sum_g e^{-2*pi*i*f*g/(G*f)} = 0.

Rungs are cumulative — level 2 holds both the redesign and the cap —
mirroring how the paper layers mitigations (Sec. IV) and how the
Emerald Conductor escalates orchestrator actions.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.ballast_inject import ballast_gflops_for_floor
from repro.core.engine import design
from repro.core.hardware import DEFAULT_HW, Hardware
from repro.core.stagger import StaggerSchedule


@dataclasses.dataclass
class Intervention:
    """A dispatched action: a transform over future aggregate power plus
    a JSON-safe parameter summary for the ``ControlLog``."""
    name: str
    params: Dict
    transform: Callable[[np.ndarray, float], np.ndarray]


def redesign_intervention(spec, history_w: np.ndarray, dt: float,
                          n_chips: int, *, hw: Hardware = DEFAULT_HW,
                          method: str = "grid", warmstart=None,
                          headroom: float = 1.25) -> Optional[Intervention]:
    """Rung 1: warm-started mitigation re-design on observed history.

    The design target is the history with its AC component scaled by
    ``headroom`` — the config must cover where the amplitude trend is
    going, not where it was.  Returns None when the design path finds no
    feasible config or a do-nothing config (nothing to dispatch — the
    controller escalates to the next rung on its own)."""
    w = np.asarray(history_w, np.float32)
    mean = float(w.mean())
    target = (mean + headroom * (w - mean)).astype(np.float32)
    sol = design(spec, target, dt, n_chips, method=method, hw=hw,
                 warmstart=warmstart)
    if sol is None:
        return None
    gpu = sol.get("device_mitigation")
    bat = sol.get("rack_mitigation")
    if gpu is None and bat is None:
        return None

    def transform(future: np.ndarray, dt_: float) -> np.ndarray:
        out = jnp.asarray(future, jnp.float32)
        if gpu is not None:
            # per-chip device mitigation, exactly as _design_eval applies it
            out = gpu.apply_jax(out / n_chips, dt_)[0] * n_chips
        if bat is not None:
            out = bat.apply_jax(out, dt_)[0]
        return np.asarray(out, np.float32)

    return Intervention(
        name="redesign",
        params={"mpf_frac": float(sol.get("mpf_frac") or 0.0),
                "battery_capacity_j": float(sol.get("battery_capacity_j")
                                            or 0.0),
                "energy_overhead": float(sol.get("energy_overhead", 0.0)),
                "method": sol.get("method", method),
                "headroom": headroom},
        transform=transform)


def power_cap_intervention(history_w: np.ndarray, dt: float, *,
                           release_amp_w: float, n_chips: int,
                           hw: Hardware = DEFAULT_HW,
                           band_frac: float = 0.5) -> Intervention:
    """Rung 2: clamp the aggregate into ``mean ± band_frac*release_amp_w``.

    A hard clamp turns a large oscillation into a square-ish residual
    whose fundamental is ``4/pi`` times the half-band, so ``band_frac=0.5``
    keeps the residual bin amplitude at most ``0.64 * release_amp_w`` —
    safely below the release-hysteresis level.  The floor side is what
    the Firefly ballast provides; its required size is reported in the
    params so the orchestrator can schedule the burn."""
    w = np.asarray(history_w, np.float64)
    mean = float(w.mean())
    half_band = band_frac * float(release_amp_w)
    cap_w = mean + half_band
    floor_w = mean - half_band
    gflops = ballast_gflops_for_floor(w, dt, floor_w, n_chips, hw=hw)

    def transform(future: np.ndarray, dt_: float) -> np.ndarray:
        return np.clip(future, np.float32(floor_w),
                       np.float32(cap_w)).astype(np.float32)

    return Intervention(
        name="power_cap",
        params={"cap_w": cap_w, "floor_w": floor_w,
                "ballast_gflops": float(gflops)},
        transform=transform)


def stagger_intervention(f_hz: float, dt: float, *, n_groups: int = 4,
                         history_w: Optional[np.ndarray] = None
                         ) -> Intervention:
    """Rung 3: phase-stagger ``n_groups`` job groups by a ``1/(G*f)``
    offset comb (a ``StaggerSchedule``), decohering the offending bin.

    The aggregate becomes the mean of time-shifted replicas
    (edge-padded, like ``waveform.aggregate``); at ``f_hz`` the comb
    factor ``|sum_g e^{-2*pi*i*f*g/(G*f)}| / G`` is exactly zero, and
    the reported ``comb_attenuation`` gives the residual at any other
    frequency."""
    G = max(int(n_groups), 2)
    offsets = np.arange(G) / (G * float(f_hz))
    shifts = np.round(offsets / dt).astype(np.int64)
    atten = float(abs(np.exp(-2j * np.pi * f_hz * offsets).mean()))
    if history_w is not None and len(history_w):
        ramp = float(np.ptp(np.asarray(history_w, np.float64)) / G
                     / max(float(offsets[1]), dt))
    else:
        ramp = 0.0
    sched = StaggerSchedule(offsets_s=offsets.astype(np.float64),
                            rack_ramp_w_per_s=ramp)

    def transform(future: np.ndarray, dt_: float) -> np.ndarray:
        n = len(future)
        if n == 0:
            return future
        idx = np.clip(np.arange(n)[None, :] - shifts[:, None], 0, n - 1)
        return np.asarray(future, np.float32)[idx].mean(axis=0) \
            .astype(np.float32)

    return Intervention(
        name="stagger",
        params={"f_hz": float(f_hz), "n_groups": G,
                "offsets_s": [float(o) for o in offsets],
                "comb_attenuation": atten,
                "total_s": sched.total_s},
        transform=transform)


class InterventionLadder:
    """Level → cumulative intervention stack, with per-level caching so a
    re-dispatch at a higher level doesn't re-run lower rungs' solvers."""

    RUNGS = ("redesign", "power_cap", "stagger")

    def __init__(self, *, spec, n_chips: int, dt: float,
                 release_amp_w: float, hw: Hardware = DEFAULT_HW,
                 design_method: str = "grid", warmstart=None,
                 headroom: float = 1.25, stagger_groups: int = 4):
        self.spec = spec
        self.n_chips = int(n_chips)
        self.dt = float(dt)
        self.release_amp_w = float(release_amp_w)
        self.hw = hw
        self.design_method = design_method
        self.warmstart = warmstart
        self.headroom = headroom
        self.stagger_groups = int(stagger_groups)
        self._cache: Dict[int, Optional[Intervention]] = {}
        #: wall-clock seconds of each cached rung's build, failed ones too
        self.build_latency_s: Dict[int, float] = {}

    def build(self, rung: int, history_w: np.ndarray,
              f_hz: float) -> Optional[Intervention]:
        """Build (or fetch) the intervention for ladder rung 1..3.  A
        fresh build's wall-clock latency, its ``repro.ladder.build``
        span's duration, is kept in ``build_latency_s[rung]``."""
        cached = rung in self._cache
        with obs.span("repro.ladder.build", rung=rung, cached=cached) as sp:
            if cached:
                return self._cache[rung]
            if rung == 1:
                iv = redesign_intervention(
                    self.spec, history_w, self.dt, self.n_chips, hw=self.hw,
                    method=self.design_method, warmstart=self.warmstart,
                    headroom=self.headroom)
            elif rung == 2:
                iv = power_cap_intervention(
                    history_w, self.dt, release_amp_w=self.release_amp_w,
                    n_chips=self.n_chips, hw=self.hw)
            else:
                iv = stagger_intervention(f_hz, self.dt,
                                          n_groups=self.stagger_groups,
                                          history_w=history_w)
        self.build_latency_s[rung] = sp.duration_s
        self._cache[rung] = iv
        return iv

    def release(self, rung: int) -> None:
        """Forget a rung's cached config so a future re-escalation
        re-solves against fresh history."""
        self._cache.pop(rung, None)
        self.build_latency_s.pop(rung, None)

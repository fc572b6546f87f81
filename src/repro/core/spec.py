"""Utility specifications (paper Sec. III) and compliance validation.

Time-domain: ramp-up / ramp-down rate limits (W/s) and a dynamic power
range (max deviation within a sliding window) — Fig. 4. Frequency-domain:
a critical band and a cap on the fraction of AC spectral energy inside it.

``UtilitySpec.validate`` is the numpy reference; ``validate_jax`` is the
pure traced mirror the batched scenario engine jits/vmaps, returning
per-violation boolean flags instead of a string list so verdicts
vectorize.

A spec splits into two halves with different compilation roles.  Its
*family* (``family()``) is everything that fixes computation shape —
band edges (which select FFT bins), the ramp/dynamic-range window sizes,
and whether a bin-amplitude check exists at all — and stays a static jit
argument.  Its *limits* (``limits()``) are the pure numeric thresholds
the metrics are compared against, and can be traced: ``validate_jax`` /
``loss_jax`` accept ``limits=`` overrides, so one compiled executable
serves every spec of the same family (lenient / moderate / tight at any
job scale).  This is what lets the serve path answer a stream of
differently-sized jobs without retracing per query.

``loss_jax`` turns the same metrics into a *smooth scalar objective* for
gradient-based mitigation design (core/engine.py ``design_gradient``):
each hard threshold comparison becomes a quadratic hinge on the
normalized excess, so the loss is zero on (margin-shrunk) compliant
waveforms, positive and differentiable outside them, and its components
line up one-to-one with the violation flags.  Both paths share
``_metrics_jax`` so the objective can never drift from the verdict.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spectrum import (band_amplitude_w, band_amplitude_w_jax,
                                 band_energy_fraction,
                                 band_energy_fraction_jax)

VIOLATION_ORDER = ("ramp_up", "ramp_down", "dynamic_range",
                   "band_energy", "band_amplitude")

# the traced-threshold keys of ``UtilitySpec.limits()`` (band_amplitude_w
# is present only when the family declares that check)
LIMIT_KEYS = ("ramp_up_w_per_s", "ramp_down_w_per_s", "dynamic_range_w",
              "max_energy_fraction", "min_ac_rms_frac",
              "max_bin_amplitude_w")


@dataclasses.dataclass(frozen=True)
class TimeDomainSpec:
    ramp_up_w_per_s: float
    ramp_down_w_per_s: float
    dynamic_range_w: float          # allowed peak-to-trough in window
    window_s: float = 1.0
    # ramp measurement granularity: utilities meter over >= this interval,
    # so single-sample dP/dt is averaged over ramp_window_s first
    ramp_window_s: float = 0.1


@dataclasses.dataclass(frozen=True)
class FrequencyDomainSpec:
    band_hz: Tuple[float, float] = (0.1, 20.0)
    max_energy_fraction: float = 0.2
    max_bin_amplitude_w: Optional[float] = None
    # the fraction cap only applies when the AC component is material:
    # a flat load with microscopic residual wobble is compliant even if
    # 100% of that wobble sits in-band
    min_ac_rms_frac: float = 0.005


@dataclasses.dataclass(frozen=True)
class UtilitySpec:
    name: str
    time: TimeDomainSpec
    freq: FrequencyDomainSpec

    # -- the family / limits split (compiled-executable reuse) --------------

    def limits(self) -> Dict[str, jnp.ndarray]:
        """The numeric thresholds as a traced-friendly dict of f32 scalars.

        Feed one family's executable a different spec's limits and it
        judges under that spec without retracing.  The bin-amplitude key
        is present iff the check exists (its existence is structural —
        part of the family)."""
        lim = {
            "ramp_up_w_per_s": jnp.asarray(self.time.ramp_up_w_per_s,
                                           jnp.float32),
            "ramp_down_w_per_s": jnp.asarray(self.time.ramp_down_w_per_s,
                                             jnp.float32),
            "dynamic_range_w": jnp.asarray(self.time.dynamic_range_w,
                                           jnp.float32),
            "max_energy_fraction": jnp.asarray(self.freq.max_energy_fraction,
                                               jnp.float32),
            "min_ac_rms_frac": jnp.asarray(self.freq.min_ac_rms_frac,
                                           jnp.float32),
        }
        if self.freq.max_bin_amplitude_w is not None:
            lim["max_bin_amplitude_w"] = jnp.asarray(
                self.freq.max_bin_amplitude_w, jnp.float32)
        return lim

    def family(self) -> "UtilitySpec":
        """The shape-determining residue of this spec: limits canonicalized
        to 1.0, name dropped.  Two specs with equal families compile to the
        SAME executable when their ``limits()`` are passed as traced
        arguments — the compiled-catalog reuse key of the serve path."""
        return UtilitySpec(
            "family",
            TimeDomainSpec(ramp_up_w_per_s=1.0, ramp_down_w_per_s=1.0,
                           dynamic_range_w=1.0, window_s=self.time.window_s,
                           ramp_window_s=self.time.ramp_window_s),
            FrequencyDomainSpec(
                band_hz=self.freq.band_hz, max_energy_fraction=1.0,
                max_bin_amplitude_w=(None if self.freq.max_bin_amplitude_w
                                     is None else 1.0),
                min_ac_rms_frac=1.0))

    def validate(self, w: np.ndarray, dt: float) -> "SpecReport":
        v: List[str] = []
        m: Dict[str, float] = {}
        # ---- ramps (averaged over the metering window)
        k = max(int(self.time.ramp_window_s / dt), 1)
        if len(w) > k:
            box = np.convolve(w, np.ones(k) / k, mode="valid")
            dp = np.diff(box) / dt
            m["max_ramp_up_w_per_s"] = float(dp.max(initial=0.0))
            m["max_ramp_down_w_per_s"] = float(-dp.min(initial=0.0))
            if m["max_ramp_up_w_per_s"] > self.time.ramp_up_w_per_s:
                v.append("ramp_up")
            if m["max_ramp_down_w_per_s"] > self.time.ramp_down_w_per_s:
                v.append("ramp_down")
        # ---- dynamic range in sliding window
        n = max(int(self.time.window_s / dt), 2)
        if len(w) >= n:
            # stride for O(len) estimate
            stride = max(n // 8, 1)
            rng = 0.0
            for i in range(0, len(w) - n, stride):
                seg = w[i:i + n]
                rng = max(rng, float(seg.max() - seg.min()))
            m["dynamic_range_w"] = rng
            if rng > self.time.dynamic_range_w:
                v.append("dynamic_range")
        # ---- frequency domain
        f_lo, f_hi = self.freq.band_hz
        frac = band_energy_fraction(w, dt, f_lo, f_hi)
        m["band_energy_fraction"] = frac
        ac_rms = float(np.std(w))
        m["ac_rms_frac"] = ac_rms / max(float(np.mean(w)), 1e-9)
        material = m["ac_rms_frac"] >= self.freq.min_ac_rms_frac
        if material and frac > self.freq.max_energy_fraction:
            v.append("band_energy")
        if self.freq.max_bin_amplitude_w is not None:
            amp = band_amplitude_w(w, dt, f_lo, f_hi)
            m["band_bin_amplitude_w"] = amp
            if amp > self.freq.max_bin_amplitude_w:
                v.append("band_amplitude")
        return SpecReport(ok=not v, violations=tuple(v), metrics=m)

    def _metrics_jax(self, w: jnp.ndarray, dt: float
                     ) -> Dict[str, jnp.ndarray]:
        """The traced metric set shared by ``validate_jax`` (hard flags)
        and ``loss_jax`` (smooth hinges).  Keys are present iff the
        waveform is long enough to measure them — lengths are static, so
        the key set is too."""
        w = jnp.asarray(w, jnp.float32)
        m: Dict[str, jnp.ndarray] = {}
        # ---- ramps (averaged over the metering window)
        k = max(int(self.time.ramp_window_s / dt), 1)
        if w.shape[-1] > k:
            # HIGHEST: a TPU convolution otherwise rounds its f32 operands
            # to bf16, a few hundred watts at fleet scale
            box = jnp.convolve(w, jnp.ones(k, jnp.float32) / k, mode="valid",
                               precision=jax.lax.Precision.HIGHEST)
            dp = jnp.diff(box) / dt
            m["max_ramp_up_w_per_s"] = jnp.maximum(dp.max(), 0.0)
            m["max_ramp_down_w_per_s"] = jnp.maximum(-dp.min(), 0.0)
        # ---- dynamic range in sliding window (same strided starts as the
        # numpy path, but as one [windows, n] gather instead of a loop)
        n = max(int(self.time.window_s / dt), 2)
        if w.shape[-1] >= n:
            starts = np.arange(0, w.shape[-1] - n, max(n // 8, 1))
            if len(starts):
                seg = w[starts[:, None] + np.arange(n)[None, :]]
                rng = (seg.max(axis=1) - seg.min(axis=1)).max()
            else:
                # exactly one window: the strided loop body never runs and
                # the numpy path reports 0.0 — mirror that, don't drop the key
                rng = jnp.asarray(0.0, jnp.float32)
            m["dynamic_range_w"] = rng
        # ---- frequency domain
        f_lo, f_hi = self.freq.band_hz
        m["band_energy_fraction"] = band_energy_fraction_jax(w, dt, f_lo, f_hi)
        m["ac_rms_frac"] = jnp.std(w) / jnp.maximum(jnp.mean(w), 1e-9)
        if self.freq.max_bin_amplitude_w is not None:
            m["band_bin_amplitude_w"] = band_amplitude_w_jax(w, dt, f_lo, f_hi)
        return m

    def validate_jax(self, w: jnp.ndarray, dt: float,
                     limits: Optional[Dict[str, jnp.ndarray]] = None
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray],
                                Dict[str, jnp.ndarray]]:
        """Traced mirror of ``validate``: (ok, violation flags, metrics).

        Waveform length and dt are static (they fix window/bin shapes).
        Thresholds default to this spec's own values; passing ``limits``
        (another same-family spec's ``limits()``) judges under those
        thresholds instead — the engine passes ``self.family()`` as the
        static spec and the real limits as a traced pytree, so distinct
        specs reuse one executable.  Use ``report_from_arrays`` to rebuild
        a ``SpecReport`` from one row of vmapped outputs.
        """
        lim = self.limits() if limits is None else limits
        m = self._metrics_jax(w, dt)
        flags: Dict[str, jnp.ndarray] = {}
        false = jnp.asarray(False)
        if "max_ramp_up_w_per_s" in m:
            flags["ramp_up"] = (m["max_ramp_up_w_per_s"]
                                > lim["ramp_up_w_per_s"])
            flags["ramp_down"] = (m["max_ramp_down_w_per_s"]
                                  > lim["ramp_down_w_per_s"])
        else:
            flags["ramp_up"] = flags["ramp_down"] = false
        if "dynamic_range_w" in m:
            flags["dynamic_range"] = (m["dynamic_range_w"]
                                      > lim["dynamic_range_w"])
        else:
            flags["dynamic_range"] = false
        material = m["ac_rms_frac"] >= lim["min_ac_rms_frac"]
        flags["band_energy"] = material & (m["band_energy_fraction"]
                                           > lim["max_energy_fraction"])
        if "band_bin_amplitude_w" in m:
            flags["band_amplitude"] = (m["band_bin_amplitude_w"]
                                       > lim["max_bin_amplitude_w"])
        else:
            flags["band_amplitude"] = false
        ok = ~(flags["ramp_up"] | flags["ramp_down"] | flags["dynamic_range"]
               | flags["band_energy"] | flags["band_amplitude"])
        return ok, flags, m

    def loss_jax(self, w: jnp.ndarray, dt: float, *, margin: float = 0.0,
                 limits: Optional[Dict[str, jnp.ndarray]] = None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Smooth scalar compliance objective: ``(total, components)``.

        Each component is the squared hinge of a ``validate_jax`` metric's
        normalized excess over its ``(1 - margin)``-shrunk limit — zero on
        (margin-)compliant waveforms, positive and differentiable outside,
        keyed like the violation flags.  ``margin`` gives a gradient
        optimizer strictly-interior targets so the final *hard* validation
        of its solution has slack.  The band-energy materiality gate
        relaxes to a sigmoid (the hard ``>=`` would zero the gradient at
        the gate); everything upstream uses hard max/min reductions, whose
        subgradients are exact on the active window.  ``limits`` overrides
        the thresholds like ``validate_jax``'s (family/limits split).
        """
        lims = self.limits() if limits is None else limits
        m = self._metrics_jax(w, dt)
        zero = jnp.asarray(0.0, jnp.float32)

        def hinge(metric, limit):
            lim = jnp.maximum(jnp.asarray(limit, jnp.float32), 1e-30)
            return jnp.square(jnp.maximum(metric / lim - (1.0 - margin), 0.0))

        comps: Dict[str, jnp.ndarray] = {
            "ramp_up": (hinge(m["max_ramp_up_w_per_s"],
                              lims["ramp_up_w_per_s"])
                        if "max_ramp_up_w_per_s" in m else zero),
            "ramp_down": (hinge(m["max_ramp_down_w_per_s"],
                                lims["ramp_down_w_per_s"])
                          if "max_ramp_down_w_per_s" in m else zero),
            "dynamic_range": (hinge(m["dynamic_range_w"],
                                    lims["dynamic_range_w"])
                              if "dynamic_range_w" in m else zero),
        }
        min_frac = jnp.maximum(jnp.asarray(lims["min_ac_rms_frac"],
                                           jnp.float32), 1e-9)
        material = jax.nn.sigmoid((m["ac_rms_frac"] / min_frac - 1.0) / 0.25)
        # far below materiality the sigmoid tail would still leak a loss
        # on numerically-flat waveforms (whose band fraction is noise);
        # hard-zero it there — the gradient only matters near the gate
        material = jnp.where(m["ac_rms_frac"] < 0.5 * min_frac, 0.0,
                             material)
        comps["band_energy"] = material * hinge(m["band_energy_fraction"],
                                                lims["max_energy_fraction"])
        comps["band_amplitude"] = (hinge(m["band_bin_amplitude_w"],
                                         lims["max_bin_amplitude_w"])
                                   if "band_bin_amplitude_w" in m else zero)
        total = sum(comps[v] for v in VIOLATION_ORDER)
        return total, comps


def report_from_arrays(ok, flags: Dict, metrics: Dict) -> "SpecReport":
    """Rebuild a SpecReport from (one row of) ``validate_jax`` outputs."""
    violations = tuple(v for v in VIOLATION_ORDER
                       if v in flags and bool(np.asarray(flags[v])))
    return SpecReport(ok=bool(np.asarray(ok)), violations=violations,
                      metrics={k: float(np.asarray(v))
                               for k, v in metrics.items()})


@dataclasses.dataclass(frozen=True)
class SpecReport:
    ok: bool
    violations: Tuple[str, ...]
    metrics: Dict[str, float]


def example_specs(job_mw: float) -> Dict[str, UtilitySpec]:
    """Representative specs at job scale (paper: '10 MW dynamic range on a
    100 MW job' is the tight case GPU smoothing alone cannot meet)."""
    P = job_mw * 1e6
    return {
        "lenient": UtilitySpec(
            "lenient",
            TimeDomainSpec(ramp_up_w_per_s=0.10 * P, ramp_down_w_per_s=0.10 * P,
                           dynamic_range_w=0.40 * P),
            FrequencyDomainSpec((0.1, 20.0), 0.5)),
        "moderate": UtilitySpec(
            "moderate",
            TimeDomainSpec(ramp_up_w_per_s=0.05 * P, ramp_down_w_per_s=0.05 * P,
                           dynamic_range_w=0.20 * P),
            FrequencyDomainSpec((0.1, 20.0), 0.2)),
        "tight": UtilitySpec(
            "tight",
            TimeDomainSpec(ramp_up_w_per_s=0.02 * P, ramp_down_w_per_s=0.02 * P,
                           dynamic_range_w=0.10 * P),
            FrequencyDomainSpec((0.1, 20.0), 0.1)),
    }

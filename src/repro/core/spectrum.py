"""Frequency-domain analysis of power waveforms (paper Fig. 3, Sec. III).

Numpy routines are the analysis-side reference; each has a pure-jnp mirror
(``*_jax``) used inside the jit/vmap scenario engine (core/engine.py).  The
*streaming* per-bin monitor used by the backstop lives in kernels/goertzel
(Pallas) with its jnp oracle in kernels/goertzel/ref.py.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# the serve path's grid-critical probe frequencies: inter-area (<1 Hz),
# plant-coupling (1-2.5 Hz), the paper band's center, and low torsional
# bins — the spectral fingerprint the warm-start predictor reads
GRID_CRITICAL_HZ = (0.25, 0.5, 1.0, 2.0, 3.0, 5.0, 9.0)


def goertzel_bin_amplitudes(x: np.ndarray, dt: float,
                            freqs: Tuple[float, ...] = GRID_CRITICAL_HZ
                            ) -> np.ndarray:
    """Single-bin DFT amplitudes (watts) of the AC component at ``freqs``.

    This is the Goertzel evaluation the sliding monitor kernel performs,
    collapsed to one full-trace window: a modulated sum per target bin,
    O(n*K) with no FFT plan — the cheap spectral fingerprint the serve
    path's feature extractor uses (``serve/warmstart.py``).  Amplitude
    convention matches ``spectrum`` sans Hann window: a pure sine of
    amplitude A at a bin frequency reports ~A.
    """
    x = np.asarray(x, np.float64)
    n = len(x)
    if n == 0:
        return np.zeros(len(freqs))
    xac = x - x.mean()
    t = np.arange(n) * dt
    phases = np.exp(-2j * np.pi * np.asarray(freqs)[:, None] * t[None, :])
    return np.abs(phases @ xac) * 2.0 / n


def goertzel_bin_amplitudes_jax(x: jnp.ndarray, dt: float,
                                freqs: Tuple[float, ...] = GRID_CRITICAL_HZ
                                ) -> jnp.ndarray:
    """jnp mirror of ``goertzel_bin_amplitudes`` (phases are static)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[-1]
    xac = x - x.mean()
    t = np.arange(n) * dt
    ph = np.exp(-2j * np.pi * np.asarray(freqs)[:, None] * t[None, :])
    # HIGHEST: a TPU matmul otherwise rounds its f32 operands to bf16
    hi = jax.lax.Precision.HIGHEST
    re = jnp.matmul(jnp.asarray(ph.real, jnp.float32), xac, precision=hi)
    im = jnp.matmul(jnp.asarray(ph.imag, jnp.float32), xac, precision=hi)
    return jnp.sqrt(re * re + im * im) * 2.0 / n


def spectrum(x: np.ndarray, dt: float) -> Tuple[np.ndarray, np.ndarray]:
    """One-sided amplitude spectrum of the AC component."""
    x = np.asarray(x, np.float64)
    xac = x - x.mean()
    n = len(xac)
    mag = np.abs(np.fft.rfft(xac * np.hanning(n))) * 2.0 / n
    freqs = np.fft.rfftfreq(n, dt)
    return freqs, mag


def band_energy_fraction(x: np.ndarray, dt: float,
                         f_lo: float, f_hi: float) -> float:
    """Fraction of total AC spectral energy inside [f_lo, f_hi]."""
    freqs, mag = spectrum(x, dt)
    e = mag ** 2
    tot = e[1:].sum()
    if tot <= 0:
        return 0.0
    sel = (freqs >= f_lo) & (freqs <= f_hi)
    sel[0] = False  # DC is not part of the AC energy budget
    return float(e[sel].sum() / tot)


def dominant_frequency(x: np.ndarray, dt: float) -> float:
    freqs, mag = spectrum(x, dt)
    if len(mag) < 2:
        return 0.0
    return float(freqs[1:][np.argmax(mag[1:])])


def band_amplitude_w(x: np.ndarray, dt: float, f_lo: float, f_hi: float) -> float:
    """Peak single-bin amplitude (watts) inside the critical band."""
    freqs, mag = spectrum(x, dt)
    sel = (freqs >= f_lo) & (freqs <= f_hi)
    return float(mag[sel].max()) if sel.any() else 0.0


def critical_band_report(x: np.ndarray, dt: float) -> Dict[str, float]:
    """The paper's bands: <1 Hz (inter-area), 1-2.5 Hz (plant coupling),
    7-100 Hz (shaft torsional)."""
    return {
        "sub_1hz": band_energy_fraction(x, dt, 0.05, 1.0),
        "plant_1_2p5hz": band_energy_fraction(x, dt, 1.0, 2.5),
        "torsional_7_100hz": band_energy_fraction(x, dt, 7.0, 100.0),
        "paper_band_0p2_3hz": band_energy_fraction(x, dt, 0.2, 3.0),
        "dominant_hz": dominant_frequency(x, dt),
    }


# ---------------------------------------------------------------------------
# jit/vmap-able mirrors.  Band edges and dt are static (they select FFT bins,
# which fixes the computation shape); the waveform is the traced input.
# ---------------------------------------------------------------------------

def spectrum_jax(x: jnp.ndarray, dt: float) -> Tuple[np.ndarray, jnp.ndarray]:
    """One-sided amplitude spectrum of the AC component (freqs are static)."""
    x = jnp.asarray(x, jnp.float32)
    xac = x - x.mean()
    n = x.shape[-1]
    mag = jnp.abs(jnp.fft.rfft(xac * jnp.asarray(np.hanning(n), jnp.float32)))
    mag = mag * 2.0 / n
    freqs = np.fft.rfftfreq(n, dt)
    return freqs, mag


def _band_mask(freqs: np.ndarray, f_lo: float, f_hi: float) -> np.ndarray:
    sel = (freqs >= f_lo) & (freqs <= f_hi)
    sel[0] = False  # DC is not part of the AC energy budget
    return sel


def band_energy_fraction_jax(x: jnp.ndarray, dt: float,
                             f_lo: float, f_hi: float) -> jnp.ndarray:
    freqs, mag = spectrum_jax(x, dt)
    e = mag ** 2
    tot = e[1:].sum()
    frac = e[_band_mask(freqs, f_lo, f_hi)].sum() / jnp.maximum(tot, 1e-30)
    return jnp.where(tot > 0, frac, 0.0)


def band_amplitude_w_jax(x: jnp.ndarray, dt: float,
                         f_lo: float, f_hi: float) -> jnp.ndarray:
    freqs, mag = spectrum_jax(x, dt)
    sel = (freqs >= f_lo) & (freqs <= f_hi)
    if not sel.any():
        return jnp.asarray(0.0, jnp.float32)
    return mag[sel].max()


def dominant_frequency_jax(x: jnp.ndarray, dt: float) -> jnp.ndarray:
    freqs, mag = spectrum_jax(x, dt)
    if len(freqs) < 2:
        return jnp.asarray(0.0, jnp.float32)
    return jnp.asarray(freqs, jnp.float32)[1:][jnp.argmax(mag[1:])]


def critical_band_report_jax(x: jnp.ndarray, dt: float) -> Dict[str, jnp.ndarray]:
    """jnp mirror of ``critical_band_report`` (one rfft, five reductions)."""
    freqs, mag = spectrum_jax(x, dt)
    e = mag ** 2
    tot = e[1:].sum()

    def frac(f_lo, f_hi):
        val = e[_band_mask(freqs, f_lo, f_hi)].sum() / jnp.maximum(tot, 1e-30)
        return jnp.where(tot > 0, val, 0.0)

    dom = (jnp.asarray(freqs, jnp.float32)[1:][jnp.argmax(mag[1:])]
           if len(freqs) >= 2 else jnp.asarray(0.0, jnp.float32))
    return {
        "sub_1hz": frac(0.05, 1.0),
        "plant_1_2p5hz": frac(1.0, 2.5),
        "torsional_7_100hz": frac(7.0, 100.0),
        "paper_band_0p2_3hz": frac(0.2, 3.0),
        "dominant_hz": dom,
    }

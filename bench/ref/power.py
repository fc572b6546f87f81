"""Plain reference of the power study: synthesis, mitigations, spec.

Straight NumPy, float64 by default, written from the semantics the
configuration states and importing nothing of the program under test.
Every routine takes ``q`` (``precision.F64`` or ``precision.BF16``) and
rounds each intermediate array with it, so the same code is the
reference and, in bfloat16, the control.

Row-wise recurrences (GPU floor, battery, escalation) walk the samples
once with every row of a group in one vector, so a hundred rows cost one
pass over the trace.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from ref.precision import F64


# ---------------------------------------------------------------------------
# synthesis
# ---------------------------------------------------------------------------

def phase_list(period_s: float, comm_frac: float,
               moe_notch: bool) -> List[Tuple[float, str]]:
    """One training iteration as (duration_s, mode): compute then the
    gradient sync, or with an MoE dispatch notch inside the compute."""
    tc = period_s * (1 - comm_frac)
    if moe_notch:
        return [(tc * 0.33, "compute"), (period_s * comm_frac * 0.3, "comm"),
                (tc * 0.67, "compute"), (period_s * comm_frac * 0.7, "comm")]
    return [(tc, "compute"), (period_s * comm_frac, "comm")]


def phase_levels(phases, steps: int, dt: float,
                 mode_w: Dict[str, float]) -> np.ndarray:
    """Per-sample chip power of ``steps`` iterations, each phase at least
    one sample long."""
    seq = []
    for _ in range(steps):
        for dur, mode in phases:
            n = max(int(round(dur / dt)), 1)
            seq.append(np.full(n, float(mode_w[mode])))
    return np.concatenate(seq)


def edp_spikes(x: np.ndarray, dt: float, tdp_w: float, edp_factor: float,
               edp_window_s: float) -> np.ndarray:
    """A rise of more than a quarter TDP overshoots to ``edp_factor``
    times the new level for the EDP window."""
    out = x.copy()
    w = max(int(edp_window_s / dt), 1)
    for r in np.where(np.diff(x) > 0.25 * tdp_w)[0]:
        hi = min(r + 1 + w, len(out))
        out[r + 1:hi] = np.maximum(out[r + 1:hi], x[r + 1] * edp_factor)
    return out


def jitter_shifts(jitter_s: float, dt: float, seed: int,
                  sample_chips: int) -> np.ndarray:
    """The sampled chips' start offsets in samples: normal draws of the
    jitter seed, rounded half to even."""
    if jitter_s <= 0 or sample_chips <= 1:
        return np.zeros(1, np.int64)
    rng = np.random.default_rng(seed)
    sh = rng.normal(0.0, jitter_s / dt, size=sample_chips)
    return np.array([int(round(s)) for s in sh], np.int64)


def aggregate(chip: np.ndarray, n_chips: float, shifts: np.ndarray,
              loss: float, q=F64) -> np.ndarray:
    """Fleet power at the grid: the mean of the shifted chip replicas,
    held at the trace's ends, times the fleet, plus distribution loss.
    ``chip`` is one trace [L] or rows [R, L] sharing ``shifts``."""
    n = chip.shape[-1]
    idx = np.clip(np.arange(n)[None, :] - shifts[:, None], 0, n - 1)
    per_chip = q(q(chip)[..., idx].mean(axis=-2))
    return q(q(per_chip * n_chips) * (1.0 + loss))


# ---------------------------------------------------------------------------
# mitigations (rows [R, L], parameters [R])
# ---------------------------------------------------------------------------

def gpu_floor(p: np.ndarray, *, mpf_w, thresh_w, ramp_up_w, ramp_down_w,
              stop_n, cap_w, q=F64) -> np.ndarray:
    """Device power floor: the floor holds while the chip is active and
    ``stop_n`` samples after, output clipped at ``cap_w`` and moved at
    most the ramp step per sample.  Ramp steps are in W per sample."""
    p = q(p)
    out = np.empty_like(p)
    o = p[:, 0].copy()
    idle = np.zeros(p.shape[0])
    for i in range(p.shape[1]):
        x = p[:, i]
        idle = np.where(x > thresh_w, 0.0, idle + 1.0)
        floor = np.where(idle <= stop_n, mpf_w, 0.0)
        target = np.minimum(np.maximum(x, floor), cap_w)
        o = q(np.clip(target, q(o - ramp_down_w), q(o + ramp_up_w)))
        out[:, i] = o
    return out


def battery(p: np.ndarray, *, capacity_j, max_discharge_w, max_charge_w,
            efficiency, target_tau_s, initial_soc, dt: float, q=F64,
            start_target=None) -> np.ndarray:
    """Rack battery tracking an EMA grid target (started at the trace
    mean, or at ``start_target`` where rows are padded): discharges into
    peaks, charges in valleys, within its power limits, tapered over the
    outer tenths of its charge."""
    p = q(p)
    cap = np.maximum(capacity_j, 1e-9)
    alpha = dt / np.maximum(target_tau_s, dt)
    soc = q(initial_soc * cap)
    tgt = q(p.mean(axis=1) if start_target is None else start_target)
    out = np.empty_like(p)
    for i in range(p.shape[1]):
        x = p[:, i]
        tgt = q(tgt + q(alpha * q(x - tgt)))
        want = q(x - tgt)
        frac = q(soc / cap)
        taper_lo = np.clip(q(frac / 0.10), 0.0, 1.0)
        taper_hi = np.clip(q((1.0 - frac) / 0.10), 0.0, 1.0)
        dis = np.clip(want, 0.0, q(max_discharge_w * taper_lo))
        dis = np.minimum(dis, q(q(soc * efficiency) / dt))
        chg = np.clip(-want, 0.0, q(max_charge_w * taper_hi))
        chg = np.minimum(chg, q(q(q(cap - soc) / efficiency) / dt))
        out[:, i] = q(q(x - dis) + chg)
        soc = q(q(soc - q(dis * dt / efficiency)) + q(chg * dt * efficiency))
        soc = np.clip(soc, 0.0, cap)
    return out


def sliding_amplitudes(x: np.ndarray, dt: float, freqs: Sequence[float],
                       win: int, mean=None, q=F64) -> np.ndarray:
    """Sliding single-bin DFT amplitudes of the mean-removed trace(s):
    at sample i, 2 |sum over the last ``win`` samples of x e^{-2 pi i f t}|
    over the samples summed (fewer than ``win`` while the first window
    fills).  ``x`` [L] or [R, L] -> [..., L, K]."""
    x = q(x)
    m = x.mean(axis=-1, keepdims=True) if mean is None else mean
    xc = q(x - m)
    n = x.shape[-1]
    t = np.arange(n) * dt
    denom = np.minimum(np.arange(n) + 1, win)
    out = np.empty(x.shape + (len(freqs),))
    for j, f in enumerate(freqs):
        ph = np.exp(-2j * np.pi * f * t)
        cs_re = q(np.cumsum(q(xc * ph.real), axis=-1))
        cs_im = q(np.cumsum(q(xc * ph.imag), axis=-1))
        w_re, w_im = cs_re.copy(), cs_im.copy()
        w_re[..., win:] = q(cs_re[..., win:] - cs_re[..., :-win])
        w_im[..., win:] = q(cs_im[..., win:] - cs_im[..., :-win])
        out[..., j] = q(2.0 * q(np.hypot(w_re, w_im)) / denom)
    return out


def escalation_levels(worst: np.ndarray, *, threshold, win: int,
                      sustain_n: int, cool_n: int,
                      max_level: int = 3) -> np.ndarray:
    """The backstop's threshold machine per sample: ``sustain_n`` samples
    above ``threshold`` (once a full window has streamed) escalate a
    level, ``cool_n`` at or below it de-escalate one.  [R, L] -> [R, L]."""
    R, n = worst.shape
    level = np.zeros(R, np.int64)
    above = np.zeros(R, np.int64)
    below = np.zeros(R, np.int64)
    out = np.empty((R, n), np.int64)
    for i in range(n):
        live = i >= win - 1
        hit = (worst[:, i] > threshold) & live
        clear = ~hit
        above = np.where(hit, above + 1, 0)
        below = np.where(clear, below + 1, 0)
        esc = hit & (above >= sustain_n) & (level < max_level)
        level = np.where(esc, level + 1, level)
        above = np.where(esc, 0, above)
        de = clear & (below >= cool_n) & (level > 0)
        level = np.where(de, level - 1, level)
        below = np.where(de, 0, below)
        out[:, i] = level
    return out


def backstop(w: np.ndarray, *, critical_hz, window_s, sustain_s, cooldown_s,
             amp_threshold_w, alpha1, shed_frac, idle_frac, dt: float,
             q=F64) -> np.ndarray:
    """Telemetry backstop: the worst critical bin's sliding amplitude
    drives the threshold machine; level 1 scales the AC part by
    ``alpha1``, level 2 caps at ``shed_frac`` of the mean, level 3 drops
    to ``idle_frac`` of it."""
    w = q(w)
    win = max(int(window_s / dt), 8)
    amps = sliding_amplitudes(w, dt, critical_hz, win, q=q)
    levels = escalation_levels(
        amps.max(axis=-1), threshold=np.asarray(amp_threshold_w),
        win=win, sustain_n=max(int(sustain_s / dt), 1),
        cool_n=max(int(cooldown_s / dt), 1))
    mean = q(w.mean(axis=1, keepdims=True))
    a1 = np.asarray(alpha1)[:, None]
    sf = np.asarray(shed_frac)[:, None]
    idf = np.asarray(idle_frac)[:, None]
    out = np.where(levels == 1, q(mean + q(a1 * q(w - mean))), w)
    out = np.where(levels == 2, np.minimum(w, q(sf * mean)), out)
    return np.where(levels == 3, q(idf * mean), out)


# ---------------------------------------------------------------------------
# spec and spectra
# ---------------------------------------------------------------------------

def amplitude_spectrum(x: np.ndarray, dt: float, q=F64):
    """One-sided Hann-windowed amplitude spectrum of the AC part."""
    x = q(x)
    n = len(x)
    xac = q(x - x.mean())
    mag = q(np.abs(np.fft.rfft(q(xac * np.hanning(n)))) * 2.0 / n)
    return np.fft.rfftfreq(n, dt), mag


def band_fraction(freqs, mag, f_lo: float, f_hi: float, q=F64) -> float:
    """Share of the AC spectral energy (DC bin excluded) in [f_lo, f_hi]."""
    e = q(mag ** 2)
    tot = e[1:].sum()
    if tot <= 0:
        return 0.0
    sel = (freqs >= f_lo) & (freqs <= f_hi)
    sel[0] = False
    return float(q(e[sel].sum() / tot))


def spec_report(w: np.ndarray, dt: float, spec: Dict,
                q=F64) -> Tuple[Dict[str, float], Tuple[str, ...]]:
    """The spec's metrics and violations of one trace.  ``spec`` holds
    absolute limits: ramp_up_w_per_s, ramp_down_w_per_s, dynamic_range_w,
    window_s, ramp_window_s, band_hz, max_energy_fraction,
    min_ac_rms_frac."""
    w = q(w)
    m: Dict[str, float] = {}
    v: List[str] = []
    k = max(int(spec["ramp_window_s"] / dt), 1)
    if len(w) > k:
        box = q(np.convolve(w, np.ones(k) / k, mode="valid"))
        dp = q(np.diff(box) / dt)
        m["max_ramp_up_w_per_s"] = max(float(dp.max()), 0.0)
        m["max_ramp_down_w_per_s"] = max(float(-dp.min()), 0.0)
        if m["max_ramp_up_w_per_s"] > spec["ramp_up_w_per_s"]:
            v.append("ramp_up")
        if m["max_ramp_down_w_per_s"] > spec["ramp_down_w_per_s"]:
            v.append("ramp_down")
    n = max(int(spec["window_s"] / dt), 2)
    if len(w) >= n:
        starts = np.arange(0, len(w) - n, max(n // 8, 1))
        rng = 0.0
        if len(starts):
            seg = w[starts[:, None] + np.arange(n)[None, :]]
            rng = float((seg.max(axis=1) - seg.min(axis=1)).max())
        m["dynamic_range_w"] = rng
        if rng > spec["dynamic_range_w"]:
            v.append("dynamic_range")
    freqs, mag = amplitude_spectrum(w, dt, q)
    f_lo, f_hi = spec["band_hz"]
    m["band_energy_fraction"] = band_fraction(freqs, mag, f_lo, f_hi, q)
    m["ac_rms_frac"] = float(q(np.std(w) / max(float(np.mean(w)), 1e-9)))
    if (m["ac_rms_frac"] >= spec["min_ac_rms_frac"]
            and m["band_energy_fraction"] > spec["max_energy_fraction"]):
        v.append("band_energy")
    return m, tuple(v)


#: the limit each spec metric is judged against, and the violation it
#: raises
SPEC_LIMITS = {
    "max_ramp_up_w_per_s": ("ramp_up_w_per_s", "ramp_up"),
    "max_ramp_down_w_per_s": ("ramp_down_w_per_s", "ramp_down"),
    "dynamic_range_w": ("dynamic_range_w", "dynamic_range"),
    "band_energy_fraction": ("max_energy_fraction", "band_energy"),
}


def spec_margins(metrics: Dict[str, float], spec: Dict) -> Dict[str, float]:
    """Each judged metric's distance from its limit, in its own unit (the
    band-energy check only where the AC part is material)."""
    out = {}
    for k, (lim_key, _) in SPEC_LIMITS.items():
        if k not in metrics:
            continue
        if (k == "band_energy_fraction"
                and metrics["ac_rms_frac"] < spec["min_ac_rms_frac"]):
            continue
        out[k] = abs(metrics[k] - spec[lim_key])
    out["ac_rms_frac"] = abs(metrics["ac_rms_frac"] - spec["min_ac_rms_frac"])
    return out

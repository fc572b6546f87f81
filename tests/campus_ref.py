"""Plain float64 reference of a campus row: several jobs behind one grid
connection.  Straight NumPy, importing nothing of the program under test;
the tests hand it plain numbers.

Semantics: each tenant's iteration levels (one cycle in samples), tiled
over the window from a phase drawn from the row's seed, then the EDP
spikes; the GPU floor on each tenant's chip trace; each tenant's
jittered aggregate times its chips and (1 + distribution loss); the sum;
the rack battery on the sum; the spec's metrics and verdicts on the sum.

Draws: tenant ``k``'s phase is ``default_rng([seed, k]).integers(cycle
length)``; its jitter seed is ``seed`` for tenant 0 and
``default_rng([seed, k, 1]).integers(2**63)`` for the others; the jitter
shifts are ``sample_chips`` normal draws of that seed in samples,
rounded half to even.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def phase_list(period_s: float, comm_frac: float,
               moe_notch: bool) -> List[Tuple[float, str]]:
    tc = period_s * (1 - comm_frac)
    if moe_notch:
        return [(tc * 0.33, "compute"), (period_s * comm_frac * 0.3, "comm"),
                (tc * 0.67, "compute"), (period_s * comm_frac * 0.7, "comm")]
    return [(tc, "compute"), (period_s * comm_frac, "comm")]


def cycle(phases, dt: float, watts: Dict[str, float]) -> np.ndarray:
    return np.concatenate([np.full(max(int(round(d / dt)), 1), watts[m])
                           for d, m in phases])


def phase(seed: int, k: int, cycle_len: int) -> int:
    return int(np.random.default_rng([seed, k]).integers(cycle_len))


def tenant_seed(seed: int, k: int) -> int:
    if k == 0:
        return int(seed)
    return int(np.random.default_rng([seed, k, 1]).integers(2 ** 63))


def jitter_shifts(jitter_s: float, dt: float, seed: int,
                  sample_chips: int) -> np.ndarray:
    if jitter_s <= 0 or sample_chips <= 1:
        return np.zeros(1, np.int64)
    sh = np.random.default_rng(seed).normal(0.0, jitter_s / dt,
                                            size=sample_chips)
    return np.array([int(round(s)) for s in sh], np.int64)


def edp_spikes(x: np.ndarray, dt: float, tdp_w: float, factor: float,
               window_s: float) -> np.ndarray:
    out = x.copy()
    w = max(int(window_s / dt), 1)
    for r in np.where(np.diff(x) > 0.25 * tdp_w)[0]:
        hi = min(r + 1 + w, len(out))
        out[r + 1:hi] = np.maximum(out[r + 1:hi], x[r + 1] * factor)
    return out


def aggregate(chip: np.ndarray, n_chips: float, shifts: np.ndarray,
              loss: float) -> np.ndarray:
    n = len(chip)
    idx = np.clip(np.arange(n)[None, :] - shifts[:, None], 0, n - 1)
    return chip[idx].mean(axis=0) * n_chips * (1.0 + loss)


def gpu_floor(p: np.ndarray, *, mpf_w, thresh_w, ramp_up_w, ramp_down_w,
              stop_n, cap_w) -> np.ndarray:
    """Ramp steps in W per sample."""
    out = np.empty_like(p)
    o, idle = p[0], 0.0
    for i, x in enumerate(p):
        idle = 0.0 if x > thresh_w else idle + 1.0
        floor = mpf_w if idle <= stop_n else 0.0
        target = min(max(x, floor), cap_w)
        o = min(max(target, o - ramp_down_w), o + ramp_up_w)
        out[i] = o
    return out


def battery(p: np.ndarray, *, capacity_j, max_discharge_w, max_charge_w,
            efficiency, target_tau_s, initial_soc, dt: float) -> np.ndarray:
    cap = max(capacity_j, 1e-9)
    alpha = dt / max(target_tau_s, dt)
    soc, tgt = initial_soc * cap, p.mean()
    out = np.empty_like(p)
    for i, x in enumerate(p):
        tgt += alpha * (x - tgt)
        want = x - tgt
        frac = soc / cap
        taper_lo = min(max(frac / 0.10, 0.0), 1.0)
        taper_hi = min(max((1.0 - frac) / 0.10, 0.0), 1.0)
        dis = min(max(want, 0.0), max_discharge_w * taper_lo,
                  soc * efficiency / dt)
        chg = min(max(-want, 0.0), max_charge_w * taper_hi,
                  (cap - soc) / efficiency / dt)
        out[i] = x - dis + chg
        soc = min(max(soc - dis * dt / efficiency + chg * dt * efficiency,
                      0.0), cap)
    return out


def spec_report(w: np.ndarray, dt: float, spec: Dict
                ) -> Tuple[Dict[str, float], Tuple[str, ...]]:
    """``spec``: ramp_up_w_per_s, ramp_down_w_per_s, dynamic_range_w,
    window_s, ramp_window_s, band_hz, max_energy_fraction,
    min_ac_rms_frac (absolute limits)."""
    m: Dict[str, float] = {}
    v: List[str] = []
    k = max(int(spec["ramp_window_s"] / dt), 1)
    box = np.convolve(w, np.ones(k) / k, mode="valid")
    dp = np.diff(box) / dt
    m["max_ramp_up_w_per_s"] = max(float(dp.max()), 0.0)
    m["max_ramp_down_w_per_s"] = max(float(-dp.min()), 0.0)
    if m["max_ramp_up_w_per_s"] > spec["ramp_up_w_per_s"]:
        v.append("ramp_up")
    if m["max_ramp_down_w_per_s"] > spec["ramp_down_w_per_s"]:
        v.append("ramp_down")
    n = max(int(spec["window_s"] / dt), 2)
    starts = np.arange(0, len(w) - n, max(n // 8, 1))
    seg = w[starts[:, None] + np.arange(n)[None, :]]
    m["dynamic_range_w"] = float((seg.max(axis=1) - seg.min(axis=1)).max())
    if m["dynamic_range_w"] > spec["dynamic_range_w"]:
        v.append("dynamic_range")
    mag = np.abs(np.fft.rfft((w - w.mean()) * np.hanning(len(w))))
    freqs = np.fft.rfftfreq(len(w), dt)
    e = mag ** 2
    lo, hi = spec["band_hz"]
    sel = (freqs >= lo) & (freqs <= hi)
    sel[0] = False
    m["band_energy_fraction"] = float(e[sel].sum() / e[1:].sum())
    m["ac_rms_frac"] = float(np.std(w) / np.mean(w))
    if (m["ac_rms_frac"] >= spec["min_ac_rms_frac"]
            and m["band_energy_fraction"] > spec["max_energy_fraction"]):
        v.append("band_energy")
    return m, tuple(v)


def campus_row(tenants: Sequence[Dict], seed: int, *, n: int, dt: float,
               hw: Dict, jitter_s: float, sample_chips: int,
               floor: Dict = None, bat: Dict = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(raw, floor-mitigated, mitigated) waveforms of one campus row.

    ``tenants``: dicts of ``phases`` (duration_s, mode) and ``chips``;
    ``hw``: tdp_w, comm_w, edp_factor, edp_window_s, loss; ``floor``:
    mpf_frac, activity_threshold_frac, ramp_up_w_per_s,
    ramp_down_w_per_s, stop_delay_s, edp_cap_frac; ``bat``: the
    battery's arguments but ``dt``."""
    watts = {"compute": hw["tdp_w"], "comm": hw["comm_w"]}
    raw = np.zeros(n)
    floored = np.zeros(n)
    for k, t in enumerate(tenants):
        c = cycle(t["phases"], dt, watts)
        x = c[(np.arange(n) + phase(seed, k, len(c))) % len(c)]
        x = edp_spikes(x, dt, hw["tdp_w"], hw["edp_factor"],
                       hw["edp_window_s"])
        sh = jitter_shifts(jitter_s, dt, tenant_seed(seed, k), sample_chips)
        raw += aggregate(x, t["chips"], sh, hw["loss"])
        if floor is not None:
            tdp = hw["tdp_w"]
            x = gpu_floor(
                x, mpf_w=floor["mpf_frac"] * tdp,
                thresh_w=floor["activity_threshold_frac"] * tdp,
                ramp_up_w=floor["ramp_up_w_per_s"] * dt,
                ramp_down_w=floor["ramp_down_w_per_s"] * dt,
                stop_n=floor["stop_delay_s"] / dt,
                cap_w=tdp * min(floor["edp_cap_frac"], hw["edp_factor"]))
        floored += aggregate(x, t["chips"], sh, hw["loss"])
    out = floored if bat is None else battery(floored, dt=dt, **bat)
    return raw, floored, out


def record(raw: np.ndarray, mit: np.ndarray, dt: float, spec: Dict) -> Dict:
    """The fields a Study record reports of a row."""
    metrics, violations = spec_report(mit, dt, spec)
    return {"mean_mw": raw.mean() / 1e6,
            "swing_mw": (raw.max() - raw.min()) / 1e6,
            "swing_mitigated_mw": (mit.max() - mit.min()) / 1e6,
            "energy_overhead": (mit.sum() - raw.sum()) / raw.sum(),
            "metrics": metrics, "violations": violations}

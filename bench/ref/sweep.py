"""Reference answers of a sweep's rows, and the comparison with the
program's records.

A row is (workload, mitigation setting, jitter seed) on the
configuration's fleet.  Its answer is what a Study record reports:
the raw aggregate's mean and swing (synthesis), the mitigated swing and
energy overhead (mitigation), the critical-band share, the spec's
metrics and its verdict (analysis).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from ref import power
from ref.precision import F64

#: record fields compared, by layer
LAYERS = {
    "synth": ("mean_mw", "swing_mw"),
    "mitig": ("swing_mitigated_mw", "energy_overhead"),
    "analysis": ("paper_band_frac", "metrics.max_ramp_up_w_per_s",
                 "metrics.max_ramp_down_w_per_s", "metrics.dynamic_range_w",
                 "metrics.band_energy_fraction", "metrics.ac_rms_frac"),
}
#: the smallest scale a field's deviation is measured against: an energy
#: overhead of 0.1 % of the job's energy
FLOORS = {"energy_overhead": 1e-3}


def chip_trace(config: Dict, workload: str, q=F64) -> np.ndarray:
    hw, wc = config["hardware"], config["waveform"]
    w = config["workloads"][workload]
    phases = power.phase_list(w["period_s"], w["comm_frac"], w["moe_notch"])
    x = power.phase_levels(phases, wc["steps"], wc["dt"],
                           {"compute": hw["tdp_w"], "comm": hw["comm_w"]})
    if wc["edp_spikes"]:
        x = power.edp_spikes(x, wc["dt"], hw["tdp_w"], hw["edp_factor"],
                             hw["edp_window_s"])
    return q(x)


def shifts(config: Dict, seed: int) -> np.ndarray:
    wc = config["waveform"]
    return power.jitter_shifts(wc["jitter_s"], wc["dt"], seed,
                               config["sample_chips"])


def sizing(config: Dict) -> Dict[str, float]:
    """Mean and swing of the sizing workload's fleet aggregate: the job's
    power, which scales the spec, and the swing, which sizes batteries."""
    w = power.aggregate(chip_trace(config, config["sizing_workload"]),
                        config["n_chips"],
                        shifts(config, config["sizing_seed"]),
                        config["hardware"]["distribution_loss"])
    return {"mean_w": float(w.mean()), "swing_w": float(w.max() - w.min())}


def spec_limits(config: Dict, job_w: float) -> Dict:
    s = config["spec"]
    return {"ramp_up_w_per_s": s["ramp_frac_per_s"] * job_w,
            "ramp_down_w_per_s": s["ramp_frac_per_s"] * job_w,
            "dynamic_range_w": s["dynamic_range_frac"] * job_w,
            "window_s": s["window_s"], "ramp_window_s": s["ramp_window_s"],
            "band_hz": tuple(s["band_hz"]),
            "max_energy_fraction": s["max_energy_fraction"],
            "min_ac_rms_frac": s["min_ac_rms_frac"]}


def _stage(kind: str, params: List[Dict], x: np.ndarray, config: Dict,
           q) -> np.ndarray:
    """One mitigation stage over rows ``x`` [R, L], row r under params[r]."""
    hw, dt = config["hardware"], config["waveform"]["dt"]

    def col(k):
        return np.asarray([p[k] for p in params], np.float64)

    if kind == "GpuPowerSmoothing":
        tdp = hw["tdp_w"]
        return power.gpu_floor(
            x, mpf_w=col("mpf_frac") * tdp,
            thresh_w=col("activity_threshold_frac") * tdp,
            ramp_up_w=col("ramp_up_w_per_s") * dt,
            ramp_down_w=col("ramp_down_w_per_s") * dt,
            stop_n=col("stop_delay_s") / dt,
            cap_w=tdp * np.minimum(col("edp_cap_frac"), hw["edp_factor"]),
            q=q)
    if kind == "RackBattery":
        return power.battery(
            x, capacity_j=col("capacity_j"),
            max_discharge_w=col("max_discharge_w"),
            max_charge_w=col("max_charge_w"), efficiency=col("efficiency"),
            target_tau_s=col("target_tau_s"), initial_soc=col("initial_soc"),
            dt=dt, q=q)
    if kind == "TelemetryBackstop":
        p0 = params[0]
        return power.backstop(
            x, critical_hz=p0["critical_hz"], window_s=p0["window_s"],
            sustain_s=p0["sustain_s"], cooldown_s=p0["cooldown_s"],
            amp_threshold_w=col("amp_threshold_w"), alpha1=col("alpha1"),
            shed_frac=col("shed_frac"), idle_frac=col("idle_frac"), dt=dt,
            q=q)
    raise ValueError(f"no reference for mitigation {kind!r}")


def rows(config: Dict, spec: Dict, items: Sequence[Dict], q=F64) -> List[Dict]:
    """Reference records of ``items``: dicts with ``workload``, ``seed``,
    ``device`` and ``rack`` (each None or {"class", "params"}).  Rows of
    one workload share a length and run their recurrences together."""
    hw, dt = config["hardware"], config["waveform"]["dt"]
    n_chips, loss = config["n_chips"], hw["distribution_loss"]
    out: List[Dict] = [None] * len(items)
    for wname in sorted({it["workload"] for it in items}):
        idx = [i for i, it in enumerate(items) if it["workload"] == wname]
        chip = chip_trace(config, wname, q)
        sh = [shifts(config, items[i]["seed"]) for i in idx]
        raw = np.stack([power.aggregate(chip, n_chips, s, loss, q)
                        for s in sh])
        dev = items[idx[0]]["device"]
        if dev is not None:
            chips = _stage(dev["class"], [items[i]["device"]["params"]
                                          for i in idx],
                           np.broadcast_to(chip, (len(idx), len(chip))),
                           config, q)
            dc = np.stack([power.aggregate(c, n_chips, s, loss, q)
                           for c, s in zip(chips, sh)])
        else:
            dc = raw
        rack = items[idx[0]]["rack"]
        if rack is not None:
            dc = _stage(rack["class"], [items[i]["rack"]["params"]
                                        for i in idx], dc, config, q)
        for j, i in enumerate(idx):
            r, m = raw[j], dc[j]
            e_in = r.sum()
            freqs, mag = power.amplitude_spectrum(m, dt, q)
            metrics, violations = power.spec_report(m, dt, spec, q)
            out[i] = {
                "mean_mw": float(r.mean()) / 1e6,
                "swing_mw": float(r.max() - r.min()) / 1e6,
                "swing_mitigated_mw": float(m.max() - m.min()) / 1e6,
                "energy_overhead": float((m.sum() - e_in) / e_in),
                "paper_band_frac": power.band_fraction(freqs, mag, 0.2, 3.0,
                                                       q),
                "metrics": metrics, "violations": violations,
                "spec_ok": not violations,
                "margins": power.spec_margins(metrics, spec),
            }
    return out


def _field(rec: Dict, key: str) -> float:
    if key.startswith("metrics."):
        return float(rec["metrics"].get(key[8:], np.nan))
    v = rec.get(key)
    return np.nan if v is None else float(v)


def compare(got: Sequence[Dict], want: Sequence[Dict],
            verdict_band: float) -> Dict[str, float]:
    """Per layer, the widest deviation of a compared field, relative to
    the largest reference value of that field over the rows (or its
    floor).  A verdict that differs where every judged reference metric
    lies farther from its threshold than ``verdict_band`` times that
    metric's scale counts as a deviation of 1."""
    out = {}
    scales = {}
    for layer, keys in LAYERS.items():
        worst = 0.0
        for k in keys:
            b = np.asarray([_field(r, k) for r in want])
            a = np.asarray([_field(r, k) for r in got])
            scale = max(float(np.nanmax(np.abs(b))), FLOORS.get(k, 1e-30))
            scales[k] = scale
            dev = np.abs(a - b) / scale
            worst = max(worst, float(np.max(np.where(np.isnan(dev), np.inf,
                                                     dev))))
        out[layer] = worst
    for g, w in zip(got, want):
        if (bool(g["spec_ok"]), tuple(g["violations"])) == (
                w["spec_ok"], tuple(w["violations"])):
            continue
        if all(dist > verdict_band * scales["metrics." + k]
               for k, dist in w["margins"].items()):
            out["analysis"] = max(out["analysis"], 1.0)
    return out

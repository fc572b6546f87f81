"""Reference answers of a campus sweep's rows: several training jobs
behind one grid connection.

A row is (campus, mitigation setting, seed) on the configuration's total
chips.  Its semantics, from the configuration:

* each tenant's steady-state iteration (one cycle of phase levels),
  tiled over the window from a phase drawn from the row's seed, uniform
  over the cycle in samples (``default_rng([seed, k])``), then the EDP
  spikes;
* the device stage (GPU floor) on each tenant's chip trace;
* each tenant's jittered aggregate (its jitter seed: the row's seed for
  tenant 0, ``default_rng([seed, k, 1])`` for the others) times its
  chips, ``round(total * share)``, and (1 + distribution loss);
* the sum over tenants; the rack stage (battery) on the sum; the spec's
  metrics and verdict on the sum.

A record carries what a Study record reports, as ``ref/sweep.py``'s rows
do, and is compared with ``ref/sweep.py``'s ``compare``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from ref import power
from ref import sweep as ref_sweep
from ref.precision import F64


def n_samples(config: Dict) -> int:
    wc = config["waveform"]
    return int(round(wc["window_s"] / wc["dt"]))


def tenant_chips(config: Dict) -> List[float]:
    return [float(round(config["n_chips"] * t["share"]))
            for t in config["tenants"].values()]


def tenant_seed(seed: int, k: int) -> int:
    if k == 0:
        return int(seed)
    return int(np.random.default_rng([seed, k, 1]).integers(2 ** 63))


def phase(seed: int, k: int, cycle_len: int) -> int:
    return int(np.random.default_rng([seed, k]).integers(cycle_len))


def chip_traces(config: Dict, seed: int, q=F64,
                phases: Sequence[int] = None) -> np.ndarray:
    """Each tenant's chip trace over the window [K, n], EDP spikes in;
    ``phases`` overrides the seed's (the fleet arithmetic starts every
    tenant at 0)."""
    hw, wc = config["hardware"], config["waveform"]
    n = n_samples(config)
    out = []
    for k, t in enumerate(config["tenants"].values()):
        c = power.phase_levels(
            power.phase_list(t["period_s"], t["comm_frac"], t["moe_notch"]),
            1, wc["dt"], {"compute": hw["tdp_w"], "comm": hw["comm_w"]})
        p0 = phase(seed, k, len(c)) if phases is None else phases[k]
        x = c[(np.arange(n) + p0) % len(c)]
        if wc["edp_spikes"]:
            x = power.edp_spikes(x, wc["dt"], hw["tdp_w"], hw["edp_factor"],
                                 hw["edp_window_s"])
        out.append(x)
    return q(np.stack(out))


def shifts(config: Dict, seed: int) -> List[np.ndarray]:
    """Each tenant's jitter shifts."""
    wc = config["waveform"]
    return [power.jitter_shifts(wc["jitter_s"], wc["dt"], tenant_seed(seed, k),
                                config["sample_chips"])
            for k in range(len(config["tenants"]))]


def per_chip_means(config: Dict, seed: int, phases=None) -> List[float]:
    """Each tenant's mean power per chip at the grid, distribution loss
    in: what the fleet arithmetic sizes the campus from."""
    loss = config["hardware"]["distribution_loss"]
    return [float(power.aggregate(x, 1.0, s, loss).mean())
            for x, s in zip(chip_traces(config, seed, phases=phases),
                            shifts(config, seed))]


def raw_aggregate(config: Dict, seed: int, q=F64) -> np.ndarray:
    loss = config["hardware"]["distribution_loss"]
    return q(sum(power.aggregate(x, c, s, loss, q)
                 for x, c, s in zip(chip_traces(config, seed, q),
                                    tenant_chips(config),
                                    shifts(config, seed))))


def sizing(config: Dict) -> Dict[str, float]:
    """Mean and swing of the campus's raw aggregate at the sizing seed:
    the power that scales the spec, and the swing that sizes batteries."""
    w = raw_aggregate(config, config["sizing_seed"])
    return {"mean_w": float(w.mean()), "swing_w": float(w.max() - w.min())}


def rows(config: Dict, spec: Dict, items: Sequence[Dict], q=F64) -> List[Dict]:
    """Reference records of ``items``: dicts with ``seed``, ``device`` and
    ``rack`` (each None or {"class", "params"}, one structure for all
    items); one record per item, in the fields ``ref/sweep.py``'s rows
    give.  The recurrences run over every item's tenants at once."""
    loss = config["hardware"]["distribution_loss"]
    dt = config["waveform"]["dt"]
    chips = tenant_chips(config)
    K = len(chips)
    dev, rack = items[0]["device"], items[0]["rack"]
    if any((it["device"] is None, it["rack"] is None) != (dev is None,
                                                          rack is None)
           for it in items):
        raise ValueError("items of one call share their stages")
    traces = np.concatenate([chip_traces(config, it["seed"], q)
                             for it in items])
    sh = [shifts(config, it["seed"]) for it in items]

    def campus_sum(x):
        return np.stack([
            q(sum(power.aggregate(x[i * K + k], chips[k], sh[i][k], loss, q)
                  for k in range(K))) for i in range(len(items))])

    raw = campus_sum(traces)
    if dev is not None:
        traces = ref_sweep._stage(
            dev["class"], [it["device"]["params"] for it in items
                           for _ in range(K)], traces, config, q)
    dc = campus_sum(traces)
    if rack is not None:
        dc = ref_sweep._stage(rack["class"],
                              [it["rack"]["params"] for it in items], dc,
                              config, q)
    return [_record(r, m, dt, spec, q) for r, m in zip(raw, dc)]


def _record(raw: np.ndarray, m: np.ndarray, dt: float, spec: Dict,
            q) -> Dict:
    e_in = raw.sum()
    freqs, mag = power.amplitude_spectrum(m, dt, q)
    metrics, violations = power.spec_report(m, dt, spec, q)
    return {"mean_mw": float(raw.mean()) / 1e6,
            "swing_mw": float(raw.max() - raw.min()) / 1e6,
            "swing_mitigated_mw": float(m.max() - m.min()) / 1e6,
            "energy_overhead": float((m.sum() - e_in) / e_in),
            "paper_band_frac": power.band_fraction(freqs, mag, 0.2, 3.0, q),
            "metrics": metrics, "violations": violations,
            "spec_ok": not violations,
            "margins": power.spec_margins(metrics, spec)}

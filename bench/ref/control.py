"""Reference of the control loop over one replay, and the comparison.

Given the raw trace a replay streamed and the stream the loop's
detector was shown (the raw trace after the interventions the loop
dispatched), the reference recomputes in float64:

* the detector's sliding amplitudes at every tick's last sample;
* the controller's per-bin escalation on their slope-projected values,
  hence its target level at every tick and its worst bin;
* the stream the logged dispatches and releases should have produced
  from the raw trace (redesign: the designed GPU floor per chip and
  battery; power cap: a clip around the history's mean; stagger: a comb
  of shifted replicas);
* each dispatched redesign's spec metrics on its own design target;
* the counterfactual breach: the first sample at which the raw trace's
  worst bin crosses the breach amplitude.

It imports nothing of the program; the configuration gives every rule.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from ref import power
from ref.precision import F64

RUNGS = ("redesign", "power_cap", "stagger")


def ramp(trace: Dict, seed_seq) -> np.ndarray:
    """A DC level with an oscillation whose amplitude ramps linearly from
    zero to its peak and holds, plus seeded Gaussian noise (float32)."""
    dt = trace["dt"]
    n = int(round(trace["duration_s"] / dt))
    t = np.arange(n) * dt
    env = trace["peak_amp_w"] * np.clip(
        (t - trace["ramp_start_s"])
        / max(trace["ramp_end_s"] - trace["ramp_start_s"], dt), 0.0, 1.0)
    w = trace["dc_w"] + env * np.sin(2.0 * np.pi * trace["f_hz"] * t)
    if trace["noise_w"] > 0:
        w = w + np.random.default_rng(seed_seq).normal(
            0.0, trace["noise_w"], size=n)
    return w.astype(np.float32)


def spec_limits(config: Dict) -> Dict:
    s, P = config["spec"], config["job_mw"] * 1e6
    return {"ramp_up_w_per_s": s["ramp_frac_per_s"] * P,
            "ramp_down_w_per_s": s["ramp_frac_per_s"] * P,
            "dynamic_range_w": s["dynamic_range_frac"] * P,
            "window_s": s["window_s"], "ramp_window_s": s["ramp_window_s"],
            "band_hz": tuple(s["band_hz"]),
            "max_energy_fraction": s["max_energy_fraction"],
            "min_ac_rms_frac": s["min_ac_rms_frac"]}


class Rules:
    """The loop's sizes and thresholds, from the configuration."""

    def __init__(self, config: Dict):
        d, c, lad = config["detector"], config["controller"], config["ladder"]
        self.dt = config["trace"]["dt"]
        self.freqs = tuple(d["freqs_hz"])
        self.win = max(int(d["window_s"] / self.dt), 8)
        self.tick_n = max(int(round(d["tick_s"] / self.dt)), 1)
        self.slope_s = d["slope_window_s"]
        self.spec = spec_limits(config)
        self.breach_w = 0.5 * self.spec["dynamic_range_w"]
        self.trigger_w = self.breach_w * c["trigger_frac"]
        self.release_w = self.breach_w * c["release_frac"]
        self.lead_s = c["lead_s"]
        self.sustain, self.cool = c["sustain_ticks"], c["release_ticks"]
        self.max_level = c["max_level"]
        self.history_n = max(int(lad["history_s"] / self.dt), self.win)
        self.headroom = lad["headroom"]
        self.cap_band = lad["power_cap_band_frac"]
        self.groups = lad["stagger_groups"]
        self.gpu = lad["redesign_gpu"]
        self.bat = lad["redesign_battery"]
        self.n_chips = config["n_chips"]
        self.hw = config["hardware"]


def tick_amplitudes(stream: np.ndarray, mean: float, ticks: int,
                    rules: Rules, q=F64) -> np.ndarray:
    """[ticks, K] amplitudes at each tick's last sample."""
    amps = power.sliding_amplitudes(np.asarray(stream, np.float64),
                                    rules.dt, rules.freqs, rules.win,
                                    mean=mean, q=q)
    ends = (np.arange(ticks) + 1) * rules.tick_n - 1
    return amps[ends]


CLEAR, BAND, HIT = 0, 1, 2


def _class(eff: float, live: bool, rules: Rules) -> int:
    if not live or eff <= rules.release_w:
        return CLEAR
    return HIT if eff > rules.trigger_w else BAND


def _step(state, cls: int, rules: Rules):
    """One tick of a bin's threshold machine: ``sustain`` ticks above the
    trigger escalate a level, ``cool`` ticks at or below the release
    de-escalate one."""
    level, above, below = state
    above = above + 1 if cls == HIT else 0
    below = below + 1 if cls == CLEAR else 0
    if cls == HIT and above >= rules.sustain and level < rules.max_level:
        level, above = level + 1, 0
    if cls == CLEAR and below >= rules.cool and level > 0:
        level, below = level - 1, 0
    return level, above, below


def controller(amps: np.ndarray, rules: Rules, band: float = 0.0) -> Dict:
    """Per tick: the slope-projected amplitudes and each bin's levels as
    the controller's rules give them.  Where a projected amplitude lies
    within ``band`` of a threshold, both classes are followed, so each
    bin carries the set of states it may be in; a tick's target level
    lies in [target_lo, target_hi], and its worst bin (highest level,
    then smallest margin) is given where every bin's level is certain."""
    T, K = amps.shape
    ends = (np.arange(T) + 1) * rules.tick_n - 1
    t_s = ends * rules.dt
    states = [{(0, 0, 0)} for _ in range(K)]
    hist: List = []
    out = {"target_lo": np.zeros(T, np.int64),
           "target_hi": np.zeros(T, np.int64),
           "worst": np.full(T, -1), "amps_eff": np.zeros((T, K)),
           "levels": np.zeros((T, K), np.int64)}
    for t in range(T):
        hist.append((t_s[t], amps[t]))
        while len(hist) > 2 and t_s[t] - hist[0][0] > rules.slope_s:
            hist.pop(0)
        span = t_s[t] - hist[0][0]
        slope = (amps[t] - hist[0][1]) / span if span > 0 else np.zeros(K)
        eff = amps[t] + np.maximum(slope, 0.0) * rules.lead_s
        live = bool(ends[t] >= rules.win - 1)
        lo, hi = np.zeros(K, np.int64), np.zeros(K, np.int64)
        for k in range(K):
            classes = range(_class(eff[k] - band, live, rules),
                            _class(eff[k] + band, live, rules) + 1)
            states[k] = {_step(s, c, rules) for s in states[k]
                         for c in classes}
            lo[k] = min(s[0] for s in states[k])
            hi[k] = max(s[0] for s in states[k])
        out["amps_eff"][t] = eff
        out["levels"][t] = lo
        out["target_lo"][t], out["target_hi"][t] = lo.max(), hi.max()
        margins = rules.trigger_w - eff
        b0, b1 = np.lexsort((margins, -lo))[:2]
        if (lo == hi).all() and not (
                lo[b0] == lo[b1] and margins[b1] - margins[b0] <= 2 * band):
            out["worst"][t] = b0
    return out


def events(records: Sequence[Dict], tick_n: int) -> List[Dict]:
    """The loop's dispatch calls in tick order: each re-applies, from the
    cursor after its tick, the rungs active after it, with the parameters
    and history each rung was built from."""
    active: Dict[str, Dict] = {}
    out: List[Dict] = []
    for tick in sorted({r["tick"] for r in records
                        if r["action"].split(":")[0]
                        in ("dispatch", "release", "dispatch_failed")}):
        for r in records:
            if r["tick"] != tick:
                continue
            kind, _, rung = r["action"].partition(":")
            if kind == "dispatch":
                active[rung] = {"params": r["params"], "tick": tick,
                                "bin_hz": r["bin_hz"]}
            elif kind == "release":
                active.pop(rung, None)
        out.append({"tick": tick, "cursor": (tick + 1) * tick_n,
                    "active": {k: active[k] for k in RUNGS if k in active}})
    return out


def design_target(observed: np.ndarray, cursor: int, rules: Rules):
    hist = np.asarray(observed[max(0, cursor - rules.history_n):cursor],
                      np.float64)
    m = hist.mean()
    return hist, m + rules.headroom * (hist - m)


def _redesign_rows(xs: List[np.ndarray], params: List[Dict],
                   swings: List[float], rules: Rules, q) -> List[np.ndarray]:
    """The designed GPU floor (per chip) and battery over each future;
    rows of different lengths run together, padded at the end."""
    n = max(len(x) for x in xs)
    pad = np.stack([np.pad(x, (0, n - len(x)), mode="edge") for x in xs])
    dt, tdp = rules.dt, rules.hw["tdp_w"]
    mpf = np.asarray([p["mpf_frac"] for p in params])
    cap = np.asarray([p["battery_capacity_j"] for p in params])
    g = rules.gpu
    ramp_step = rules.spec["ramp_up_w_per_s"] / rules.n_chips * dt
    floored = q(power.gpu_floor(
        q(pad / rules.n_chips), mpf_w=mpf * tdp,
        thresh_w=g["activity_threshold_frac"] * tdp, ramp_up_w=ramp_step,
        ramp_down_w=rules.spec["ramp_down_w_per_s"] / rules.n_chips * dt,
        stop_n=g["stop_delay_s"] / dt,
        cap_w=tdp * min(g["edp_cap_frac"], rules.hw["edp_factor"]),
        q=q) * rules.n_chips)
    pad = np.where((mpf > 0)[:, None], floored, pad)
    b = rules.bat
    sw = np.asarray(swings)
    starts = np.asarray([pad[i, :len(x)].mean() for i, x in enumerate(xs)])
    batt = power.battery(
        pad, capacity_j=cap, max_discharge_w=sw, max_charge_w=sw,
        efficiency=b["efficiency"], target_tau_s=b["target_tau_s"],
        initial_soc=b["initial_soc"], dt=dt, q=q, start_target=starts)
    pad = np.where((cap > 0)[:, None], batt, pad)
    return [pad[i, :len(x)] for i, x in enumerate(xs)]


def expected_stream(raw: np.ndarray, observed: np.ndarray,
                    records: Sequence[Dict], rules: Rules,
                    q=F64) -> np.ndarray:
    """The stream the logged dispatches should have produced: after each
    dispatch call the raw remainder through its active rungs, in rung
    order, shown until the next call."""
    raw = q(np.asarray(raw, np.float64))
    out = raw.copy()
    evs = events(records, rules.tick_n)
    futures, plan = [], []
    for i, ev in enumerate(evs):
        c = ev["cursor"]
        if c >= len(raw):
            continue
        nxt = evs[i + 1]["cursor"] if i + 1 < len(evs) else len(raw)
        plan.append((ev, c, min(nxt, len(raw))))
        futures.append(raw[c:])
    # the redesign stage first, all calls at once
    red = [(k, ev) for k, (ev, _, _) in enumerate(plan)
           if "redesign" in ev["active"]]
    staged = list(futures)
    if red:
        params, swings = [], []
        for _, ev in red:
            r = ev["active"]["redesign"]
            _, tgt = design_target(observed, (r["tick"] + 1) * rules.tick_n,
                                   rules)
            params.append(r["params"])
            swings.append(float(tgt.max() - tgt.min()))
        rows = _redesign_rows([futures[k] for k, _ in red], params, swings,
                              rules, q)
        for (k, _), row in zip(red, rows):
            staged[k] = row
    for (ev, c, nxt), x in zip(plan, staged):
        if "power_cap" in ev["active"]:
            r = ev["active"]["power_cap"]
            hist, _ = design_target(observed, (r["tick"] + 1) * rules.tick_n,
                                    rules)
            half = rules.cap_band * rules.release_w
            m = hist.mean()
            x = np.clip(x, q(m - half), q(m + half))
        if "stagger" in ev["active"]:
            f = ev["active"]["stagger"]["bin_hz"]
            G = max(int(rules.groups), 2)
            shifts = np.round(np.arange(G) / (G * f) / rules.dt).astype(int)
            n = len(x)
            idx = np.clip(np.arange(n)[None, :] - shifts[:, None], 0, n - 1)
            x = q(x[idx].mean(axis=0))
        out[c:nxt] = x[:nxt - c]
    return out


def design_excess(observed: np.ndarray, records: Sequence[Dict],
                  rules: Rules) -> List[float]:
    """Per dispatched redesign: by how much (relative to its limit) the
    worst judged spec metric exceeds its limit when the dispatched design
    is applied to its own design target; at most 0 means it passes."""
    designs = [r for r in records if r["action"] == "dispatch:redesign"]
    if not designs:
        return []
    targets = [design_target(observed, (r["tick"] + 1) * rules.tick_n,
                             rules)[1] for r in designs]
    mits = _redesign_rows(targets, [r["params"] for r in designs],
                          [float(t.max() - t.min()) for t in targets],
                          rules, F64)
    out = []
    for mit in mits:
        metrics, _ = power.spec_report(mit, rules.dt, rules.spec)
        worst = -np.inf
        for k, (lim_key, _) in power.SPEC_LIMITS.items():
            if k not in metrics or (
                    k == "band_energy_fraction" and metrics["ac_rms_frac"]
                    < rules.spec["min_ac_rms_frac"]):
                continue
            lim = rules.spec[lim_key]
            worst = max(worst, (metrics[k] - lim) / lim)
        out.append(worst)
    return out


def breach_index(raw: np.ndarray, rules: Rules, q=F64) -> int:
    """First sample at which the raw trace's worst bin exceeds the breach
    amplitude (-1 if never)."""
    x = np.asarray(raw, np.float64)
    amps = power.sliding_amplitudes(x, rules.dt, rules.freqs, rules.win,
                                    mean=x.mean(), q=q)
    over = np.nonzero(amps.max(axis=1) > rules.breach_w)[0]
    return int(over[0]) if len(over) else -1


def replay_answers(rep: Dict, rules: Rules, q=F64,
                   band_rel: float = 0.0) -> Dict:
    """What the reference (or, in bfloat16, the control) answers for one
    replay, in the form the program's answers take.  ``band_rel``: the
    share of the largest amplitude within which a projected amplitude
    may lie on either side of a threshold."""
    raw = np.asarray(rep["raw"], np.float64)
    ticks = len(rep["series"])
    amps = tick_amplitudes(rep["observed"], raw.mean(), ticks, rules, q)
    ctl = controller(amps, rules, band_rel * float(np.abs(amps).max()))
    return dict(ctl, amps=amps, target=ctl["target_lo"],
                stream=expected_stream(raw, rep["observed"], rep["records"],
                                       rules, q),
                breach=breach_index(raw, rules, q))


def program_answers(rep: Dict, rules: Rules) -> Dict:
    """The program's answers for one replay, from its ControlLog."""
    series = rep["series"]
    worst = np.full(len(series), -1)
    for r in rep["records"]:
        if r["action"].startswith("dispatch") and r["bin_hz"] is not None:
            worst[r["tick"]] = rules.freqs.index(r["bin_hz"])
    return {"amps": np.asarray([s["amps_w"] for s in series]),
            "target": np.asarray([s["level"] for s in series]),
            "worst": worst, "stream": np.asarray(rep["observed"], np.float64),
            "breach": rep["breach"],
            "design": design_excess(rep["observed"], rep["records"], rules)}


def compare(got: List[Dict], want: List[Dict], reps: List[Dict],
            rules: Rules, limits: Dict) -> Dict[str, float]:
    """A control cell's numbers over its replays: ``amp_dev``, the widest
    gap of a detector amplitude, and ``loop_dev``, of the stream after
    dispatches, both relative to the largest reference amplitude; a
    decision fault (a target level the rules cannot give, allowing either
    side of a threshold where an amplitude lies within the ambiguity band;
    a worst bin other than theirs where every level is certain; a
    redesign that fails its spec by more than the tolerance) sets
    ``loop_dev`` to 1.
    ``breach_dev_samples``: the counterfactual breach's gap in samples."""
    scale = max(float(np.abs(w["amps"]).max()) for w in want)
    amp = stream = breach = 0.0
    faults = 0
    for g, w, rep in zip(got, want, reps):
        amp = max(amp, float(np.abs(g["amps"] - w["amps"]).max()) / scale)
        n = len(rep["observed"])
        stream = max(stream, float(
            np.abs(g["stream"][:n] - w["stream"][:n]).max()) / scale)
        breach = max(breach, abs(g["breach"] - w["breach"])
                     if min(g["breach"], w["breach"]) >= 0
                     or g["breach"] == w["breach"] else np.inf)
        t = np.arange(len(g["target"]))
        faults += int(((g["target"] < w["target_lo"][t])
                       | (g["target"] > w["target_hi"][t])).sum())
        known = (g["worst"] >= 0) & (w["worst"] >= 0)
        faults += int((g["worst"][known] != w["worst"][known]).sum())
        if "design" in g:
            faults += sum(e > limits["design_tolerance"] for e in g["design"])
    # a decisive wrong decision reads as a deviation of the whole scale
    return {"amp_dev": amp, "loop_dev": max(stream, float(faults > 0)),
            "breach_dev_samples": float(breach)}

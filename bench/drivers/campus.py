"""Campus sweep cells: several training jobs behind one grid connection,
as ``Study.run(stream=...)`` over one ``Campus`` workload.

The configuration names the tenants (each a workload's phases and its
share of the chips) and the window; the traffic file declares the grid
as ``drivers/sweep.py``'s does, the device stage acting on every tenant
and the rack stage on the campus sum, sized by the campus's swing.  The
window, the rows checked and what the readers see are the sweep
driver's; the reference is ``ref/campus.py``.
"""
from __future__ import annotations

from typing import Dict, List

from drivers import sweep
from ref import campus as ref_campus
from ref import sweep as ref_sweep
from ref.power import phase_list
from ref.precision import F64


class Session(sweep.Session):
    """One run of a campus sweep cell."""

    def __init__(self, config: Dict, cell: Dict, seed: int):
        import repro.core as core
        from repro.core.campus import Campus, Tenant
        from repro.core.hardware import DEFAULT_HW
        from repro.core.phases import IterationTimeline, Phase
        from repro.core.waveform import WaveformConfig

        self.config, self.cell, self.seed = config, cell, int(seed)
        traffic = cell["traffic"]
        self.chunk = traffic["stream"]
        self.seeds_per_study = traffic["seeds_per_study"]
        sweep._check_hardware(config["hardware"], DEFAULT_HW)
        size = ref_campus.sizing(config)
        self.scale = {"x_swing": size["swing_w"], "x_mean": size["mean_w"]}
        self.spec = ref_sweep.spec_limits(config, size["mean_w"])
        self._spec = _utility_spec(config["spec"]["tier"], self.spec)
        wc = config["waveform"]
        self._wave = WaveformConfig(dt=wc["dt"], jitter_s=wc["jitter_s"],
                                    edp_spikes=wc["edp_spikes"])
        campus = Campus(tuple(
            Tenant(name, IterationTimeline(tuple(
                Phase(f"p{i}", dur, mode) for i, (dur, mode) in enumerate(
                    phase_list(t["period_s"], t["comm_frac"],
                               t["moe_notch"])))), t["share"])
            for name, t in config["tenants"].items()), wc["window_s"])
        self.workloads = [config["name"]]
        self._timelines = {config["name"]: campus}
        self.points = [(d, r)
                       for d in sweep.grid(traffic["device"], self.scale)
                       for r in sweep.grid(traffic["rack"], self.scale)]
        self.row_lengths = ([ref_campus.n_samples(config)] * len(self.points)
                            * self.seeds_per_study)
        self._configs = {f"c{i:03d}": (sweep._build(core, d),
                                       sweep._build(core, r))
                         for i, (d, r) in enumerate(self.points)}
        self._core = core
        self.finished: List = []
        self.stats: Dict = {}

    def setup(self, span) -> None:
        """The first chunk of a Study's rows, run as one chunk, compiles
        every program and shape the window runs: each chunk of the window
        holds as many rows, of one length, and every seed of the Study."""
        from repro.core.study import run_rows
        study = self.study(0)
        with span("bench.warmup"):
            run_rows(study.workloads, study.rows()[:self.chunk], study.specs,
                     wave_cfg=study.wave_cfg, hw=study.hw,
                     padding=study.padding, stream=self.chunk,
                     sample_chips=study.sample_chips)

    def check(self, q=F64, items=None) -> List[Dict]:
        limits = self.cell["check"]
        items = items or self.sample()
        for it in items:
            rec = it["record"]
            if (rec["workload"], rec["seed"]) != (it["workload"], it["seed"]):
                raise AssertionError("record order differs from the grid's")
        want = ref_campus.rows(self.config, self.spec, items)
        got = (ref_campus.rows(self.config, self.spec, items, q)
               if q is not F64 else [it["record"] for it in items])
        devs = ref_sweep.compare(got, want, limits["verdict_band"])
        return [{"name": f"{k}_dev", "value": devs[k],
                 "limit": limits[f"{k}_dev"]} for k in ("synth", "mitig",
                                                        "analysis")]


def _utility_spec(tier: str, s: Dict):
    from repro.core.spec import (FrequencyDomainSpec, TimeDomainSpec,
                                 UtilitySpec)
    return UtilitySpec(
        tier,
        TimeDomainSpec(ramp_up_w_per_s=s["ramp_up_w_per_s"],
                       ramp_down_w_per_s=s["ramp_down_w_per_s"],
                       dynamic_range_w=s["dynamic_range_w"],
                       window_s=s["window_s"],
                       ramp_window_s=s["ramp_window_s"]),
        FrequencyDomainSpec(band_hz=s["band_hz"],
                            max_energy_fraction=s["max_energy_fraction"],
                            min_ac_rms_frac=s["min_ac_rms_frac"]))

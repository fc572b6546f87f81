"""repro.api — the one-stop public surface: declare -> run -> query.

Everything a study of the paper's scenario matrix needs, in one import:

    from repro import api

    study = api.Study(
        workloads={"dense": api.synthetic_timeline(2.0, 0.19),
                   "moe":   api.synthetic_timeline(3.0, 0.25, moe_notch=True)},
        fleets=[256, 512],
        configs={"none": None,
                 "mpf90": (api.GpuPowerSmoothing(mpf_frac=0.9), None)},
        specs=api.example_specs(job_mw=100.0),
        key=0)
    result = study.run()                      # compiled batched engine
    result.passing().pivot("workload", "config", "energy_overhead")

    service = api.PowerComplianceService()    # the serve path
    service.query(api.synthetic_timeline(2.0, 0.25), 512, "moderate")

The engine functions behind this (``repro.core.engine``) remain available
for direct use; the Study layer is the supported surface.
"""
from repro.core.hardware import DEFAULT_HW, Hardware
from repro.core.campus import Campus, Tenant
from repro.core.phases import (IterationTimeline, Phase, from_dryrun_cell,
                               load_cell, synthetic_timeline)
from repro.core.engine import (StreamChunk, design, design_gradient,
                               design_grid, stream_batches)
from repro.parallel.sharding import ScenarioShardPlan, scenario_plan
from repro.core.smoothing import (CombinedMitigation, Firefly,
                                  GpuPowerSmoothing, RackBattery, Stack,
                                  TelemetryBackstop, design_mitigation)
from repro.core.spec import (FrequencyDomainSpec, SpecReport, TimeDomainSpec,
                             UtilitySpec, example_specs)
from repro.core.stratosim import SimResult, simulate, simulate_jit
from repro.core.study import (MitigationConfig, Scenario, Study, StudyResult)
from repro.core.telemetry import TelemetrySource
from repro.core.waveform import WaveformConfig
from repro.control import (ControlLog, ControlLoop, GridController,
                           InterventionLadder, OnlineGoertzelDetector,
                           ReplaySource, synthesize_ramp, watch_trace)
from repro.serve.power import PowerComplianceService, default_catalog
from repro.serve.warmstart import WarmStartPredictor, train_warmstart

__all__ = [
    # the declarative study surface
    "Study", "StudyResult", "Scenario", "MitigationConfig",
    # streaming execution + scenario-axis sharding
    "stream_batches", "StreamChunk", "ScenarioShardPlan", "scenario_plan",
    # the serve path
    "PowerComplianceService", "default_catalog",
    "WarmStartPredictor", "train_warmstart",
    # the grid-interactive control plane
    "ControlLoop", "ControlLog", "GridController", "InterventionLadder",
    "OnlineGoertzelDetector", "ReplaySource", "synthesize_ramp",
    "watch_trace",
    # scenario ingredients
    "IterationTimeline", "Phase", "synthetic_timeline", "from_dryrun_cell",
    "Campus", "Tenant",
    "load_cell", "WaveformConfig", "TelemetrySource",
    "Hardware", "DEFAULT_HW",
    # mitigations
    "GpuPowerSmoothing", "RackBattery", "Firefly", "TelemetryBackstop",
    "CombinedMitigation", "Stack", "design_mitigation",
    "design", "design_gradient", "design_grid",
    # specs + serial reference
    "UtilitySpec", "TimeDomainSpec", "FrequencyDomainSpec", "SpecReport",
    "example_specs", "SimResult", "simulate", "simulate_jit",
]

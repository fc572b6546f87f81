"""Power-stabilization core — the paper's contribution as a JAX subsystem.

Analysis objects (specs, spectra, phase timelines), the StratoSim-analogue
datacenter power simulator, and the mitigation stack (Firefly software
smoothing, GB200-style device power floor, rack-level energy storage,
telemetry backstop, combined design solver).
"""
from repro.core.hardware import ChipSpec, DatacenterTopology, DEFAULT_HW, Hardware, ServerSpec
from repro.core.campus import Campus, Tenant
from repro.core.phases import (IterationTimeline, Phase, checkpoint_phase,
                               from_dryrun_cell, load_cell, synthetic_timeline)
from repro.core.spec import (FrequencyDomainSpec, SpecReport, TimeDomainSpec,
                             UtilitySpec, example_specs)
from repro.core.spectrum import (band_energy_fraction, critical_band_report,
                                 dominant_frequency, spectrum)
from repro.core.stratosim import SimResult, simulate, simulate_cell, simulate_jit
from repro.core.telemetry import TelemetrySource
from repro.core.waveform import (WaveformConfig, aggregate, chip_waveform,
                                 job_waveform, swing_stats)
from repro.core.smoothing import (CombinedMitigation, Firefly, GpuPowerSmoothing,
                                  RackBattery, Stack, TelemetryBackstop,
                                  design_mitigation, energy_overhead)
from repro.core.engine import (BatchResult, StreamChunk, analyze_batch,
                               apply_batch, design, design_gradient,
                               design_grid, simulate_batch,
                               stack_mitigations, stream_batches, sweep,
                               validate_many)
from repro.core.study import MitigationConfig, Scenario, Study, StudyResult
from repro.core.ballast_inject import attach_ballast, ballast_gflops_for_cell
from repro.core.stagger import StaggerSchedule, max_ramp, plan_stagger, ramp_waveform

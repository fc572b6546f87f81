"""Tier-2 registry: the repo's jitted entry points at representative shapes.

Every entry names one *compiled hot path* plus a builder that returns
``(fn, args, kwargs)`` ready for ``jax.make_jaxpr`` — the shapes are the
smallest ones that still exhibit the path's real structure (full-window
segments for the monitor, a multi-start lattice for the designer, a
padded scenario batch for the engine).  The jaxpr analyzers
(``jaxpr_checks``) walk these programs for f32 long-axis accumulation
and host callbacks, pin their primitive mix (``primitive_counts`` —
consumed by ``benchmarks/roofline.py``), and the recompile gate re-runs
the *callable* pairs registered in ``RECOMPILE_PAIRS`` to prove a second
same-shape-bucket call hits the jit cache.

Deliberately NOT registered: ``kernels/goertzel/ref.py``'s
``sliding_bin_power_jnp`` — the analysis-side cumsum oracle carries a
trace-length f32/c64 prefix sum by design (it is f64-gold-checked in
tests, and the product path is the segmented Pallas kernel).  Register
it and the long-axis gate fires — which is exactly the regression test
``tests/test_analysis.py`` runs against a deliberately reverted copy.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

#: reduced-axis lengths above which a sequential f32/c64 cumsum is a finding
LONG_AXIS_CUMSUM = 4096
#: reduce_sum threshold (tree reductions lose ~log2(n) bits, far safer —
#: only flag genuinely enormous f32 reductions)
LONG_AXIS_REDUCE = 1 << 22


@dataclasses.dataclass
class EntryPoint:
    name: str
    build: Callable[[], Tuple[Callable, tuple, dict]]
    description: str


def _monitor_shapes():
    import jax.numpy as jnp
    x = jnp.asarray(__import__("numpy").random.default_rng(0)
                    .normal(5e8, 1e5, 100_000), jnp.float32)
    return x, 0.001, (0.5, 1.0, 2.0, 9.0), 2000


def _build_sliding_bin_power():
    """The backstop/product monitor: segmented Pallas path (interpret mode
    off-TPU), 1e5 samples / 2000-sample windows / 4 bins."""
    from repro.kernels.goertzel.ops import _sliding_bin_power_full
    x, dt, freqs, win = _monitor_shapes()
    return (_sliding_bin_power_full, (x,),
            dict(dt=dt, freqs=freqs, win=win, interpret=True))


def _build_detector_step():
    """Control-plane online detector: one segment step of the carry API
    (lane-major v2 kernel, prefix state streamed through [KP, win])."""
    import jax.numpy as jnp
    from repro.kernels.goertzel.ops import _phase_tables_v2, _sliding_seg_v2
    _, dt, freqs, win = _monitor_shapes()
    cosp, sinp, rot = (jnp.asarray(t) for t in
                       _phase_tables_v2(freqs, dt, win))
    seg = jnp.zeros((win,), jnp.float32)
    zeros = jnp.zeros_like(cosp)
    return (_sliding_seg_v2, (seg, zeros, zeros, cosp, sinp, rot,
                              jnp.float32(0.0)),
            dict(win=win, k=len(freqs), interpret=True))


def _build_monitor_fused():
    """The fused v2 monitor (backstop/detector fast path): worst bin +
    escalation class reduced in VMEM, blocked escalation scan on top."""
    import jax.numpy as jnp
    from repro.kernels.goertzel.ops import _sliding_monitor_full
    x, dt, freqs, win = _monitor_shapes()
    return (_sliding_monitor_full,
            (x, jnp.float32(1e6), jnp.float32(8e5)),
            dict(dt=dt, freqs=freqs, win=win, sustain_n=50, cool_n=80,
                 max_level=3, block_s=0, interpret=True, use_pallas=True))


def _sim_inputs(B: int = 2, spec=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import synthetic_timeline
    from repro.core.hardware import DEFAULT_HW
    from repro.core.smoothing.battery import RackBattery
    from repro.core.smoothing.gpu_floor import GpuPowerSmoothing
    from repro.core.waveform import WaveformConfig, jitter_shifts, phase_levels
    from repro.core.engine import stack_mitigations

    cfg = WaveformConfig(dt=0.002, steps=4, jitter_s=0.002)
    hw = DEFAULT_HW
    tl = synthetic_timeline(period_s=1.0, comm_frac=0.3)
    levels = phase_levels(tl, cfg, hw)
    n = levels.shape[-1]
    shifts = np.stack([jitter_shifts(cfg, seed=s, sample_chips=64)
                       for s in range(B)])
    swing = 1e6
    gpus = stack_mitigations([
        GpuPowerSmoothing(mpf_frac=0.3 + 0.1 * i, ramp_up_w_per_s=2000.0,
                          ramp_down_w_per_s=2000.0, hw=hw)
        for i in range(B)])
    bats = stack_mitigations([
        RackBattery(capacity_j=swing * (i + 1), max_discharge_w=swing,
                    max_charge_w=swing) for i in range(B)])
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(B)])
    return dict(cfg=cfg, hw=hw, levels=jnp.asarray(
        np.broadcast_to(levels, (B, n)).copy(), jnp.float32),
        shifts=jnp.asarray(shifts), gpus=gpus, bats=bats, keys=keys, B=B, n=n)


def _build_simulate_step():
    """The engine's compiled scenario step (synthesis -> mitigation ->
    metrics -> spec verdicts), B=2 scenarios, spec validation on, with
    the aggregation band ``simulate_batch`` would pick for the shifts."""
    import jax.numpy as jnp
    from repro.core import engine
    from repro.core.spec import example_specs
    from repro.core.waveform import jitter_reach

    spec = example_specs(job_mw=1.0)["moderate"]
    si = _sim_inputs()
    B = si["B"]
    on = jnp.ones((B,), jnp.float32)
    limits = spec.limits()
    fn = engine._simulate_vmapped.__wrapped__   # trace the pre-jit function
    return (fn, (si["levels"], si["shifts"],
                 jnp.full((B,), 256.0, jnp.float32), si["gpus"], si["bats"],
                 on, on, si["keys"], None, limits),
            dict(cfg=si["cfg"], hw=si["hw"], spec=spec.family(),
                 spectra=False, plan=None,
                 reach=jitter_reach(si["shifts"], si["cfg"], si["n"])))


def _build_design_gradient_step():
    """One vmapped multi-start Adam descent of ``design_gradient`` (the
    compiled solver core), 4 starts x 12 steps on a 1e6 W square wave."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import engine
    from repro.core.hardware import DEFAULT_HW
    from repro.core.smoothing.battery import RackBattery
    from repro.core.smoothing.gpu_floor import GpuPowerSmoothing
    from repro.core.spec import example_specs

    dt = 0.002
    n = 2000
    w = np.where((np.arange(n) // 250) % 2, 2e6, 1e6).astype(np.float32)
    spec = example_specs(job_mw=1.0)["moderate"]
    swing = 1e6
    cap_scale = swing * 2.0
    hw = DEFAULT_HW
    gpu_t = GpuPowerSmoothing(
        mpf_frac=0.5, hw=hw,
        ramp_up_w_per_s=spec.time.ramp_up_w_per_s / 256,
        ramp_down_w_per_s=spec.time.ramp_down_w_per_s / 256,
        smooth_tau=0.05)
    bat_t = RackBattery(capacity_j=cap_scale, max_discharge_w=swing,
                        max_charge_w=swing, smooth_tau=0.05)
    x0 = {"mpf": jnp.asarray([0.3, 0.6, 0.85, 0.5], jnp.float32),
          "cap": jnp.asarray([0.25, 1.0, 0.5, 0.75], jnp.float32)}
    lo = {"mpf": jnp.asarray(0.0, jnp.float32),
          "cap": jnp.asarray(1e-3, jnp.float32)}
    hi = {"mpf": jnp.asarray(hw.chip.mpf_max, jnp.float32),
          "cap": jnp.asarray(4.0, jnp.float32)}
    hyper = {"lr": jnp.asarray(0.08, jnp.float32),
             "margin": jnp.asarray(0.05, jnp.float32),
             "overhead_weight": jnp.asarray(0.5, jnp.float32),
             "size_weight": jnp.asarray(0.02, jnp.float32),
             "cap_scale": jnp.asarray(cap_scale, jnp.float32)}
    fn = engine._design_descend.__wrapped__
    return (fn, (x0, gpu_t, bat_t, jnp.asarray(w),
                 jnp.asarray(256.0, jnp.float32), lo, hi, hyper,
                 spec.limits()),
            dict(spec=spec.family(), dt=dt, steps=12))


def _build_serve_fingerprint():
    """Serve feature extractor: grid-critical Goertzel fingerprint."""
    import jax.numpy as jnp
    from repro.core.spectrum import (GRID_CRITICAL_HZ,
                                     goertzel_bin_amplitudes_jax)
    x = jnp.zeros((20_000,), jnp.float32)
    return (lambda x: goertzel_bin_amplitudes_jax(x, 0.002, GRID_CRITICAL_HZ),
            (x,), {})


def _build_warmstart_mlp():
    """Serve warm-start predictor forward pass (batch 8)."""
    import jax
    import jax.numpy as jnp
    from repro.serve.warmstart import (N_FEATURES, init_warmstart,
                                      warmstart_forward)
    params = init_warmstart(jax.random.PRNGKey(0))
    xb = jnp.zeros((8, N_FEATURES), jnp.float32)
    return (warmstart_forward, (params, xb), {})


ENTRY_POINTS: List[EntryPoint] = [
    EntryPoint("engine.simulate_step", _build_simulate_step,
               "batched scenario pipeline (synthesis->mitigation->spec)"),
    EntryPoint("engine.design_gradient_step", _build_design_gradient_step,
               "vmapped multi-start Adam descent on the smooth design stack"),
    EntryPoint("kernels.sliding_bin_power", _build_sliding_bin_power,
               "segmented sliding-Goertzel monitor (backstop hot path)"),
    EntryPoint("control.detector_step", _build_detector_step,
               "online monitor segment step (carry API, v2 kernel)"),
    EntryPoint("kernels.monitor_fused", _build_monitor_fused,
               "fused worst-bin + escalation monitor (v2 kernel)"),
    EntryPoint("serve.fingerprint", _build_serve_fingerprint,
               "grid-critical spectral fingerprint (serve features)"),
    EntryPoint("serve.warmstart_mlp", _build_warmstart_mlp,
               "warm-start MLP forward"),
]

ENTRY_BY_NAME: Dict[str, EntryPoint] = {e.name: e for e in ENTRY_POINTS}


# ---------------------------------------------------------------------------
# recompile gate registrations: (label, warm callable) pairs.  Each thunk
# invokes a *public* path twice with different data in the SAME shape
# bucket; between the two calls the tracked jit caches must not grow.
# ---------------------------------------------------------------------------

def _tracked_jit_fns() -> Dict[str, object]:
    """The jitted callables whose caches the gate watches."""
    from repro.core import engine
    from repro.kernels.goertzel import ops
    from repro.serve import warmstart
    return {
        "engine._simulate_vmapped": engine._simulate_vmapped,
        "engine._synth_vmapped": engine._synth_vmapped,
        "engine._mitigate_vmapped": engine._mitigate_vmapped,
        "engine._analyze_vmapped": engine._analyze_vmapped,
        "engine._validate_vmapped": engine._validate_vmapped,
        "engine._design_eval": engine._design_eval,
        "ops._sliding_bin_power_full": ops._sliding_bin_power_full,
        "ops._sliding_seg_v2": ops._sliding_seg_v2,
        "ops._monitor_seg_v2": ops._monitor_seg_v2,
        "ops._monitor_tail": ops._monitor_tail,
        "ops._sliding_monitor_full": ops._sliding_monitor_full,
        "ops._amps_at": ops._amps_at,
        "warmstart._predict_normalized": warmstart._predict_normalized,
    }


def _gate_monitor(seed: int) -> None:
    import numpy as np
    from repro.kernels.goertzel.ops import sliding_bin_power
    x = np.random.default_rng(seed).normal(5e8, 1e5, 30_000)
    sliding_bin_power(x.astype(np.float32), 0.001, (0.5, 1.0, 2.0, 9.0),
                      win=2000, interpret=True)


def _gate_engine(seed: int) -> None:
    from repro.core import engine, synthetic_timeline
    from repro.core.spec import example_specs
    from repro.core.waveform import WaveformConfig
    tl = synthetic_timeline(period_s=1.0, comm_frac=0.3)
    cfg = WaveformConfig(dt=0.002, steps=4, jitter_s=0.002)
    engine.simulate_batch(tl, 256, cfg, spec=example_specs(job_mw=1.0)["moderate"],
                          seeds=seed, sample_chips=64)


def _gate_monitor_fused(seed: int) -> None:
    import numpy as np
    from repro.kernels.goertzel.ops import (monitor_carry_init,
                                            sliding_monitor_fused)
    freqs = (0.5, 1.0, 2.0, 9.0)
    x = np.random.default_rng(seed).normal(5e8, 1e5, 30_000)
    x = x.astype(np.float32)
    sliding_monitor_fused(x, 0.001, freqs, win=2000, threshold=1e6,
                          sustain_n=50, cool_n=80, interpret=True)
    carry = monitor_carry_init(0.001, freqs, win=2000)
    for lo in range(0, 6000, 3000):
        _, _, _, carry = sliding_monitor_fused(
            x[lo:lo + 3000], 0.001, freqs, win=2000, threshold=1e6,
            sustain_n=50, cool_n=80, interpret=True, carry=carry)


RECOMPILE_PAIRS: List[Tuple[str, Callable[[int], None]]] = [
    ("monitor.sliding_bin_power", _gate_monitor),
    ("monitor.sliding_monitor_fused", _gate_monitor_fused),
    ("engine.simulate_batch", _gate_engine),
]

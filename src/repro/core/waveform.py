"""Power-waveform synthesis: phase timeline -> sampled watts.

Reproduces the paper's Fig. 1 structure: per-chip square-ish waves between
near-TDP compute and near-idle communication, EDP overshoot spikes at phase
rises, checkpoint valleys, and rack/DC aggregation with per-chip jitter
(stragglers soften edges at scale, they do not remove the swing — the job
is bulk-synchronous).

Two layers:

* the numpy-facing API (``chip_waveform`` / ``aggregate`` / ``job_waveform``)
  used by existing callers, and
* pure jnp building blocks (``chip_waveform_jax`` / ``aggregate_jax`` /
  ``swing_stats_jax``) that run inside jit/vmap for the batched scenario
  engine (core/engine.py).  The shape-determining timeline->samples
  expansion stays in numpy (``phase_levels``); everything downstream of the
  level array is traceable.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hardware import DEFAULT_HW, Hardware
from repro.core.phases import CKPT, COMM, COMPUTE, IDLE, MEMORY, IterationTimeline, Phase

MODE_POWER_ATTR = {COMPUTE: "tdp_w", MEMORY: "hbm_bound_w", COMM: "comm_w",
                   IDLE: "idle_w", CKPT: "comm_w"}


def mode_power(mode: str, hw: Hardware = DEFAULT_HW) -> float:
    return getattr(hw.chip, MODE_POWER_ATTR[mode])


@dataclasses.dataclass(frozen=True)
class WaveformConfig:
    dt: float = 0.001                 # 1 ms resolution (telemetry-grade)
    steps: int = 30                   # iterations to synthesize
    ckpt_every: int = 0               # 0 = no checkpoint phases
    ckpt_phase: Optional[Phase] = None
    edp_spikes: bool = True           # 50 ms overshoot at rising edges
    jitter_s: float = 0.0             # per-chip phase jitter (sigma)
    include_host: bool = False        # add per-chip host overhead (Fig. 2)


def phase_levels(tl: IterationTimeline, cfg: WaveformConfig,
                 hw: Hardware = DEFAULT_HW) -> np.ndarray:
    """Base per-sample power levels [n_samples] — no EDP spikes, no host.

    This is the only shape-determining step (sample count depends on the
    timeline), so it runs in numpy outside jit; the result feeds
    ``chip_waveform_jax`` inside the compiled engine.
    """
    seq = []
    for s in range(cfg.steps):
        phases = list(tl.phases)
        if cfg.ckpt_every and (s + 1) % cfg.ckpt_every == 0:
            phases.append(cfg.ckpt_phase or Phase("checkpoint", 2.0, CKPT))
        for p in phases:
            n = max(int(round(p.duration_s / cfg.dt)), 1)
            seq.append(np.full(n, mode_power(p.mode, hw)))
    return np.concatenate(seq)


def chip_waveform(tl: IterationTimeline, cfg: WaveformConfig,
                  hw: Hardware = DEFAULT_HW) -> np.ndarray:
    """One chip's power trace [n_samples] over cfg.steps iterations."""
    x = phase_levels(tl, cfg, hw)
    if cfg.edp_spikes:
        x = _add_edp_spikes(x, cfg.dt, hw)
    if cfg.include_host:
        x = x + hw.server.overhead_per_chip_w()
    return x


def _add_edp_spikes(x: np.ndarray, dt: float, hw: Hardware) -> np.ndarray:
    """EDP overshoot: brief (<=50 ms) peaks above TDP on rising edges."""
    out = x.copy()
    w = max(int(hw.chip.edp_window_s / dt), 1)
    rises = np.where(np.diff(x) > 0.25 * hw.chip.tdp_w)[0]
    for r in rises:
        hi = min(r + 1 + w, len(out))
        out[r + 1:hi] = np.maximum(out[r + 1:hi],
                                   x[r + 1] * hw.chip.edp_factor)
    return out


def chip_waveform_jax(levels: jnp.ndarray, dt: float,
                      hw: Hardware = DEFAULT_HW, *, edp_spikes: bool = True,
                      include_host: bool = False) -> jnp.ndarray:
    """jnp mirror of ``chip_waveform`` on a precomputed level array."""
    x = jnp.asarray(levels, jnp.float32)
    if edp_spikes:
        x = _add_edp_spikes_jax(x, dt, hw)
    if include_host:
        x = x + hw.server.overhead_per_chip_w()
    return x


def _add_edp_spikes_jax(x: jnp.ndarray, dt: float, hw: Hardware) -> jnp.ndarray:
    """Vectorized EDP overshoot: a rise at r plants a spike source of value
    x[r+1]*edp_factor at r+1 that persists for the EDP window; the output is
    the running max of x against all active sources (order-free, so it
    matches the serial rise-by-rise update exactly)."""
    w = max(int(hw.chip.edp_window_s / dt), 1)
    rise = jnp.diff(x) > 0.25 * hw.chip.tdp_w
    src = jnp.concatenate([jnp.zeros(1, x.dtype),
                           jnp.where(rise, x[1:], 0.0)]) * hw.chip.edp_factor
    # held[i] = max(src[i-w+1 .. i]): one sliding-window max (spikes decay
    # to 0 past the EDP window, and src >= 0, so 0-padding is neutral)
    held = jax.lax.reduce_window(src, jnp.asarray(0.0, x.dtype), jax.lax.max,
                                 (w,), (1,), [(w - 1, 0)])
    return jnp.maximum(x, held)


def jitter_shifts(cfg: WaveformConfig, seed: int = 0,
                  sample_chips: int = 64) -> np.ndarray:
    """Per-chip sample shifts (int32) used by both aggregate paths; a
    degenerate [0] when jitter is off so the aggregation math is uniform."""
    if cfg.jitter_s <= 0 or sample_chips <= 1:
        return np.zeros(1, np.int32)
    rng = np.random.default_rng(seed)
    sh = rng.normal(0.0, cfg.jitter_s / cfg.dt, size=sample_chips)
    return np.array([int(round(s)) for s in sh], np.int32)


def aggregate(chip_wave: np.ndarray, n_chips: int, cfg: WaveformConfig,
              hw: Hardware = DEFAULT_HW, *, seed: int = 0,
              sample_chips: int = 64) -> np.ndarray:
    """Datacenter-level waveform: sum of jittered chip replicas.

    Sampling `sample_chips` distinct jitter offsets and scaling captures the
    edge-softening of stragglers at O(sample) cost instead of O(n_chips).
    Shifted replicas are edge-padded (the chip holds its boundary power),
    not wrapped: rolling the tail onto the head used to create a spurious
    edge at t=0.
    """
    shifts = jitter_shifts(cfg, seed, sample_chips)
    n = len(chip_wave)
    idx = np.clip(np.arange(n)[None, :] - shifts[:, None], 0, n - 1)
    total = chip_wave[idx].mean(axis=0) * n_chips
    return total * (1.0 + hw.topo.distribution_loss)


def jitter_reach(shift_rows, cfg: WaveformConfig, n: int) -> int:
    """The band half-width ``reach`` for ``aggregate_jax`` over every row
    of one call: 0 when no shift is nonzero (the jitter-off [1] row),
    else the smallest power of two that is at least the largest |shift|,
    at least 8 and at least 8 sigma (``cfg.jitter_s / cfg.dt``), capped
    at n - 1.  The 8-sigma floor keeps one bucket for every seed block
    of a deployment, so a new block compiles nothing."""
    # a shift beyond n - 1 lands on the same edge sample as n - 1
    m = min(int(np.abs(np.asarray(shift_rows)).max(initial=0)), n - 1)
    if m == 0:
        return 0
    reach = 8
    while reach < max(m, 8.0 * cfg.jitter_s / cfg.dt):
        reach <<= 1
    return min(reach, n - 1)


def aggregate_jax(chip_wave: jnp.ndarray, n_chips, shifts,
                  hw: Hardware = DEFAULT_HW, *, reach: int) -> jnp.ndarray:
    """jnp mirror of ``aggregate``: the mean of the S edge-clamped
    shifted replicas, scaled to ``n_chips``.  ``shifts`` comes from
    ``jitter_shifts`` ([1] zero when jitter is off); ``n_chips`` may be a
    traced scalar.

    The mean is a banded sum over the row's shift histogram: edge-pad
    the trace by the static ``reach`` R (``jitter_reach``), and add the
    2R + 1 slices of the padded trace, each weighted by how many shifts
    fall on its value, in plain float32 multiply-adds.  Empty bins add
    exact zeros, so the result does not depend on R.  R must be at least
    every |shift| (those beyond n - 1 count as n - 1).  The loop over
    slices is unrolled 17 at a time, the band of the 8-sample floor, so
    that band is one fused pass and a wide one still compiles small."""
    n = chip_wave.shape[-1]
    sh = jnp.clip(jnp.asarray(shifts), -(n - 1), n - 1)
    pad = jnp.pad(jnp.asarray(chip_wave), reach, mode="edge")

    def add(j, acc):
        # slice j of the padded trace is the replica shifted by R - j
        count = jnp.sum(sh == reach - j, dtype=pad.dtype)
        return acc + count * jax.lax.dynamic_slice_in_dim(pad, j, n)

    acc = jax.lax.fori_loop(0, 2 * reach + 1, add,
                            jnp.zeros(n, pad.dtype),
                            unroll=min(2 * reach + 1, 17))
    mean = acc / sh.shape[-1]
    return mean * n_chips * (1.0 + hw.topo.distribution_loss)


def job_waveform(tl: IterationTimeline, n_chips: int,
                 cfg: Optional[WaveformConfig] = None,
                 hw: Hardware = DEFAULT_HW, *, seed: int = 0):
    """Convenience: (t_seconds, watts) at the utility point of coupling."""
    cfg = cfg or WaveformConfig()
    cw = chip_waveform(tl, cfg, hw)
    w = aggregate(cw, n_chips, cfg, hw, seed=seed)
    t = np.arange(len(w)) * cfg.dt
    return t, w


def swing_stats(w: np.ndarray) -> Dict[str, float]:
    return {
        "peak_w": float(np.max(w)),
        "trough_w": float(np.min(w)),
        "swing_w": float(np.max(w) - np.min(w)),
        "mean_w": float(np.mean(w)),
        "swing_frac": float((np.max(w) - np.min(w)) / max(np.max(w), 1e-9)),
    }


def swing_stats_jax(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    peak, trough = jnp.max(w), jnp.min(w)
    return {
        "peak_w": peak,
        "trough_w": trough,
        "swing_w": peak - trough,
        "mean_w": jnp.mean(w),
        "swing_frac": (peak - trough) / jnp.maximum(peak, 1e-9),
    }

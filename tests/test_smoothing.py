"""Mitigation-stack behaviour tests (paper Sec. IV)."""
import dataclasses

import numpy as np
import pytest

import repro.core as core
from repro.core.hardware import DEFAULT_HW

DT = 0.001
TDP = DEFAULT_HW.chip.tdp_w


def chip_square(period=2.0, duty=0.75, secs=30, lo=None):
    lo = DEFAULT_HW.chip.comm_w if lo is None else lo
    n = int(secs / DT)
    t = np.arange(n) * DT
    return np.where((t % period) < duty * period, TDP, lo)


# ---------------------------------------------------------------------------
# GPU power smoothing (Sec. IV-B)
# ---------------------------------------------------------------------------

def test_gpu_floor_holds_mpf():
    w = chip_square()
    gf = core.GpuPowerSmoothing(mpf_frac=0.9, ramp_up_w_per_s=5000,
                                ramp_down_w_per_s=5000, stop_delay_s=10.0)
    out, aux = gf.apply(w, DT)
    # after the first rise, power never drops below MPF (stop delay long)
    first_hi = np.argmax(w >= TDP) + 100
    assert out[first_hi:].min() >= 0.9 * TDP - 1e-3
    assert aux["energy_overhead"] > 0


def test_gpu_floor_respects_ramp_rates():
    w = chip_square()
    ru, rd = 800.0, 400.0
    gf = core.GpuPowerSmoothing(mpf_frac=0.65, ramp_up_w_per_s=ru,
                                ramp_down_w_per_s=rd, stop_delay_s=0.5)
    out, _ = gf.apply(w, DT)
    d = np.diff(out) / DT
    assert d.max() <= ru * 1.001
    assert d.min() >= -rd * 1.001


def test_gpu_floor_stop_delay_then_rampdown():
    """Fig. 5 phases: steady -> stop delay at MPF -> ramp down."""
    n = int(10 / DT)
    w = np.full(n, DEFAULT_HW.chip.idle_w)
    w[: n // 2] = TDP  # workload ends at t=5s
    gf = core.GpuPowerSmoothing(mpf_frac=0.65, ramp_up_w_per_s=2000,
                                ramp_down_w_per_s=200, stop_delay_s=1.0,
                                activity_threshold_frac=0.5)
    out, _ = gf.apply(w, DT)
    t_end = n // 2
    hold = out[t_end + 100: t_end + int(0.9 / DT)]
    assert np.all(hold >= 0.65 * TDP - 1e-3)  # floor held during stop delay
    # by 2.5s after stop delay the ramp-down has pulled power well below MPF
    later = out[t_end + int(3.5 / DT):]
    assert later.min() < 0.4 * TDP


def test_mpf_energy_overhead_monotonic_in_floor():
    w = chip_square()
    overheads = []
    for mpf in (0.5, 0.65, 0.8, 0.9):
        gf = core.GpuPowerSmoothing(mpf_frac=mpf, ramp_up_w_per_s=5000,
                                    ramp_down_w_per_s=5000, stop_delay_s=10.0)
        _, aux = gf.apply(w, DT)
        overheads.append(aux["energy_overhead"])
    assert all(b >= a - 1e-9 for a, b in zip(overheads, overheads[1:]))


def test_mpf_capped_at_90_percent():
    with pytest.raises(AssertionError):
        core.GpuPowerSmoothing(mpf_frac=0.95)


# ---------------------------------------------------------------------------
# Battery (Sec. IV-C)
# ---------------------------------------------------------------------------

def test_battery_smooths_and_conserves():
    w = chip_square() * 1000  # rack-ish scale
    swing = w.max() - w.min()
    bat = core.RackBattery(capacity_j=swing * 4, max_discharge_w=swing,
                           max_charge_w=swing, efficiency=1.0,
                           target_tau_s=5.0)
    out, aux = bat.apply(w, DT)
    assert (out.max() - out.min()) < 0.35 * swing
    # exact conservation at efficiency 1.0
    soc = aux["soc_trace"]
    e_in, e_out = w.sum() * DT, out.sum() * DT
    np.testing.assert_allclose(e_out, e_in + (soc[-1] - soc[0]), rtol=1e-3)
    assert 0.0 <= aux["soc_min_frac"] <= aux["soc_max_frac"] <= 1.0


def test_battery_lossy_never_creates_energy():
    w = chip_square() * 1000
    swing = w.max() - w.min()
    bat = core.RackBattery(capacity_j=swing * 4, max_discharge_w=swing,
                           max_charge_w=swing, efficiency=0.9)
    out, aux = bat.apply(w, DT)
    soc = aux["soc_trace"]
    # grid energy + battery drawdown must cover the load (losses >= 0)
    e_grid = out.sum() * DT
    e_load = w.sum() * DT
    assert e_grid + (soc[0] - soc[-1]) / 0.9 >= e_load - 1e-3 * e_load


def test_battery_capacity_limits_bite():
    w = chip_square() * 1000
    swing = w.max() - w.min()
    small = core.RackBattery(capacity_j=swing * 0.05, max_discharge_w=swing,
                             max_charge_w=swing)
    out, aux = small.apply(w, DT)
    # too small to remove the swing
    assert (out.max() - out.min()) > 0.5 * swing


# ---------------------------------------------------------------------------
# Firefly (Sec. IV-A)
# ---------------------------------------------------------------------------

def test_firefly_fills_valleys_to_target():
    w = chip_square()
    ff = core.Firefly(engage_frac=0.85, threshold_frac=0.8)
    out, aux = ff.apply(w, DT)
    # valleys filled except telemetry/backoff gaps
    valley = out[(w < 100)]
    frac_filled = (valley >= 0.84 * TDP).mean()
    assert frac_filled > 0.9
    assert aux["energy_overhead"] > 0.05
    assert aux["perf_overhead"] < 0.05  # paper: <5%


def test_firefly_reaches_full_tdp():
    """Paper: 'Firefly was able to increase utilization up to 100% of TDP'."""
    w = chip_square()
    ff = core.Firefly(engage_frac=1.0, threshold_frac=0.95)
    out, aux = ff.apply(w, DT)
    assert aux["reaches_tdp_frac"] >= 0.999


def test_firefly_slow_telemetry_misses_fast_swings():
    """Paper: 100 ms counters are too slow for 20 Hz swings."""
    n = int(10 / DT)
    t = np.arange(n) * DT
    w = np.where((t % 0.05) < 0.025, TDP, DEFAULT_HW.chip.comm_w)  # 20 Hz
    fast = core.Firefly(telemetry=core.TelemetrySource(period_s=0.001,
                                                       latency_s=0.001))
    slow = core.Firefly(telemetry=core.TelemetrySource(period_s=0.1,
                                                       latency_s=0.1))
    out_f, _ = fast.apply(w, DT)
    out_s, _ = slow.apply(w, DT)
    res_f = core.band_energy_fraction(out_f, DT, 15, 25)
    res_s = core.band_energy_fraction(out_s, DT, 15, 25)
    assert res_f < res_s  # fast telemetry suppresses the 20 Hz line better


# ---------------------------------------------------------------------------
# Backstop (Sec. IV-E) + combined (Sec. IV-D)
# ---------------------------------------------------------------------------

def test_backstop_detects_and_escalates():
    n = int(60 / DT)
    t = np.arange(n) * DT
    base = 50e6
    w = base + np.where(t > 20, 8e6 * np.sign(np.sin(2 * np.pi * 2.0 * t)), 0.0)
    bs = core.TelemetryBackstop(critical_hz=(1.0, 2.0, 3.0), window_s=4.0,
                                amp_threshold_w=4e6, sustain_s=2.0)
    out, aux = bs.apply(w, DT)
    assert aux["max_level"] >= 1
    assert 20.0 < aux["detect_latency_s"] < 35.0
    # response attenuates the resonant line
    pre = core.band_energy_fraction(w[int(25 / DT):], DT, 1.5, 2.5)
    post = core.band_energy_fraction(out[int(25 / DT):], DT, 1.5, 2.5)
    assert post < pre


def test_backstop_quiet_load_untouched():
    w = np.full(int(30 / DT), 50e6)
    bs = core.TelemetryBackstop(amp_threshold_w=1e6)
    out, aux = bs.apply(w, DT)
    assert aux["max_level"] == 0
    np.testing.assert_array_equal(out, w)


def _prefix_backstop_max_level(w, dt, freqs, window_s, thr, sustain_s):
    """PRE-FIX monitor replica (estimator without DC removal + state
    machine without the warm-up gate), kept inline to lock the regression."""
    import jax.numpy as jnp
    w32 = jnp.asarray(w, jnp.float32)
    n = len(w)
    win = max(int(window_s / dt), 8)
    f = jnp.asarray(freqs, jnp.float32)
    t = jnp.arange(n, dtype=jnp.float32) * dt
    ph = jnp.exp(-2j * jnp.pi * t[:, None] * f[None, :])
    cs = jnp.cumsum(w32[:, None] * ph, axis=0)
    acc = jnp.concatenate([cs[:win], cs[win:] - cs[:-win]]) if n > win else cs
    denom = np.minimum(np.arange(n) + 1, win)
    worst = np.asarray(2.0 * jnp.abs(acc)).max(axis=1) / denom
    sustain_n = max(int(sustain_s / dt), 1)
    level = above = 0
    for hit in worst > thr:
        above = above + 1 if hit else 0
        if hit and above >= sustain_n and level < 3:
            level, above = level + 1, 0
    return level


def test_backstop_detects_mw_scale_oscillation():
    """Acceptance regression: a 1e5 W oscillation riding on a 5e8 W DC
    offset over a 10-minute f32 trace.  The fixed backstop (both the jnp
    oracle and the Pallas kernel path) stays quiet on the DC-only trace
    and detects the oscillation with the right latency.  The pre-fix
    sliding path provably misses it: its partial warm-up windows read
    ~2*DC at every usable threshold, so the monitor escalates on the
    QUIET trace — no threshold both rejects a quiet MW trace and sees a
    1e5 W line."""
    dt = 0.002
    n = int(600.0 / dt)
    t = np.arange(n) * dt
    quiet = np.full(n, 5e8, np.float32)
    onset = 300.0
    signal = (5e8 + np.where(t >= onset,
                             1e5 * np.sin(2 * np.pi * 2.0 * t), 0.0))
    freqs = (0.5, 1.0, 2.0, 9.0)
    for use_pallas in (False, True):
        bs = core.TelemetryBackstop(critical_hz=freqs, window_s=8.0,
                                    amp_threshold_w=5e4, sustain_s=1.5,
                                    use_pallas=use_pallas)
        _, aux_q = bs.apply(quiet, dt)
        assert aux_q["max_level"] == 0, f"false positive (pallas={use_pallas})"
        _, aux_s = bs.apply(signal, dt)
        assert aux_s["max_level"] >= 1, f"missed signal (pallas={use_pallas})"
        # detection after onset + window fill + sustain, not at warm-up
        assert onset < aux_s["detect_latency_s"] < onset + 15.0
    # the pre-fix monitor escalates on the quiet trace => provably cannot
    # separate the 1e5 W signal from a quiet MW trace at this threshold
    assert _prefix_backstop_max_level(quiet, dt, freqs, 8.0, 5e4, 1.5) >= 1


def test_backstop_fused_scan_matches_kernel_and_oracle():
    """The fused amps->escalation scan (one lax.scan over window-sized
    segments; the [n, K] amplitude matrix never exists) implements the
    same hop-and-overlap math as the Pallas sliding kernel: identical
    ``worst_bin_amp`` stream and escalation trace, and the same verdicts
    as the separate-pass cumsum oracle — including on a tail that is not
    a whole number of windows."""
    import jax.numpy as jnp
    dt = 0.002
    n = int(45.0 / dt) + 7                   # non-multiple of win
    t = np.arange(n) * dt
    w = (50e6 + np.where(t > 15, 6e6 * np.sin(2 * np.pi * 2.0 * t), 0.0)
         ).astype(np.float32)
    base = core.TelemetryBackstop(critical_hz=(0.5, 1.0, 2.0), window_s=4.0,
                                  amp_threshold_w=3e6, sustain_s=1.0,
                                  use_pallas=False)
    fused = base                                       # fused_scan defaults on
    kernel = dataclasses.replace(base, use_pallas=True)
    oracle = dataclasses.replace(base, fused_scan=False)
    out_f, aux_f = fused.apply_jax(jnp.asarray(w), dt)
    out_k, aux_k = kernel.apply_jax(jnp.asarray(w), dt)
    out_o, aux_o = oracle.apply_jax(jnp.asarray(w), dt)
    # same segment-restarted prefix-sum math as the kernel: bit-level match
    np.testing.assert_array_equal(np.asarray(aux_f["worst_bin_amp"]),
                                  np.asarray(aux_k["worst_bin_amp"]))
    np.testing.assert_array_equal(np.asarray(aux_f["levels"]),
                                  np.asarray(aux_k["levels"]))
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_k))
    # verdict parity with the cumsum-oracle reference path (the two
    # estimators round differently near threshold crossings, so the
    # escalation trace may shift by a sample — the detection verdict,
    # latency and amplitude stream must agree)
    assert int(aux_f["max_level"]) == int(aux_o["max_level"]) >= 1
    np.testing.assert_allclose(float(aux_f["detect_latency_s"]),
                               float(aux_o["detect_latency_s"]), atol=0.1)
    np.testing.assert_allclose(np.asarray(aux_f["worst_bin_amp"]),
                               np.asarray(aux_o["worst_bin_amp"]),
                               rtol=5e-3, atol=200.0)


def test_backstop_warmup_spike_does_not_escalate():
    """A spike at t=0 must not trigger escalation off partial-window
    amplitude estimates: no level change before one full window has
    streamed (and none at all — the spike's full-window amplitude is
    small)."""
    dt = 0.002
    n = int(30.0 / dt)
    w = np.full(n, 50e6, np.float32)
    w[:25] += 4e7                            # hard spike at t=0
    bs = core.TelemetryBackstop(window_s=8.0, amp_threshold_w=1e6,
                                sustain_s=0.2, use_pallas=False)
    win = int(8.0 / dt)
    for use_pallas in (False, True):
        bs = dataclasses.replace(bs, use_pallas=use_pallas)
        out, aux = bs.apply(w, dt)
        assert aux["levels"][:win].max() == 0, \
            f"escalated during warm-up (pallas={use_pallas})"
        assert aux["max_level"] == 0
        np.testing.assert_array_equal(out, w)


def test_design_mitigation_finds_passing_combo():
    tl = core.synthetic_timeline(period_s=2.0, comm_frac=0.25)
    cfg = core.WaveformConfig(dt=0.002, steps=20, jitter_s=0.002)
    n_chips = 512
    w = core.aggregate(core.chip_waveform(tl, cfg), n_chips, cfg)
    spec = core.example_specs(job_mw=w.mean() / 1e6)["moderate"]
    sol = core.design_mitigation(spec, w, cfg.dt, n_chips)
    assert sol is not None
    assert sol["report"].ok
    # must not be maximally wasteful: solver prefers low-MPF solutions
    assert sol["energy_overhead"] < 0.5


@pytest.mark.parametrize("cap_x_swing", [0.25, 1.0])
def test_battery_at_100mw_tracks_float64(cap_x_swing):
    """A campus-scale load (100 MW, four beating square waves, 60 s at
    2 ms): the float32 battery stays within 5e-5 of the mean of a float64
    evaluation.  A float32 grid target held near 100 MW moves in 8 W
    steps, and its drift reached the charge and the output (3e-4-7e-4 of
    the mean); the scan runs on the load less its mean."""
    import campus_ref
    dt = 0.002
    t = np.arange(30000) * dt
    w = 1e8 + sum(a * np.sign(np.sin(2 * np.pi * t / p + ph))
                  for a, p, ph in ((1.5e7, 2.0, 0.3), (8e6, 3.0, 1.1),
                                   (5e6, 1.0, 2.0), (3e6, 1.5, 0.7)))
    swing = w.max() - w.min()
    bat = core.RackBattery(capacity_j=cap_x_swing * swing,
                           max_discharge_w=swing, max_charge_w=swing,
                           target_tau_s=10.0)
    got = np.asarray(bat.apply_jax(w.astype(np.float32), dt)[0])
    want = campus_ref.battery(
        w, capacity_j=bat.capacity_j, max_discharge_w=swing,
        max_charge_w=swing, efficiency=bat.efficiency, target_tau_s=10.0,
        initial_soc=bat.initial_soc, dt=dt)
    assert np.abs(got - want).max() < 5e-5 * w.mean()

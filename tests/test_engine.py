"""Batched scenario engine: parity with the serial path + batching laws.

The contract under test: for every mitigation, ``simulate_batch`` /
``apply_batch`` (vmapped apply_jax) produce the same waveforms, swing
stats, band reports and spec verdicts as looping the serial ``simulate`` /
``apply`` over the scenarios one at a time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core
from repro.core import engine
from repro.core.hardware import DEFAULT_HW

DT = 0.002
N_CHIPS = 512


def _timeline(period=1.0, comm=0.3, moe=False):
    return core.synthetic_timeline(period_s=period, comm_frac=comm,
                                   moe_notch=moe)


def _cfg(**kw):
    kw.setdefault("dt", DT)
    kw.setdefault("steps", 6)
    return core.WaveformConfig(**kw)


def _chip_wave():
    return core.chip_waveform(_timeline(), _cfg())


def _dc_wave():
    cfg = _cfg(jitter_s=0.002)
    return core.aggregate(core.chip_waveform(_timeline(), cfg), N_CHIPS, cfg)


def _gpu(mpf, **kw):
    kw.setdefault("ramp_up_w_per_s", 2000)
    kw.setdefault("ramp_down_w_per_s", 2000)
    kw.setdefault("stop_delay_s", 1.0)
    return core.GpuPowerSmoothing(mpf_frac=mpf, **kw)


def _bat(cap, swing):
    return core.RackBattery(capacity_j=cap, max_discharge_w=swing,
                            max_charge_w=swing, target_tau_s=5.0)


# ---------------------------------------------------------------------------
# apply_batch: vmapped apply_jax == per-config serial apply
# ---------------------------------------------------------------------------

def _grids():
    chip = _chip_wave()
    dc = _dc_wave()
    swing_c = float(chip.max() - chip.min())
    swing_d = float(dc.max() - dc.min())
    return {
        "gpu_floor": (chip, [_gpu(m) for m in (0.5, 0.65, 0.9)]),
        "battery": (dc, [_bat(f * swing_d, swing_d) for f in (0.5, 1.0, 2.0)]),
        "firefly": (chip, [core.Firefly(engage_frac=e, threshold_frac=e - 0.05)
                           for e in (0.85, 0.95)]),
        "backstop": (dc, [core.TelemetryBackstop(
            critical_hz=(0.5, 1.0), window_s=2.0, sustain_s=0.5,
            amp_threshold_w=a * swing_d) for a in (0.05, 10.0)]),
        "backstop_jnp": (dc, [core.TelemetryBackstop(
            critical_hz=(0.5, 1.0), window_s=2.0, sustain_s=0.5,
            amp_threshold_w=a * swing_d, use_pallas=False)
            for a in (0.05, 10.0)]),
        "combined": (dc, [core.CombinedMitigation(
            _gpu(m), _bat(swing_d, swing_d), N_CHIPS) for m in (0.5, 0.9)]),
        "stack": (chip, [core.Stack([_gpu(m), _bat(2 * swing_c, swing_c)])
                         for m in (0.5, 0.9)]),
    }


@pytest.mark.parametrize("name", ["gpu_floor", "battery", "firefly",
                                  "backstop", "backstop_jnp", "combined",
                                  "stack"])
def test_apply_batch_matches_serial(name):
    w, mits = _grids()[name]
    outs, aux = core.apply_batch(mits, w, DT)
    assert outs.shape == (len(mits), len(w))
    for i, m in enumerate(mits):
        ref, ref_aux = m.apply(w, DT)
        np.testing.assert_allclose(outs[i], ref, rtol=1e-5, atol=1e-3)
        # scalar aux entries agree row-by-row
        for k, v in ref_aux.items():
            if isinstance(v, float):
                np.testing.assert_allclose(
                    np.asarray(aux[k][i], np.float64), v,
                    rtol=1e-4, atol=1e-6, err_msg=f"{name}.{k}")


# ---------------------------------------------------------------------------
# config batching: leaves built on the host == the per-element device stack
# ---------------------------------------------------------------------------

STACK_B = 6


def _stack_reference(mits):
    """The stacking ``_normalize_mits`` did before leaves were built in
    NumPy: one float32 device array per row and leaf, then a ``jnp.stack``
    per leaf, and the on-mask from a Python list."""
    mits = list(mits) * STACK_B if len(mits) == 1 else list(mits)
    enabled = [m for m in mits if m is not None]
    on = (None if len(enabled) == len(mits) else
          jnp.asarray([0.0 if m is None else 1.0 for m in mits], jnp.float32))
    rows = [enabled[0] if m is None else m for m in mits]
    return jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x, jnp.float32) for x in xs]),
        *rows), on


def _stack_row(kind, i):
    """Row ``i`` of a class: values a float32 cannot hold exactly, a NumPy
    scalar and a Python int among them."""
    swing = 3.0e6 + i / 3
    if kind == "gpu":
        return _gpu(0.5 + i / 30, ramp_up_w_per_s=np.float64(2000 + i / 7))
    if kind == "battery":
        return core.RackBattery(capacity_j=(i + 1) * swing / 7,
                                max_discharge_w=int(swing),
                                max_charge_w=swing, target_tau_s=5.0 + i / 3)
    if kind == "backstop":
        return core.TelemetryBackstop(amp_threshold_w=1e5 * (i + 1) / 3,
                                      alpha1=0.25 + i / 9)
    return core.Stack([_stack_row("gpu", i), _stack_row("battery", i)])


def _stack_rows(kind, rows):
    if rows == "tiled":
        return [_stack_row(kind, 1)]
    mits = [_stack_row(kind, i) for i in range(STACK_B)]
    if rows == "mixed":
        mits[0] = mits[3] = None
    return mits


def _same_leaf(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert got.weak_type == want.weak_type
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("kind,rows,leaves", [
    ("gpu", "all", "python"), ("battery", "all", "python"),
    ("backstop", "all", "python"), ("stack", "all", "python"),
    ("gpu", "mixed", "python"), ("stack", "mixed", "python"),
    ("backstop", "tiled", "python"), ("battery", "mixed", "jax.Array")])
def test_stacked_mitigations_match_per_element_stack(kind, rows, leaves):
    mits = _stack_rows(kind, rows)
    want, want_on = _stack_reference(mits)
    if leaves == "jax.Array":
        mits = [None if m is None else
                jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), m)
                for m in mits]
    got, got_on = engine._normalize_mits(mits, STACK_B, "test")
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _same_leaf(g, w)
    assert (got_on is None) == (want_on is None)
    if want_on is not None:
        _same_leaf(got_on, want_on)


# ---------------------------------------------------------------------------
# simulate_batch: one compiled call == loop of serial simulate
# ---------------------------------------------------------------------------

def _scenarios():
    """(device, rack) configs covering every mitigation class, batchable
    per group."""
    dc = _dc_wave()
    swing = float(dc.max() - dc.min())
    return {
        "device_gpu": ([_gpu(m) for m in (0.5, 0.8, 0.9)], None),
        "device_firefly": ([core.Firefly(engage_frac=e, threshold_frac=e - 0.05)
                            for e in (0.85, 0.95)], None),
        "rack_battery": (None, [_bat(f * swing, swing) for f in (0.5, 2.0)]),
        "rack_backstop": (None, [core.TelemetryBackstop(
            critical_hz=(0.5, 1.0), window_s=2.0, sustain_s=0.5,
            amp_threshold_w=a * swing) for a in (0.05, 10.0)]),
        "rack_backstop_jnp": (None, [core.TelemetryBackstop(
            critical_hz=(0.5, 1.0), window_s=2.0, sustain_s=0.5,
            amp_threshold_w=a * swing, use_pallas=False)
            for a in (0.05, 10.0)]),
        "gpu_plus_battery": ([_gpu(m) for m in (0.5, 0.9)],
                             [_bat(f * swing, swing) for f in (0.5, 2.0)]),
    }


@pytest.mark.parametrize("name", ["device_gpu", "device_firefly",
                                  "rack_battery", "rack_backstop",
                                  "rack_backstop_jnp",
                                  "gpu_plus_battery"])
def test_simulate_batch_matches_simulate(name):
    dev, rack = _scenarios()[name]
    B = len(dev) if dev is not None else len(rack)
    tl = _timeline()
    # firefly's ballast quantization has ceil() decision boundaries that
    # f32/f64 EDP-spike rounding can flip; exact levels keep parity exact
    cfg = _cfg(jitter_s=0.002, edp_spikes=(name != "device_firefly"))
    spec = core.example_specs(job_mw=0.1)["moderate"]

    res = engine.simulate_batch(tl, N_CHIPS, cfg, device_mitigation=dev,
                                rack_mitigation=rack, spec=spec, seeds=3)
    assert len(res) == B
    for i in range(B):
        ref = core.simulate(
            tl, N_CHIPS, cfg,
            device_mitigation=dev[i] if dev is not None else None,
            rack_mitigation=rack[i] if rack is not None else None,
            spec=spec, seed=3)
        np.testing.assert_allclose(res.dc_raw[i], ref.dc_raw,
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.dc_mitigated[i], ref.dc_mitigated,
                                   rtol=1e-4, atol=1e-3)
        if dev is not None:
            np.testing.assert_allclose(res.chip_mitigated[i],
                                       ref.chip_mitigated,
                                       rtol=1e-5, atol=1e-3)
        for k, v in ref.swing_mitigated.items():
            np.testing.assert_allclose(res.swing_mitigated[k][i], v,
                                       rtol=1e-4, atol=1e-3, err_msg=k)
        for k, v in ref.bands_mitigated.items():
            np.testing.assert_allclose(res.bands_mitigated[k][i], v,
                                       rtol=5e-3, atol=2e-3, err_msg=k)
        np.testing.assert_allclose(res.energy_overhead[i],
                                   ref.energy_overhead, rtol=1e-3, atol=1e-6)
        # spec verdicts and violation sets agree exactly
        assert bool(res.spec_ok[i]) == ref.spec_report.ok
        assert res.report(i).violations == ref.spec_report.violations
        # the reconstructed per-scenario SimResult round-trips
        sr = res.scenario(i)
        assert sr.spec_report.ok == ref.spec_report.ok
        np.testing.assert_allclose(sr.dc_mitigated, ref.dc_mitigated,
                                   rtol=1e-4, atol=1e-3)


def test_simulate_batch_broadcasts_fleet_and_seeds():
    tl = _timeline()
    cfg = _cfg(jitter_s=0.002)
    fleets = [128, 512, 2048]
    res = engine.simulate_batch(tl, fleets, cfg, seeds=[0, 1, 2])
    for i, n in enumerate(fleets):
        ref = core.simulate(tl, n, cfg, seed=i)
        np.testing.assert_allclose(res.dc_raw[i], ref.dc_raw,
                                   rtol=1e-4, atol=1e-3)


def test_simulate_batch_mixes_enabled_and_disabled_rows():
    """Disabled (None) rows batch alongside enabled configs: the masked-off
    row reproduces the unmitigated serial run exactly."""
    tl = _timeline()
    cfg = _cfg(jitter_s=0.002)
    dc = _dc_wave()
    swing = float(dc.max() - dc.min())
    spec = core.example_specs(job_mw=0.1)["moderate"]
    dev = [_gpu(0.5), None, _gpu(0.9), None]
    rack = [_bat(swing, swing), _bat(2 * swing, swing), None, None]
    res = engine.simulate_batch(tl, N_CHIPS, cfg, device_mitigation=dev,
                                rack_mitigation=rack, spec=spec, seeds=3)
    for i in range(4):
        ref = core.simulate(tl, N_CHIPS, cfg, device_mitigation=dev[i],
                            rack_mitigation=rack[i], spec=spec, seed=3)
        np.testing.assert_allclose(res.dc_mitigated[i], ref.dc_mitigated,
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(res.energy_overhead[i],
                                   ref.energy_overhead, rtol=1e-3, atol=1e-6)
        assert bool(res.spec_ok[i]) == ref.spec_report.ok
        assert res.report(i).violations == ref.spec_report.violations
        # scenario() reflects the mask: no chip_mitigated and no
        # placeholder aux on disabled rows (the serial reference has none)
        sc = res.scenario(i)
        assert (sc.chip_mitigated is None) == (dev[i] is None)
        assert ("device" in sc.aux) == (dev[i] is not None)
        assert ("rack" in sc.aux) == (rack[i] is not None)


def test_simulate_batch_rejects_mixed_lengths():
    with pytest.raises(ValueError):
        engine.simulate_batch([_timeline(1.0), _timeline(2.0)],
                              N_CHIPS, _cfg())


# ---------------------------------------------------------------------------
# sweep: cartesian product, bucketed by waveform length
# ---------------------------------------------------------------------------

def test_sweep_buckets_mixed_length_workloads():
    workloads = {"short": _timeline(1.0), "long": _timeline(2.0, moe=True)}
    cfg = _cfg(jitter_s=0.002, steps=4)
    spec = core.example_specs(job_mw=0.1)["moderate"]
    dc = core.aggregate(core.chip_waveform(workloads["short"], cfg),
                        N_CHIPS, cfg)
    swing = float(dc.max() - dc.min())
    configs = [(_gpu(0.65), _bat(swing, swing)),
               (_gpu(0.9), _bat(2 * swing, swing))]
    recs = engine.sweep(workloads, [256, 512], configs, cfg, spec=spec)
    assert len(recs) == 2 * 2 * 2          # workloads x fleets x configs
    # record order follows the declared cartesian order despite bucketing
    assert [r["workload"] for r in recs] == ["short"] * 4 + ["long"] * 4
    for r in recs:
        ci, ni = r["config"], r["n_chips"]
        ref = core.simulate(workloads[r["workload"]], ni, cfg,
                            device_mitigation=configs[ci][0],
                            rack_mitigation=configs[ci][1], spec=spec)
        assert r["spec_ok"] == ref.spec_report.ok
        np.testing.assert_allclose(r["energy_overhead"], ref.energy_overhead,
                                   rtol=1e-3, atol=1e-6)


# ---------------------------------------------------------------------------
# batched design grid
# ---------------------------------------------------------------------------

def _serial_design_reference(spec, w, dt, n_chips, period_hint_s=2.0):
    """The pre-engine serial grid search, kept as the parity oracle."""
    swing = float(w.max() - w.min())
    mpf_grid = [0.0, 0.5, 0.65, 0.8, 0.9]
    cap_grid = [0.0] + [swing * period_hint_s * f for f in
                        (0.125, 0.25, 0.5, 1.0, 2.0)]
    for mpf in mpf_grid:
        for cap in cap_grid:
            gpu = _design_gpu(spec, mpf, n_chips) if mpf > 0 else None
            bat = (core.RackBattery(capacity_j=cap, max_discharge_w=swing,
                                    max_charge_w=swing) if cap > 0 else None)
            if gpu and bat:
                out, _ = core.CombinedMitigation(gpu, bat, n_chips).apply(w, dt)
            elif gpu:
                per_chip, _ = gpu.apply(w / n_chips, dt)
                out = per_chip * n_chips
            elif bat:
                out, _ = bat.apply(w, dt)
            else:
                out = w
            if spec.validate(out, dt).ok:
                return mpf, cap
    return None


def _design_gpu(spec, mpf, n_chips):
    return core.GpuPowerSmoothing(
        mpf_frac=mpf,
        ramp_up_w_per_s=spec.time.ramp_up_w_per_s / n_chips,
        ramp_down_w_per_s=spec.time.ramp_down_w_per_s / n_chips)


def test_design_mitigation_matches_serial_reference():
    tl = _timeline(period=2.0, comm=0.25)
    cfg = core.WaveformConfig(dt=0.002, steps=20, jitter_s=0.002)
    w = core.aggregate(core.chip_waveform(tl, cfg), N_CHIPS, cfg)
    spec = core.example_specs(job_mw=w.mean() / 1e6)["moderate"]
    sol = core.design_mitigation(spec, w, cfg.dt, N_CHIPS)
    assert sol is not None and sol["report"].ok
    ref = _serial_design_reference(spec, w, cfg.dt, N_CHIPS)
    assert ref is not None
    assert (sol["mpf_frac"], sol["battery_capacity_j"]) == pytest.approx(ref)


def test_design_grid_vmap_matches_per_candidate():
    """Each cell of the vmapped (MPF x capacity) grid equals the serial
    gated evaluation of that candidate."""
    tl = _timeline(period=2.0, comm=0.25)
    cfg = core.WaveformConfig(dt=0.002, steps=10, jitter_s=0.002)
    w = core.aggregate(core.chip_waveform(tl, cfg), N_CHIPS, cfg)
    spec = core.example_specs(job_mw=w.mean() / 1e6)["moderate"]
    swing = float(w.max() - w.min())
    mpf_grid, cap_grid = [0.0, 0.9], [0.0, 2.0 * swing]
    sol = engine.design_grid(spec, w, cfg.dt, N_CHIPS, mpf_grid, cap_grid,
                             swing=swing)
    grid_ok = (sol["grid_ok"] if sol is not None
               else np.zeros((2, 2), bool))
    for i, mpf in enumerate(mpf_grid):
        for j, cap in enumerate(cap_grid):
            gpu = _design_gpu(spec, mpf, N_CHIPS) if mpf > 0 else None
            bat = (core.RackBattery(capacity_j=cap, max_discharge_w=swing,
                                    max_charge_w=swing) if cap > 0 else None)
            if gpu and bat:
                out, _ = core.CombinedMitigation(gpu, bat, N_CHIPS).apply(
                    w, cfg.dt)
            elif gpu:
                per, _ = gpu.apply(w / N_CHIPS, cfg.dt)
                out = per * N_CHIPS
            elif bat:
                out, _ = bat.apply(w, cfg.dt)
            else:
                out = w
            assert bool(grid_ok[i, j]) == spec.validate(out, cfg.dt).ok, \
                (mpf, cap)


# ---------------------------------------------------------------------------
# aggregate jitter: edge padding, no wraparound
# ---------------------------------------------------------------------------

def test_aggregate_jitter_does_not_wrap_tail_to_head():
    cfg = core.WaveformConfig(dt=0.001, steps=1, jitter_s=0.02)
    lo, hi = 100.0, 200.0
    chip = np.concatenate([np.full(2000, lo), np.full(1000, hi)])
    agg = core.aggregate(chip, N_CHIPS, cfg, seed=0)
    scale = N_CHIPS * (1.0 + DEFAULT_HW.topo.distribution_loss)
    # head must see only the head level: a wrapping shift would leak the
    # hi tail into t=0 and lift it above lo
    np.testing.assert_allclose(agg[:100] / scale, lo, rtol=1e-6)
    # tail likewise holds its boundary level
    np.testing.assert_allclose(agg[-1] / scale, hi, rtol=1e-6)


def test_aggregate_jax_matches_numpy():
    from repro.core.waveform import aggregate_jax, jitter_reach, jitter_shifts
    cfg = core.WaveformConfig(dt=0.001, steps=3, jitter_s=0.005)
    chip = core.chip_waveform(_timeline(), cfg)
    shifts = jitter_shifts(cfg, seed=7)
    ref = core.aggregate(chip, N_CHIPS, cfg, seed=7)
    out = np.asarray(aggregate_jax(
        np.asarray(chip, np.float32), float(N_CHIPS), shifts,
        reach=jitter_reach(shifts, cfg, len(chip))))
    np.testing.assert_allclose(out, ref, rtol=1e-5)


def _band_case(case):
    """(chip, shifts, cfg) of one ``aggregate_jax`` case: chip [n] or, for
    a campus row, [K, n] with shifts [K, S]."""
    from repro.core.waveform import jitter_shifts
    cfg = _cfg(jitter_s=(5 if case.startswith("sigma5") else 1) * DT)
    chip = core.chip_waveform(_timeline(), cfg)
    shifts = jitter_shifts(cfg, seed=3)
    if case == "sigma5":
        shifts = jitter_shifts(cfg, seed=4, sample_chips=256)
    elif case == "jitter_off":
        cfg = _cfg()
        shifts = jitter_shifts(cfg, seed=3)
    elif case == "edge_of_band":
        shifts[:2] = (8, -8)
    elif case == "beyond_n":
        chip = chip[990:1010]
        shifts[:2] = (97, -300)
    elif case == "pad_and_mask":
        chip = chip.copy()
        chip[-500:] = chip[-501]
    elif case == "campus":
        chip = np.stack([np.roll(chip, 137 * k) for k in range(3)])
        shifts = np.stack([jitter_shifts(cfg, seed=k) for k in range(3)])
    return chip, shifts, cfg


def _gather_aggregate(chip, n_chips, shifts):
    """The mean of the S edge-clamped shifted replicas as one gather
    against a [S, n] shift-index matrix, in float32: the banded sum's
    term-by-term counterpart."""
    n = chip.shape[-1]
    idx = jnp.clip(jnp.arange(n)[None, :] - shifts[:, None], 0, n - 1)
    return (chip[idx].mean(axis=0) * n_chips
            * (1.0 + DEFAULT_HW.topo.distribution_loss))


@pytest.mark.parametrize("case", [
    "sigma1", "sigma5", "jitter_off", "edge_of_band", "beyond_n",
    "pad_and_mask", "campus", "sigma5_wide"])
def test_aggregate_band_matches_gather(case, monkeypatch):
    """The banded sum over each row's shift histogram against a gather
    of the S shifted replicas and against the float64 NumPy
    ``aggregate``, also where the band is wider than S."""
    from repro.core import waveform
    chip, shifts, cfg = _band_case(case)
    n = chip.shape[-1]
    reach = waveform.jitter_reach(shifts, cfg, n)
    assert (2 * reach + 1 > shifts.shape[-1]) == (case == "sigma5_wide")

    def agg(fn):
        f = lambda c, s: fn(c, float(N_CHIPS), s)
        return np.asarray(jax.vmap(f)(chip.astype(np.float32), shifts)
                          if chip.ndim == 2 else
                          f(chip.astype(np.float32), shifts))

    out = agg(functools.partial(waveform.aggregate_jax, reach=reach))
    np.testing.assert_allclose(out, agg(_gather_aggregate), rtol=1e-6)
    refs = []
    for c, s in zip(chip.reshape(-1, n), shifts.reshape(-1, shifts.shape[-1])):
        monkeypatch.setattr(waveform, "jitter_shifts", lambda *a, **k: s)
        refs.append(waveform.aggregate(c, N_CHIPS, cfg))
    np.testing.assert_allclose(out.reshape(-1, n), np.stack(refs), rtol=1e-5)
    if case == "pad_and_mask":
        # the valid region of an edge-filled row is the unpadded row's
        short = waveform.aggregate_jax(chip[:-500].astype(np.float32),
                                       float(N_CHIPS), shifts, reach=reach)
        np.testing.assert_array_equal(out[:-500], np.asarray(short))


def test_aggregate_band_does_not_depend_on_reach():
    """Empty bins add exact zeros: a wider band than the shifts need
    gives the same bits."""
    from repro.core import waveform
    chip, shifts, cfg = _band_case("sigma1")
    chip = chip.astype(np.float32)
    outs = [np.asarray(waveform.aggregate_jax(chip, float(N_CHIPS), shifts,
                                              reach=r)) for r in (8, 16, 40)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


@pytest.mark.parametrize("shifts,sigma,n,want", [
    ([0], 0.0, 1000, 0),            # jitter off: one slice
    ([3, -2, 0], 1.0, 1000, 8),     # the sweep cells' sigma of one sample
    ([9, -1], 1.0, 1000, 16),       # a shift past the floor
    ([-16, 2], 1.0, 1000, 16),
    ([1, -1], 1.5, 1000, 16),       # the 8-sigma floor, 12
    ([1, -1], 3.0, 1000, 32),
    ([3, 0], 1.0, 6, 5),            # capped at n - 1
    ([40, 0], 1.0, 6, 5),           # a shift beyond n
])
def test_jitter_reach_buckets(shifts, sigma, n, want):
    from repro.core.waveform import jitter_reach
    cfg = _cfg(jitter_s=sigma * DT)
    assert jitter_reach(np.asarray([shifts], np.int32), cfg, n) == want

"""Several jobs behind one grid connection: ``Campus`` rows through
``Study`` and the engine, against a plain float64 reference
(``campus_ref``), and tied to the single-job row they generalize."""
import numpy as np
import pytest

import campus_ref as ref
import repro.core as core
from repro.core import Campus, Tenant
from repro.core.waveform import jitter_shifts

DT = 0.002
WINDOW_S = 3.6                     # 1,800 samples
SAMPLE_CHIPS = 8
N_CHIPS = 2000
SEEDS = [3, 11]
#: (name, period_s, comm_frac, moe_notch, share)
TENANTS = [("dense_1s", 1.0, 0.3, False, 0.5),
           ("moe_1p5s", 1.5, 0.25, True, 0.3),
           ("fast_0p6s", 0.6, 0.4, False, 0.2)]


def _cfg():
    return core.WaveformConfig(dt=DT, steps=1, jitter_s=0.002)


def _campus(tenants=TENANTS):
    return Campus(tuple(Tenant(n, core.synthetic_timeline(p, c, m), s)
                        for n, p, c, m, s in tenants), WINDOW_S)


def _gpu():
    return core.GpuPowerSmoothing(mpf_frac=0.8, ramp_up_w_per_s=2000.0,
                                  ramp_down_w_per_s=2000.0,
                                  stop_delay_s=1.0)


def _battery():
    return core.RackBattery(capacity_j=2e5, max_discharge_w=1.5e5,
                            max_charge_w=1.5e5, target_tau_s=10.0)


def _spec():
    # the moderate spec at the campus's ~0.39 MW
    return core.example_specs(job_mw=0.39)["moderate"]


CONFIGS = {"none": None, "floor": (_gpu(), None),
           "battery": (None, _battery()),
           "floor+battery": (_gpu(), _battery())}


def _ref_row(campus, seed, config, tenants=None, sample_chips=SAMPLE_CHIPS):
    """The float64 reference's (raw, floor-mitigated, mitigated) of one
    campus row, from plain numbers."""
    hw = core.DEFAULT_HW
    chips = campus.tenant_chips(N_CHIPS)
    plain = [{"phases": ref.phase_list(p, c, m), "chips": float(n)}
             for (_, p, c, m, _), n in zip(TENANTS, chips)]
    gpu, bat = CONFIGS[config] or (None, None)
    floor = None if gpu is None else {
        f: getattr(gpu, f) for f in ("mpf_frac", "activity_threshold_frac",
                                     "ramp_up_w_per_s", "ramp_down_w_per_s",
                                     "stop_delay_s", "edp_cap_frac")}
    battery = None if bat is None else {
        f: getattr(bat, f) for f in ("capacity_j", "max_discharge_w",
                                     "max_charge_w", "efficiency",
                                     "target_tau_s", "initial_soc")}
    return ref.campus_row(
        plain if tenants is None else [plain[k] for k in tenants], seed,
        n=campus.n_samples(_cfg()), dt=DT,
        hw={"tdp_w": hw.chip.tdp_w, "comm_w": hw.chip.comm_w,
            "edp_factor": hw.chip.edp_factor,
            "edp_window_s": hw.chip.edp_window_s,
            "loss": hw.topo.distribution_loss},
        jitter_s=0.002, sample_chips=sample_chips, floor=floor, bat=battery)


def _ref_spec(spec):
    return {"ramp_up_w_per_s": spec.time.ramp_up_w_per_s,
            "ramp_down_w_per_s": spec.time.ramp_down_w_per_s,
            "dynamic_range_w": spec.time.dynamic_range_w,
            "window_s": spec.time.window_s,
            "ramp_window_s": spec.time.ramp_window_s,
            "band_hz": spec.freq.band_hz,
            "max_energy_fraction": spec.freq.max_energy_fraction,
            "min_ac_rms_frac": spec.freq.min_ac_rms_frac}


#: field -> (rtol, atol, scale of atol).  The program is float32
#: throughout; "mean_mw" and "mean_w" scale atol by the row's mean power
#: in the field's unit, "ramp" by the spec's ramp limit.
TOL = {
    # sums over 8 sampled chips, 3 tenants and 1,800 samples in float32
    # agree to a few parts in 1e7; a bfloat16 sum is ~1e-3 off
    "mean_mw": (1e-5, 0.0, None),
    "swing_mw": (1e-4, 0.0, None),
    # a float32 waveform at ~0.4 MW rounds by ~0.03 W (observed 0.03 W
    # after the battery): 1e-6 of the mean (~0.4 W) past test_study's
    # rtol 1e-4
    "swing_mitigated_mw": (1e-4, 1e-6, "mean_mw"),
    "metrics.dynamic_range_w": (1e-4, 1e-6, "mean_w"),
    # a difference of two float32 sums (test_study: rtol 1e-3, atol 1e-6)
    "energy_overhead": (1e-3, 1e-6, None),
    # the box-car derivative of a float32 trace: 0.03 W of rounding over
    # a 2 ms step is 15 W/s, observed 33 W/s; 3e-3 of the 19.5 kW/s limit
    # (58 W/s) past test_study's rtol 5e-3
    "metrics.max_ramp_up_w_per_s": (5e-3, 3e-3, "ramp"),
    "metrics.max_ramp_down_w_per_s": (5e-3, 3e-3, "ramp"),
    # a share of the float32 FFT's energy (test_study: rtol 5e-3)
    "metrics.band_energy_fraction": (5e-3, 1e-4, None),
}


def _get(rec, k):
    return rec["metrics"][k[8:]] if k.startswith("metrics.") else rec[k]


def _mismatches(got, want, spec):
    """Fields of ``got`` outside their tolerance of ``want``, and
    verdicts that differ."""
    scale = {None: 1.0, "ramp": spec["ramp_up_w_per_s"],
             "mean_mw": want["mean_mw"], "mean_w": 1e6 * want["mean_mw"]}
    bad = [k for k, (rtol, atol, of) in TOL.items()
           if not np.isclose(_get(got, k), _get(want, k), rtol=rtol,
                             atol=atol * scale[of])]
    if tuple(got["violations"]) != tuple(want["violations"]):
        bad.append("violations")
    return bad


def _study(workloads, **kw):
    kw.setdefault("fleets", [N_CHIPS])
    kw.setdefault("configs", CONFIGS)
    kw.setdefault("seeds", SEEDS)
    kw.setdefault("sample_chips", SAMPLE_CHIPS)
    return core.Study(workloads, specs={"moderate": _spec()},
                      wave_cfg=_cfg(), padding="pad", key=None, **kw)


@pytest.mark.parametrize("stream", [None, 4], ids=["oneshot", "stream4"])
def test_campus_rows_match_float64_reference(stream):
    campus = _campus()
    res = _study({"campus": campus}).run(stream=stream)
    spec = _ref_spec(_spec())
    assert len(res) == len(CONFIGS) * len(SEEDS)
    for rec in res:
        assert rec["workload"] == "campus"
        assert rec["n_samples"] == campus.n_samples(_cfg())
        raw, _, mit = _ref_row(campus, rec["seed"], rec["config"])
        want = ref.record(raw, mit, DT, spec)
        assert _mismatches(rec, want, spec) == [], (rec["config"], rec["seed"])


def test_tolerances_fail_bfloat16_and_a_dropped_tenant():
    """The comparison above is tight enough to refuse a campus summed in
    bfloat16 or one tenant short."""
    import ml_dtypes
    spec = _ref_spec(_spec())
    campus = _campus()
    for config in CONFIGS:
        raw, _, mit = _ref_row(campus, SEEDS[0], config)
        want = ref.record(raw, mit, DT, spec)

        def bf16(x):
            return x.astype(ml_dtypes.bfloat16).astype(np.float64)

        assert _mismatches(ref.record(bf16(raw), bf16(mit), DT, spec), want,
                           spec)
        raw2, _, mit2 = _ref_row(campus, SEEDS[0], config, tenants=[0, 1])
        assert _mismatches(ref.record(raw2, mit2, DT, spec), want, spec)


def _one_tenant_campus_is_the_single_job_row(sample_chips):
    name, p, c, m, _ = TENANTS[1]
    campus = _campus([(name, p, c, m, 1.0)])
    tl = campus.tenants[0].timeline
    for seed in SEEDS:
        one = _study({"campus": campus}, seeds=[seed],
                     sample_chips=sample_chips).run(stream=2)
        job = core.study.run_rows(
            {"campus": tl},
            [(w, n, cf, s) for w, n, cf, s in _study(
                {"campus": tl}, seeds=[seed]).rows()],
            [("moderate", _spec())], wave_cfg=_cfg(), padding="pad",
            stream=2, sample_chips=sample_chips,
            levels={"campus": campus.levels(seed, _cfg())[0]})
        assert one.records == job.records


def test_one_tenant_campus_is_the_single_job_row():
    """A campus of one tenant is a single-job row: bit-identical records
    to the job run alone on the campus's levels, chips and seed."""
    _one_tenant_campus_is_the_single_job_row(SAMPLE_CHIPS)


def test_one_tenant_campus_is_the_single_job_row_at_64_sampled_chips():
    """The same at the campus cell's 64 sampled chips, where the band of
    the jittered aggregation (half-width 8) is narrower than the sample."""
    _one_tenant_campus_is_the_single_job_row(64)


def test_campus_is_the_sum_of_its_tenants_alone():
    """The campus's raw and floor-mitigated aggregates are its tenants'
    single-job rows, each at its phase (levels), chips and jitter seed,
    summed."""
    campus = _campus()
    configs = {"none": None, "floor": (_gpu(), None)}
    res = _study({"campus": campus}, configs=configs,
                 keep_waveforms=True).run(stream=3)
    chips = campus.tenant_chips(N_CHIPS)
    for seed in SEEDS:
        levels = campus.levels(seed, _cfg())
        alone = [_study({t.name: t.timeline}, fleets=[int(n)],
                        configs=configs, seeds=[s],
                        keep_waveforms=True)
                 for t, n, s in zip(campus.tenants, chips,
                                    campus.tenant_seeds(seed))]
        alone = [core.study.run_rows(
            st.workloads, st.rows(), st.specs, wave_cfg=_cfg(),
            padding="pad", sample_chips=SAMPLE_CHIPS, keep_waveforms=True,
            levels={t.name: lv})
            for st, t, lv in zip(alone, campus.tenants, levels)]
        for cname in configs:
            row = next(r["row"] for r in res
                       if (r["seed"], r["config"]) == (seed, cname))
            mine = res.waveforms[row]
            for key in ("dc_raw", "dc_mitigated"):
                total = sum(a.waveforms[next(
                    r["row"] for r in a if r["config"] == cname)][key]
                    for a in alone)
                # the same float32 aggregates, summed in another order
                np.testing.assert_allclose(mine[key], total, rtol=1e-6,
                                           err_msg=f"{cname} {key}")


def test_phases_and_shifts_are_reproducible_from_the_seed():
    campus, cfg = _campus(), _cfg()
    np.testing.assert_array_equal(campus.levels(7, cfg),
                                  campus.levels(7, cfg))
    np.testing.assert_array_equal(campus.shifts(7, cfg, SAMPLE_CHIPS),
                                  campus.shifts(7, cfg, SAMPLE_CHIPS))
    assert not np.array_equal(campus.levels(7, cfg), campus.levels(8, cfg))
    shifts = campus.shifts(7, cfg, SAMPLE_CHIPS)
    # tenant 0 draws what a single job of the seed draws; the others
    # draw shifts of their own
    np.testing.assert_array_equal(shifts[0],
                                  jitter_shifts(cfg, 7, SAMPLE_CHIPS))
    assert not np.array_equal(shifts[1], shifts[0])
    # the draws are the reference's: each tenant's cycle tiled from the
    # phase it draws
    levels, t = campus.levels(7, cfg), np.arange(campus.n_samples(cfg))
    for k, c in enumerate(campus.cycles(cfg)):
        np.testing.assert_array_equal(
            levels[k], c[(t + ref.phase(7, k, len(c))) % len(c)])
        np.testing.assert_array_equal(
            shifts[k], ref.jitter_shifts(0.002, DT, ref.tenant_seed(7, k),
                                         SAMPLE_CHIPS))
    a = _study({"campus": campus}, configs={"floor": (_gpu(), None)}).run()
    b = _study({"campus": campus}, configs={"floor": (_gpu(), None)}).run()
    assert a.records == b.records


def test_campus_rejects_bad_shares():
    tl = core.synthetic_timeline(1.0, 0.3)
    with pytest.raises(ValueError):
        Campus((Tenant("a", tl, 0.5), Tenant("b", tl, 0.4)), 1.0)
    with pytest.raises(ValueError):
        Campus((Tenant("a", tl, 0.5), Tenant("a", tl, 0.5)), 1.0)


def test_study_mixes_campus_and_job_rows():
    """Campus and single-job workloads in one Study: each row as when
    run alone."""
    campus, tl = _campus(), core.synthetic_timeline(2.0, 0.2)
    configs = {"floor": (_gpu(), None)}

    def fields(res, name):
        return [{k: v for k, v in r.items() if k not in ("index", "row")}
                for r in res if r["workload"] == name]

    both = _study({"campus": campus, "job": tl}, configs=configs).run(
        stream=2)
    for name, w in (("campus", campus), ("job", tl)):
        alone = _study({name: w}, configs=configs).run(stream=2)
        assert fields(both, name) == fields(alone, name)


def test_campus_chunks_record_tenant_spans(tmp_path):
    """In a profiler session each chunk's dispatch counts its tenant rows
    and holds one ``repro.engine.tenants`` span."""
    import jax
    from repro import obs
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        _study({"campus": _campus()},
               configs={"floor": (_gpu(), None)}).run(stream=1)
    spans = obs.spans().spans
    obs.clear()
    by_id = {s.span_id: s for s in spans}
    dispatch = [s for s in spans if s.name == "repro.stream.dispatch"]
    assert [s.attrs["tenant_rows"] for s in dispatch] == [3] * len(SEEDS)
    tenants = [s for s in spans if s.name == "repro.engine.tenants"]
    assert [by_id[s.parent_id].name for s in tenants] == \
        ["repro.stream.dispatch"] * len(SEEDS)


@pytest.mark.parametrize("dedup,sample_chips", [
    pytest.param(False, SAMPLE_CHIPS, id="False"),
    pytest.param(True, SAMPLE_CHIPS, id="True"),
    # the campus cell's sample, with a band narrower than it
    pytest.param(False, 64, id="False-64"),
    pytest.param(True, 64, id="True-64")])
def test_simulate_batch_campus_rows_match_float64_reference(dedup,
                                                            sample_chips):
    """The engine's one-call path (``_simulate_vmapped``) and its
    deduplicated split take campus rows as the Study does."""
    campus, spec = _campus(), _spec()
    rows = [(c, s) for c in CONFIGS for s in SEEDS]
    res = core.simulate_batch(
        campus, N_CHIPS, _cfg(),
        device_mitigation=[(CONFIGS[c] or (None, None))[0] for c, _ in rows],
        rack_mitigation=[(CONFIGS[c] or (None, None))[1] for c, _ in rows],
        spec=spec, seeds=[s for _, s in rows], sample_chips=sample_chips,
        dedup=dedup)
    assert res.chip_raw.shape == (len(rows), 3, campus.n_samples(_cfg()))
    for i, (c, s) in enumerate(rows):
        raw, _, mit = _ref_row(campus, s, c, sample_chips=sample_chips)
        want = ref.record(raw, mit, DT, _ref_spec(spec))
        report = res.report(i)
        got = {"mean_mw": res.swing["mean_w"][i] / 1e6,
               "swing_mw": res.swing["swing_w"][i] / 1e6,
               "swing_mitigated_mw": res.swing_mitigated["swing_w"][i] / 1e6,
               "energy_overhead": res.energy_overhead[i],
               "metrics": report.metrics, "violations": report.violations}
        assert _mismatches(got, want, _ref_spec(spec)) == [], (c, s)

"""Host spans (``repro.obs``): recorded only inside a profiler session,
at the sweep executor's and the control tick's layer boundaries, on the
profiler's own trace, with the compiles that ran inside them, and
without changing a result."""
import collections
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

import repro.core as core
from repro import control, obs
from repro.core.spec import example_specs

DT = 0.002
CHUNK_SPANS = ("repro.stream.dispatch", "repro.stream.materialize",
               "repro.study.fill_chunk")


def _study(seeds=(0, 1)):
    """Four rows, so ``stream=2`` runs two chunks."""
    cfg = core.WaveformConfig(dt=DT, steps=3, jitter_s=0.002)
    tl = core.synthetic_timeline(period_s=1.0, comm_frac=0.3)
    gpu = {f"mpf{int(m * 100)}": (core.GpuPowerSmoothing(
        mpf_frac=m, ramp_up_w_per_s=2000, ramp_down_w_per_s=2000,
        stop_delay_s=1.0), None) for m in (0.7, 0.8)}
    dc = core.aggregate(core.chip_waveform(tl, cfg), 256, cfg)
    spec = example_specs(job_mw=dc.mean() / 1e6)["moderate"]
    return core.Study({"a": tl}, fleets=[256], configs=gpu,
                      specs={"moderate": spec}, seeds=list(seeds),
                      wave_cfg=cfg)


def _traced(logdir, fn):
    """``fn()`` inside a profiler session; its result and the spans."""
    obs.clear()
    with jax.profiler.trace(str(logdir)):
        out = fn()
    snap = obs.spans()
    obs.clear()
    return out, snap


def _host_names(logdir):
    """Event names on the host plane's python line of the session's
    ``.xplane.pb``."""
    path, = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return {e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines if line.name.startswith("python")
            for e in line.events}


@pytest.fixture(scope="module")
def study_runs(tmp_path_factory):
    study = _study()
    obs.clear()
    plain = study.run(stream=2)
    recorded_outside = obs.spans()
    logdir = tmp_path_factory.mktemp("study_trace")
    traced, snap = _traced(logdir, lambda: study.run(stream=2))
    return {"plain": plain, "outside": recorded_outside, "traced": traced,
            "snap": snap, "logdir": logdir}


TICKS = 48          # the whole 24 s ramp at 0.5 s ticks


def _loop():
    spec = example_specs(job_mw=500.0)["moderate"]
    w = control.synthesize_ramp(duration_s=24.0, ramp_start_s=4.0,
                                ramp_end_s=16.0, dt=DT)
    return control.watch_trace(w, DT, spec=spec, n_chips=512,
                               max_ticks=TICKS)


@pytest.fixture(scope="module")
def loop_runs(tmp_path_factory):
    plain = _loop()
    traced, snap = _traced(tmp_path_factory.mktemp("loop_trace"), _loop)
    return {"plain": plain, "traced": traced, "snap": snap}


def test_nothing_recorded_outside_a_profiler_session(study_runs):
    assert study_runs["outside"] == obs.Snapshot((), 0)


def test_two_chunk_study_span_tree(study_runs):
    spans = study_runs["snap"].spans
    assert study_runs["snap"].dropped == 0
    count = collections.Counter(s.name for s in spans)
    assert count["repro.study.run"] == 1
    for name in CHUNK_SPANS:
        assert count[name] == 2, name
    assert len(spans) / 2 <= 12
    run, = [s for s in spans if s.name == "repro.study.run"]
    assert run.parent_id is None and run.attrs == {"rows": 4, "chunk": 2}
    by_id = {s.span_id: s for s in spans}
    assert all(s.trace_id == run.trace_id for s in spans)
    assert all(s.parent_id in by_id for s in spans if s is not run)
    for s in spans:
        if s.name in CHUNK_SPANS + ("repro.stream.prepare",):
            assert s.parent_id == run.span_id, s.name
        if s.name.startswith("repro.engine."):
            assert by_id[s.parent_id].name == "repro.stream.dispatch"
    # a child lies inside its parent on the clock
    for s in spans:
        if s.parent_id is not None:
            p = by_id[s.parent_id]
            assert p.start <= s.start <= s.end <= p.end
    lo = sorted(s.attrs["lo"] for s in spans
                if s.name == "repro.stream.dispatch")
    assert lo == [0, 2]


def test_enqueue_span_names_the_aggregation(study_runs):
    """2 ms jitter at 2 ms samples over 64 sampled chips, as the sweep
    cells have it: every chunk sums a band of half-width 8."""
    attrs = [s.attrs for s in study_runs["snap"].spans
             if s.name == "repro.engine.enqueue"]
    assert attrs == [{"aggregate": "band", "reach": 8}] * 2


def test_new_seed_block_compiles_nothing(study_runs):
    """The band's bucket holds for a new block of seeds at a sigma of one
    sample, so the next Study reuses every executable."""
    from repro.core import engine
    fns = (engine._synth_vmapped, engine._mitigate_vmapped)
    before = [f._cache_size() for f in fns]
    _study(seeds=(1_000_000, 1_000_001)).run(stream=2)
    assert [f._cache_size() for f in fns] == before


def test_spans_are_on_the_profilers_host_line(study_runs):
    names = _host_names(study_runs["logdir"])
    want = {s.name for s in study_runs["snap"].spans}
    assert "repro.stream.pull" in want
    assert want <= names


def test_stack_mits_spans_for_float_and_array_rows(study_runs, tmp_path):
    """One ``repro.engine.stack_mits`` span a stage and chunk; rows given
    as ``jax.Array`` leaves give the waveform Python floats give."""
    stacks = [s.attrs for s in study_runs["snap"].spans
              if s.name == "repro.engine.stack_mits"]
    assert sorted(a["stage"] for a in stacks) == ["device", "device",
                                                  "rack", "rack"]

    cfg = core.WaveformConfig(dt=DT, steps=2)
    tl = core.synthetic_timeline(period_s=1.0, comm_frac=0.3)
    gpus = [core.GpuPowerSmoothing(mpf_frac=m, ramp_up_w_per_s=2000,
                                   ramp_down_w_per_s=2000, stop_delay_s=1.0)
            for m in (0.6, 0.8)]
    on_device = [jax.tree.map(jax.numpy.asarray, g) for g in gpus]

    def both():
        return [core.simulate_batch(tl, 64, cfg, device_mitigation=rows,
                                    spectra=False).dc_mitigated
                for rows in (gpus, on_device)]

    (host, device), snap = _traced(tmp_path, both)
    np.testing.assert_array_equal(host, device)
    stages = [s.attrs["stage"] for s in snap.spans
              if s.name == "repro.engine.stack_mits"]
    assert stages == ["device", "rack"] * 2


def test_compiles_counted_in_the_span_that_ran_them(tmp_path):
    c = float(np.random.default_rng().random())   # a program never seen
    f = jax.jit(lambda x: x * c + 1.0)
    x = np.arange(4.0, dtype=np.float32)

    def calls():
        with obs.span("repro.test.outer"):
            with obs.span("repro.test.first"):
                f(x).block_until_ready()
            with obs.span("repro.test.second"):
                f(x).block_until_ready()

    _, snap = _traced(tmp_path, calls)
    by_name = {s.name: s for s in snap.spans}
    first, second = by_name["repro.test.first"], by_name["repro.test.second"]
    assert first.compiles == first.own_compiles == 1
    assert first.compile_s > 0
    assert second.compiles == 0
    outer = by_name["repro.test.outer"]
    assert outer.compiles == 1 and outer.own_compiles == 0


def test_control_loop_spans_and_dispatch_latencies(loop_runs):
    log, spans = loop_runs["traced"], loop_runs["snap"].spans
    by_id = {s.span_id: s for s in spans}
    ticks = [s for s in spans if s.name == "repro.control.tick"]
    assert len(ticks) == TICKS == len(log.series)
    assert [t.attrs["tick"] for t in ticks] == list(range(TICKS))
    assert len({t.trace_id for t in ticks}) == 1
    assert all(t.parent_id is None for t in ticks)
    assert all(s.trace_id == ticks[0].trace_id for s in spans)
    dispatches = [r for r in log.records if r.action.startswith("dispatch")]
    assert dispatches and not [r for r in dispatches
                               if r.action.startswith("dispatch_failed")]
    decided = {by_id[s.parent_id].attrs["tick"] for s in spans
               if s.name == "repro.control.dispatch"}
    assert {r.tick for r in dispatches} <= decided
    assert all(by_id[s.parent_id].name == "repro.control.tick"
               for s in spans if s.name == "repro.control.dispatch")
    builds = [s for s in spans
              if s.name == "repro.ladder.build" and not s.attrs["cached"]]
    assert all(by_id[s.parent_id].name == "repro.control.dispatch"
               for s in builds)
    assert [s.duration_s for s in builds] == [r.latency_s
                                              for r in dispatches]


def test_results_identical_with_recording_on_and_off(study_runs, loop_runs):
    assert study_runs["traced"].records == study_runs["plain"].records

    def answers(log):
        recs = [dict(dataclasses.asdict(r), latency_s=None)
                for r in log.records]
        return recs, log.series

    on, off = answers(loop_runs["traced"]), answers(loop_runs["plain"])
    assert on[0] == off[0]
    assert len(on[1]) == len(off[1])
    for a, b in zip(on[1], off[1]):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_span_that_raises_records_its_exception(tmp_path):
    def boom():
        with obs.span("repro.test.outer"):
            with obs.span("repro.test.inner"):
                raise KeyError("x")

    def run():
        with pytest.raises(KeyError):
            boom()

    _, snap = _traced(tmp_path, run)
    assert [(s.name, s.error) for s in snap.spans] == [
        ("repro.test.inner", "KeyError"), ("repro.test.outer", "KeyError")]
    assert obs._stack() == []


def test_buffer_is_bounded(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "MAX_SPANS", 2)

    def three():
        for _ in range(3):
            with obs.span("repro.test.one"):
                pass

    _, snap = _traced(tmp_path, three)
    assert len(snap.spans) == 2 and snap.dropped == 1


def test_disabled_span_still_times_its_body():
    with obs.span("repro.test.off") as sp:
        pass
    assert sp.duration_s >= 0
    assert obs.spans().spans == ()

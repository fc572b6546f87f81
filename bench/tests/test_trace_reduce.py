"""The reduction from a device trace to busy, idle and program times."""
import gzip
import json
import os

import pytest

import trace_reduce as tr
from trace_reduce import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    ops = [Event("fusion.1", 0.0, 1.0, "_synth_vmapped"),
           Event("fusion.2", 0.5, 2.0, "_mitigate_vmapped"),
           Event("custom-call.3", 3.0, 4.0, "_mitigate_vmapped"),
           Event("fusion.9", 5.5, 6.0, "_mitigate_vmapped")]
    mods = [Event("jit__synth_vmapped(7)", 0.0, 1.0),
            Event("jit__mitigate_vmapped(8)", 0.5, 4.0),
            Event("jit__mitigate_vmapped(8)", 5.5, 6.0)]
    host = [Event("bench.window", 0.0, 5.0),
            Event("bench.study", 0.0, 4.5),
            Event("PjitFunction(_analyze_vmapped)", 2.2, 2.9),
            Event("bench.study", 4.5, 5.0)]
    return Trace(devices={"/device:TPU:0": {"modules": mods, "ops": ops}},
                 host=host)


def test_busy_idle_programs_and_gaps_by_hand():
    red = tr.reduce(_trace())
    assert red.window_s == 5.0
    # ops (0, 1) and (0.5, 2) merge to (0, 2); (3, 4); (5.5, 6) is outside
    assert red.busy_s == pytest.approx(3.0)
    assert red.idle_share() == pytest.approx(0.4)
    assert red.program_s == {"_synth_vmapped": 1.0,
                             "_mitigate_vmapped": pytest.approx(3.5)}
    assert red.ops_in("_mitigate_vmapped", "custom-call") == 1.0
    assert red.gaps == [("bench.study > PjitFunction(_analyze_vmapped)",
                         1.0), ("bench.study", 1.0)]
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["_mitigate_vmapped/fusion.2", 1.5]


def test_window_defaults_to_the_harness_span():
    t = _trace()
    t.host = [e for e in t.host if e.name != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(t)
    assert tr.reduce(t, window=(0.0, 2.0)).busy_s == 2.0


def test_program_names():
    assert tr.program_name("jit__mitigate_vmapped(12)") == \
        "_mitigate_vmapped"
    assert tr.program_name("jit_trace_mean") == "trace_mean"


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e (a 4-row backstop Study, its window
    annotated ``window``); the expected numbers were read off the raw
    events independently: busy on a 10 ns grid, programs by summing the
    module events inside the window."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "small_tpu_trace.xplane.pb.gz")) as fh:
        trace = tr.from_profile(ProfileData.from_serialized_xspace(fh.read()))
    with open(os.path.join(DATA, "small_tpu_trace.expected.json")) as fh:
        exp = json.load(fh)
    assert list(trace.devices) == ["/device:TPU:0"]
    span = next(e for e in trace.host if e.name == exp["window_span"])
    red = tr.reduce(trace, window=(span.start, span.end))
    assert red.window_s == pytest.approx(exp["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(exp["busy_s"],
                                       abs=exp["busy_resolution_s"] * 50)
    assert red.program_s.keys() == exp["program_s"].keys()
    for prog, s in exp["program_s"].items():
        assert red.program_s[prog] == pytest.approx(s, rel=1e-6)
    # every operation inside the window ran inside some program
    ops = [e for e in trace.devices["/device:TPU:0"]["ops"]
           if span.start <= e.start < span.end]
    assert ops and all(e.program for e in ops)
    assert red.ops_in("_mitigate_vmapped",
                      r"^vmap_jit__sliding_monitor_full__") > 0


def test_op_names():
    assert tr.op_name("%fusion.3 = f32[512,17988]{1,0:T(8,128)} fusion(x)") \
        == "fusion.3 f32[512,17988]"
    assert tr.op_name("%while.7 = (u32[]{:T(128)}, f32[2]) while(x)") == \
        "while.7 tuple"
    assert tr.op_name("copy-start.1") == "copy-start.1"


def test_metric_readers_on_the_recorded_trace():
    """Every sweep reader, on the recorded backstop Study: 4 rows of
    1,000 samples (one workload at 2 iterations of 1 s), 4 bins."""
    import costs
    import harness
    import run
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "small_tpu_trace.xplane.pb.gz")) as fh:
        trace = tr.from_profile(ProfileData.from_serialized_xspace(fh.read()))
    with open(os.path.join(DATA, "small_tpu_trace.expected.json")) as fh:
        exp = json.load(fh)
    span = next(e for e in trace.host if e.name == exp["window_span"])
    red = tr.reduce(trace, window=(span.start, span.end))
    peak = costs.peaks("TPU v5 lite")
    ctx = run.Context(red, {"rows_dispatched": 4,
                            "rows_by_length": {"1000": 4},
                            "monitor": {"bins": 4}}, peak)
    bench = harness.benchmark()
    got = {m["name"]: harness.load_module(
        os.path.join(harness.BENCH, "metrics", f"{m['name']}.py"),
        "reader").read(ctx) for m in bench["per_layer"]}
    for name, prog in (("synth_us_per_scenario", "_synth_vmapped"),
                       ("mitigate_us_per_scenario", "_mitigate_vmapped"),
                       ("analyze_us_per_scenario", "_analyze_vmapped")):
        assert got[name] == pytest.approx(1e6 * exp["program_s"][prog] / 4)
    assert got["device_idle_share.sweep"] == pytest.approx(
        100 * (1 - exp["busy_s"] / exp["window_s"]), abs=5e-3)
    kernel_s = red.ops_in("_mitigate_vmapped",
                          r"^vmap_jit__sliding_monitor_full__")
    share = got["monitor_roofline_share.sweep"]
    assert 0 < share < 100
    assert share == pytest.approx(100 * 4000 * 9 / 819e9 / kernel_s)
    assert ctx.notes["monitor_roofline_share.sweep"]["bound"] == "hbm"
    # the control readers find nothing to read in a sweep trace
    assert got["detector_us_per_tick"] is None
    assert got["dispatch_ms_p50"] is None

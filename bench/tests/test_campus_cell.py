"""The campus sweep cell: its configuration's fleet arithmetic, its check
against the plain reference (a sound run passes, the bfloat16 control
fails, a run that drops a tenant comes out not correct), and the readers
of its three per-layer metrics on hand-made spans.  Small sizes, on the
CPU."""
import json
import os

import pytest

import calibrate
import harness
import run
from conftest import small_files
from ref import campus as ref_campus
from repro.obs import SpanRecord

CELL = "sweep.campus4.mpf_battery"


def small():
    """The cell cut to a size a test holds: a 4 s window, 8 sampled chips,
    two points per grid axis, two seeds a Study and 4-row chunks."""
    files = small_files(CELL)
    files["config"]["waveform"].pop("steps")
    files["config"]["waveform"]["window_s"] = 4.0
    files["config"]["sample_chips"] = 8
    files["cell"]["traffic"]["stream"] = 4
    return files


def test_fleet_arithmetic_gives_100_mw():
    config = harness.cell_files(CELL)["config"]
    means = ref_campus.per_chip_means(config, 0, phases=[0] * 4)
    draw = sum(c * m for c, m in zip(ref_campus.tenant_chips(config), means))
    assert draw == pytest.approx(100e6, rel=1e-4)
    assert sum(ref_campus.tenant_chips(config)) == config["n_chips"]
    assert ref_campus.n_samples(config) == 30_000


@pytest.mark.usefixtures("on_cpu")
def test_program_passes_and_control_fails():
    rows = []
    calibrate.readings(small(), [5], 1, 0.5,
                       emit=lambda line: rows.append(json.loads(line)))
    limits = small()["cell"]["check"]
    prog, ctrl = rows[0]["program"], rows[0]["control"]
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl


@pytest.mark.usefixtures("on_cpu")
def test_dropped_tenant_is_not_correct(monkeypatch):
    from repro.core.campus import Campus
    orig = Campus.tenant_chips

    def without_last(self, n_chips):
        chips = orig(self, n_chips)
        chips[-1] = 0.0
        return chips

    monkeypatch.setattr(Campus, "tenant_chips", without_last)
    out = run.run_cell(small(), 9, 0.5, False, log=lambda *_: None)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["synth_dev"]["value"] > out["checks"]["synth_dev"][
        "limit"]


def test_cell_files_are_found_by_name():
    files = harness.cell_files(CELL)
    assert files["cell"]["name"] == CELL
    assert files["config"]["reduced"] == []
    assert harness.driver(files["cell"]).Session


def _reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


def _span(sid, name, start, end, parent=None, **attrs):
    return SpanRecord(name, start, end, sid, parent, 1, attrs)


def _campus_spans(tenant_rows=(512, 488)):
    """Two chunks of a campus Study, each with its tenants span (0.02 s,
    of which 0.005 s in a child) inside the dispatch."""
    spans = [_span(1, "repro.study.run", 0.0, 10.0)]
    for c, rows in enumerate(tenant_rows):
        base, t0 = 10 * (c + 1), 2.0 + 4 * c
        attrs = {} if rows is None else {"tenant_rows": rows}
        spans += [
            _span(base, "repro.stream.dispatch", t0, t0 + 1.0, 1,
                  lo=128 * c, rows=128, **attrs),
            _span(base + 1, "repro.engine.tenants", t0, t0 + 0.02, base),
            _span(base + 2, "repro.stream.pull", t0 + 0.01, t0 + 0.015,
                  base + 1),
        ]
    return spans


@pytest.mark.parametrize("name", ["synth_us_per_tenant_row.campus",
                                  "mitigate_us_per_tenant_row.campus"])
def test_device_time_per_tenant_row(name):
    mod = _reader(name)
    assert mod.reduce(2.0, _campus_spans()) == pytest.approx(1e6 * 2.0 / 1000)
    # a program whose dispatch spans carry no tenant rows, or no device
    # time, reads nothing
    assert mod.reduce(2.0, _campus_spans((None, None))) is None
    assert mod.reduce(0.0, _campus_spans()) is None
    assert mod.reduce(2.0, []) is None


def test_tenant_inputs_self_time_per_chunk():
    mod = _reader("tenant_inputs_ms_per_chunk.campus")
    assert mod.reduce(_campus_spans()) == pytest.approx(1e3 * 0.015)
    # without the tenants span (a program before the tenant axis)
    spans = [s for s in _campus_spans() if s.name != "repro.engine.tenants"]
    assert mod.reduce(spans) is None
    assert mod.reduce([]) is None


@pytest.mark.parametrize("name", ["synth_us_per_tenant_row.campus",
                                  "mitigate_us_per_tenant_row.campus",
                                  "tenant_inputs_ms_per_chunk.campus"])
def test_no_spans_read_nothing(name):
    from repro import obs
    obs.clear()

    class Ctx:
        def program_s(self, program):
            return 1.0

    assert _reader(name).read(Ctx()) is None

"""Each cell's check against its plain reference: sound runs pass, the
bfloat16 control fails, and a run whose timed path is broken
underneath comes out not correct.  Small sizes, on the CPU."""
import dataclasses

import numpy as np
import pytest

import calibrate
import harness
import run
from conftest import small_files

pytestmark = pytest.mark.usefixtures("on_cpu")

CELLS = ("sweep.mpf_battery", "sweep.backstop", "control.ramp9hz")
SECONDS = {"sweep.mpf_battery": 0.5, "sweep.backstop": 0.5,
           "control.ramp9hz": 15.0}


@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_and_control_fails(cell):
    rows = []
    calibrate.readings(small_files(cell), [5], 1, SECONDS[cell],
                       emit=lambda line: rows.append(__import__(
                           "json").loads(line)))
    limits = small_files(cell)["cell"]["check"]
    prog, ctrl = rows[0]["program"], rows[0]["control"]
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl


def _wrap(monkeypatch, module, name, post):
    orig = getattr(module, name)

    def wrapped(*args, **kwargs):
        return post(orig(*args, **kwargs), *args)

    monkeypatch.setattr(module, name, wrapped)


def _synth_altered(monkeypatch):
    from repro.core import engine
    _wrap(monkeypatch, engine, "_synth_vmapped",
          lambda out, *a: (out[0], out[1] * 1.01))


def _mitigation_unchanged(monkeypatch):
    from repro.core import engine

    def post(res, *a):
        res = dict(res)
        res["dc_mitigated"] = res["dc_raw"]
        res["swing_mitigated"] = res["swing"]
        res["energy_overhead"] = res["energy_overhead"] * 0.0
        return res

    _wrap(monkeypatch, engine, "_mitigate_vmapped", post)


def _verdict_altered(monkeypatch):
    from repro.core import engine

    def post(res, *a):
        if "spec_ok" in res:
            res = dict(res, spec_ok=~res["spec_ok"])
        return res

    _wrap(monkeypatch, engine, "_analyze_vmapped", post)


def _amps_altered(monkeypatch):
    from repro.control import detector

    def post(frame, *a):
        return dataclasses.replace(frame, amps=frame.amps * np.float32(1.01))

    _wrap(monkeypatch, detector.OnlineGoertzelDetector, "step", post)


def _dispatch_dropped(monkeypatch):
    from repro.control import stream
    monkeypatch.setattr(stream.ReplaySource, "apply_interventions",
                        lambda self, ivs: setattr(self, "active", list(ivs)))


def _level_altered(monkeypatch):
    from repro.control import controller

    def post(decision, *a):
        if decision.tick % 40 == 39:
            decision.target_level = min(decision.target_level + 1, 3)
        return decision

    _wrap(monkeypatch, controller.GridController, "decide", post)


FAULTS = [("sweep.mpf_battery", _synth_altered),
          ("sweep.mpf_battery", _mitigation_unchanged),
          ("sweep.backstop", _mitigation_unchanged),
          ("sweep.backstop", _verdict_altered),
          ("control.ramp9hz", _amps_altered),
          ("control.ramp9hz", _dispatch_dropped),
          ("control.ramp9hz", _level_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run.run_cell(small_files(cell), 9, SECONDS[cell], False,
                       log=lambda *_: None)
    assert out["correct"] is False, out["checks"]


def test_cell_files_are_found_by_name():
    for cell in CELLS:
        files = harness.cell_files(cell)
        assert files["cell"]["name"] == cell
        assert harness.driver(files["cell"]).Session

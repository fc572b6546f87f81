"""The 4-chip sweep cell against its plain reference: a sound run
passes, the bfloat16 control fails, and a run whose timed path is broken
underneath comes out not correct.  Small sizes, on the CPU (one device:
the cell's plan has one shard here)."""
import json

import pytest

import calibrate
import harness
import run
from conftest import small_files

pytestmark = pytest.mark.usefixtures("on_cpu")

CELL = "sweep.mpf_battery.mesh4"


def small():
    """``small_files``, with the chunks cut to 32 rows."""
    files = small_files(CELL)
    files["cell"]["traffic"]["stream"] = 32
    return files


def test_program_passes_and_control_fails():
    rows = []
    calibrate.readings(small(), [5], 1, 0.5,
                       emit=lambda line: rows.append(json.loads(line)))
    limits = small()["cell"]["check"]
    prog, ctrl = rows[0]["program"], rows[0]["control"]
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl


def test_unchanged_mitigation_is_not_correct(monkeypatch):
    from repro.core import engine
    orig = engine._mitigate_vmapped

    def unchanged(*args, **kwargs):
        res = dict(orig(*args, **kwargs))
        res["dc_mitigated"] = res["dc_raw"]
        res["swing_mitigated"] = res["swing"]
        res["energy_overhead"] = res["energy_overhead"] * 0.0
        return res

    monkeypatch.setattr(engine, "_mitigate_vmapped", unchanged)
    out = run.run_cell(small(), 9, 0.5, False, log=lambda *_: None)
    assert out["correct"] is False, out["checks"]


def test_cell_files_are_found_by_name():
    files = harness.cell_files(CELL)
    assert files["cell"]["name"] == CELL
    assert harness.driver(files["cell"]).Session

"""Shared set-up of the benchmark's own tests: run them on the CPU with an
explicit path (``python -m pytest bench/tests``); the repository's test
run does not collect them."""
import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import harness  # noqa: E402

harness.add_paths()


def small_files(cell: str):
    """The cell's files cut to a size a test holds: sweep cells keep two
    points per grid axis, two seeds per Study, 16-row chunks and
    ``steps`` iterations; control cells run as they are."""
    files = copy.deepcopy(harness.cell_files(cell))
    if "waveform" in files["config"]:
        files["config"]["waveform"]["steps"] = 3
        t = files["cell"]["traffic"]
        t["seeds_per_study"], t["stream"] = 2, 16
        for stage in ("device", "rack"):
            if t[stage] is None:
                continue
            for k, v in list(t[stage]["grid"].items()):
                if isinstance(v, dict):
                    (unit, xs), = v.items()
                    t[stage]["grid"][k] = {unit: xs[:2]}
                else:
                    t[stage]["grid"][k] = v[:2]
    return files


@pytest.fixture(scope="session", autouse=True)
def _cpu_cache(tmp_path_factory):
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(tmp_path_factory.mktemp("jax_cache")))


@pytest.fixture
def on_cpu(monkeypatch):
    """A run on the CPU: the harness's look for a TPU is skipped, and the
    peaks are the TPU v5e's, so that everything after it runs as on the
    chip."""
    import jax
    monkeypatch.setattr(harness, "devices",
                        lambda chips: jax.devices()[:chips])
    tpu = costs.peaks("TPU v5 lite")
    monkeypatch.setattr(costs, "peaks", lambda kind: tpu)

"""The main-path Pallas kernels compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler installed with JAX
compiles for a ``v5e:2x2`` topology that is described, not attached.
That catches what interpret mode cannot — primitives Mosaic does not
lower, block shapes off the (8, 128) tiling, kernels the SPMD
partitioner refuses — at no chip time.  Each test asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers each import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import engine
from repro.core.hardware import DEFAULT_HW
from repro.core.smoothing import backstop
from repro.core.smoothing.backstop import TelemetryBackstop
from repro.core.spec import example_specs
from repro.core.waveform import WaveformConfig, jitter_reach, jitter_shifts
from repro.kernels.goertzel.goertzel import (sliding_goertzel_v2_pallas,
                                             sliding_monitor_pallas)
from repro.parallel.sharding import ScenarioShardPlan

K = 4          # the four GRID_CRITICAL_HZ bins
KP = 8         # K sublane-padded


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A described-chip compile can be written to the persistent cache
    but never read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_args(sharding, S, win):
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=sharding)
    return (f32((S, win)), f32((KP, win)), f32((KP, win)), f32((KP, 2)),
            f32((1, 4)), f32((KP, win)), f32((KP, win)))


@pytest.mark.parametrize("kernel,win,S,block_s", [
    # the offline monitor at the benchmark width: 1e6 samples, win=8000
    (sliding_monitor_pallas, 8000, 128, 8),
    # the online carry calls: one segment per call
    (sliding_monitor_pallas, 2000, 1, 1),
    (sliding_goertzel_v2_pallas, 2000, 1, 1),
])
def test_kernel_compiles_for_v5e(one_chip, kernel, win, S, block_s):
    fn = jax.jit(lambda *a: kernel(*a, k=K, block_s=block_s))
    text = fn.lower(*_kernel_args(one_chip, S, win)).compile().as_text()
    assert "tpu_custom_call" in text


def _backstop_rows(B, n, cfg, sharding, replicated):
    """ShapeDtypeStructs for ``engine._mitigate_vmapped`` over ``B``
    scenario rows of ``n`` samples that all share one synthesized prefix,
    with a ``TelemetryBackstop`` as the rack mitigation, the spec's family
    and the aggregation band ``simulate_batch`` would pass."""
    def sds(a, sh):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    shifts = np.stack([jitter_shifts(cfg, s, 64) for s in range(B)])
    rack = engine.stack_mitigations([TelemetryBackstop()] * B)
    spec = example_specs(job_mw=50.0)["moderate"]
    shared = (sds(np.zeros((1, n), np.float32), replicated),
              sds(np.zeros((1, n), np.float32), replicated))
    rows = (sds(np.zeros(B, np.int32), sharding), sds(shifts, sharding),
            sds(np.full(B, 512.0, np.float32), sharding), None,
            jax.tree.map(lambda a: sds(a, sharding), rack),
            None, None, None, None)
    limits = jax.tree.map(lambda a: sds(a, replicated), spec.limits())
    return (shared + rows + (limits,), spec.family(),
            jitter_reach(shifts, cfg, n))


@pytest.mark.parametrize("chips", [1, 4])
def test_vmapped_backstop_compiles_for_v5e(topo, monkeypatch, chips):
    """The backstop's monitor inside the vmapped engine, 64 scenarios of
    18000 samples: on one chip, and on a 4-chip scenario mesh, where the
    kernel runs inside the engine's ``shard_map`` (the SPMD partitioner
    refuses to split a Mosaic kernel itself)."""
    # the engine picks interpret mode from the attached backend (the CPU
    # here); the described chip needs the compiled kernel
    monkeypatch.setattr(backstop, "_interpret_default", lambda: False)
    cfg = WaveformConfig(dt=0.001, steps=1, jitter_s=0.001)
    devices = topo.devices[:chips]
    plan = ScenarioShardPlan(Mesh(np.asarray(devices), ("scenario",)))
    args, family, reach = _backstop_rows(
        64, 18000, cfg, NamedSharding(plan.mesh, PartitionSpec("scenario")),
        NamedSharding(plan.mesh, PartitionSpec()))
    fn = jax.jit(engine._mitigate_vmapped.__wrapped__,
                 static_argnames=("cfg", "hw", "spec", "spectra",
                                  "chip_outputs", "plan", "reach"))
    compiled = fn.lower(*args, cfg=cfg, hw=DEFAULT_HW, spec=family,
                        spectra=False, chip_outputs=False,
                        plan=plan, reach=reach).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jittered_aggregation_compiles_without_an_index_matrix(one_chip):
    """Waveform synthesis for 64 rows of 17,988 samples and 64 sampled
    chips at a jitter of one sample, as the sweep cells run it: the
    banded sum needs a few row-sized buffers, where a gather of the 64
    shifted replicas would hold a [64, 64, n] index matrix (295 MB)."""
    B, n, S = 64, 17988, 64
    cfg = WaveformConfig(dt=0.002, steps=1, jitter_s=0.002)
    shifts = np.stack([jitter_shifts(cfg, s, S) for s in range(B)])
    reach = jitter_reach(shifts, cfg, n)
    assert reach == 8
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn = jax.jit(engine._synth_vmapped.__wrapped__,
                 static_argnames=("cfg", "hw", "reach"))
    compiled = fn.lower(sds((B, n), jnp.float32), sds((B, S), jnp.int32),
                        sds((B,), jnp.float32), None, cfg=cfg, hw=DEFAULT_HW,
                        reach=reach).compile()
    assert " gather(" not in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 16 * B * n * 4, temp

"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape x mesh) cell:
  compute term    = HLO_FLOPs / (chips * 197e12)            [s/step]
  memory term     = HLO_bytes / (chips * 819e9)             [s/step]
  collective term = per-chip collective bytes / 50e9        [s/step]
(FLOPs/bytes are the jaxpr-exact global counts — launch/hlo_analysis.py —
divided per chip; collective bytes come from the partitioned HLO with
while-loop trip multipliers, already per chip.)

Also: MODEL_FLOPS (6*N*D train / 2*N_active*tokens inference), the
useful-compute ratio MODEL_FLOPS/HLO_FLOPS, the dominant term, a roofline
fraction (useful compute time / dominant term = the score), and a
suggestion for the dominant bottleneck. Emits CSV + artifacts/roofline.json.

``--kernels`` is the hot-path cost regression gate (the CI mode): it
re-derives the jaxpr-exact FLOPs/bytes of the serve and backstop hot
kernels at fixed reference shapes, asserts each stays inside its
recorded budget (these counts are deterministic, so a budget breach
means someone made the kernel do more work), and merges the counts into
``BENCH_kernels.json`` under ``"per_kernel"``.  It also derives the
measured-bandwidth section (``"measured_bandwidth"``): jaxpr-exact bytes
moved by the fused v2 monitor vs the two-pass jnp path at the 1e6-sample
benchmark shape, divided by the wall times ``kernels_bench`` recorded —
so the before/after roofline shows the fused speedup is bytes-moved,
not just wall-clock.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict

from benchmarks.common import emit, load_cells
from repro.configs import get_config

PEAK = 197e12
HBM = 819e9
LINK = 50e9

KERNELS_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_kernels.json")

# jaxpr-exact costs at the reference shapes below, with ~20% headroom;
# deterministic, so a breach = the hot path genuinely got heavier.
# sliding_goertzel moved to the lane-major v2 Pallas kernel: per-cell
# body FLOPs are counted once per grid step (hence the higher FLOPs
# budget vs the old jnp-cumsum path), but HBM traffic collapsed from
# 32.1e6 to 3.6e6 bytes — the kernel streams operand blocks and keeps
# the [S, win, K] intermediates in VMEM.  monitor_fused adds the
# in-kernel worst-bin/classify reduction + blocked escalation scan on
# top and still never materializes per-sample amplitudes.  Both count
# the in-kernel log-step lane scan (Mosaic has no cumsum): ceil(log2(win))
# masked adds per sample and bin in place of one, 4.4x the FLOPs of the
# cumsum form at the reference shape, bytes unchanged.
KERNEL_BUDGETS = {
    "sliding_goertzel": {"max_flops": 69.0e6, "max_bytes": 4.3e6},
    "monitor_fused": {"max_flops": 80.0e6, "max_bytes": 21.9e6},
    "goertzel_fingerprint": {"max_flops": 0.73e6, "max_bytes": 1.8e6},
    "warmstart_mlp": {"max_flops": 0.78e6, "max_bytes": 0.28e6},
    "ballast": {"max_flops": 10.4e9, "max_bytes": 103.2e6},
}

# pinned jaxpr primitive histograms (repro.analysis Tier-2 registry,
# inner scan/cond/pallas bodies included).  FLOPs/bytes budgets have
# headroom, so a fusion regression that swaps cheap primitives for a
# materializing pattern can hide under them — the exact primitive mix
# cannot drift silently: any change fails with a named per-primitive
# diff.  The ballast burner has no Tier-2 entry (its geometry is gated
# by the Tier-3 kernel checks); it stays FLOPs/bytes-only here.
KERNEL_PRIMITIVES = {
    "sliding_goertzel": ("kernels.sliding_bin_power", {
        "add": 109, "broadcast_in_dim": 126, "concatenate": 10, "cond": 1,
        "convert_element_type": 123, "div": 2, "eq": 1, "ge": 88, "get": 30,
        "iota": 10, "jit": 122, "min": 1, "mul": 42, "ne": 32, "neg": 4,
        "pallas_call": 1, "program_id": 1, "reduce_sum": 1, "reshape": 2,
        "roll": 88, "select_n": 120, "slice": 27, "sqrt": 4, "squeeze": 2,
        "sub": 14, "swap": 16}),
    "monitor_fused": ("kernels.monitor_fused", {
        "add": 125, "and": 17, "broadcast_in_dim": 136, "concatenate": 11,
        "cond": 2, "convert_element_type": 142, "div": 4, "eq": 8, "ge": 94,
        "get": 33, "gt": 7, "iota": 14, "jit": 152, "le": 2, "lt": 5,
        "max": 5, "min": 5, "mul": 46, "ne": 37, "neg": 4, "not": 4,
        "pallas_call": 1, "program_id": 1, "reduce_and": 2, "reduce_max": 6,
        "reduce_sum": 2, "rem": 2, "reshape": 6, "roll": 88, "scan": 2,
        "select_n": 147, "sign": 4, "slice": 32, "sqrt": 4, "squeeze": 4,
        "sub": 29, "swap": 19}),
    "goertzel_fingerprint": ("serve.fingerprint", {
        "add": 1, "div": 2, "dot_general": 2, "mul": 3, "reduce_sum": 1,
        "sqrt": 1, "sub": 1}),
    "warmstart_mlp": ("serve.warmstart_mlp", {
        "add": 3, "broadcast_in_dim": 1, "concatenate": 1, "dot_general": 4,
        "integer_pow": 1, "mul": 4, "tanh": 1}),
}

SUGGEST = {
    "compute": ("cut non-useful FLOPs: triangular-chunk attention schedule, "
                "remat policy 'dots' instead of 'full'"),
    "memory": ("raise arithmetic intensity: larger microbatch per pass, fuse "
               "loss chunks, widen attention KV chunks"),
    "collective": ("reshard: sequence-parallel activations to turn TP "
                   "all-reduces into reduce-scatter+all-gather; overlap "
                   "grad reduce with ballast/compute; int8 grad compression"),
}


def model_flops(cell: Dict) -> float:
    cfg = get_config(cell["arch"])
    n_act = cell["active_params"]
    if cell["kind"] == "train":
        tokens = 4096 * 256
        return 6.0 * n_act * tokens
    if cell["kind"] == "prefill":
        tokens = 32768 * 32
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    bsz = {"decode_32k": 128, "long_500k": 1}[cell["shape"]]
    return 2.0 * n_act * bsz


def analyze(cell: Dict) -> Dict:
    chips = cell["n_chips"]
    t_comp = cell["exact"]["flops"] / chips / PEAK
    t_mem = cell["exact"]["bytes"] / chips / HBM
    coll = sum(cell.get("collectives", {}).values())
    t_coll = coll / LINK
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mf = model_flops(cell)
    useful_t = mf / chips / PEAK
    frac = useful_t / max(terms[dom], 1e-30)
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "kind": cell["kind"], "chips": chips,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dom,
        "model_flops": mf,
        "useful_ratio": mf / cell["exact"]["flops"],
        "roofline_fraction": frac,
        "hbm_state_gb": cell["memory"]["state_bytes_per_device"] / 1e9,
        "suggestion": SUGGEST[dom],
    }


def kernel_costs() -> Dict[str, Dict[str, float]]:
    """jaxpr-exact FLOPs/bytes of the serve + backstop hot kernels at
    fixed reference shapes: the backstop's lane-major v2 sliding
    Goertzel kernel and its fused worst-bin/escalation monitor
    (1e5-sample trace, 2000-sample window, 4 bins), the serve feature
    extractor's spectral fingerprint (2e4 samples, 7 grid-critical
    bins), the warm-start MLP (batch 64), and the ballast burn tile
    (1024x256x256, 64 iterations)."""
    import jax
    import jax.numpy as jnp

    from repro.core.spectrum import GRID_CRITICAL_HZ, goertzel_bin_amplitudes_jax
    from repro.kernels.ballast.ref import ballast_ref
    from repro.kernels.goertzel.ops import (sliding_bin_power,
                                            sliding_monitor_fused)
    from repro.launch.hlo_analysis import jaxpr_costs
    from repro.serve.warmstart import (N_FEATURES, init_warmstart,
                                       warmstart_forward)

    x = jnp.zeros(100_000, jnp.float32)
    xf = jnp.zeros(20_000, jnp.float32)
    params = init_warmstart(jax.random.PRNGKey(0))
    xb = jnp.zeros((64, N_FEATURES), jnp.float32)
    a = jnp.zeros((1024, 256), jnp.float32)
    b = jnp.zeros((256, 256), jnp.float32)
    costs = {
        "sliding_goertzel": jaxpr_costs(
            lambda x: sliding_bin_power(x, 0.001, (0.5, 1.0, 2.0, 9.0),
                                        win=2000, interpret=True), x),
        "monitor_fused": jaxpr_costs(
            lambda x: sliding_monitor_fused(
                x, 0.001, (0.5, 1.0, 2.0, 9.0), win=2000,
                threshold=jnp.float32(1e6), sustain_n=50, cool_n=80,
                interpret=True), x),
        "goertzel_fingerprint": jaxpr_costs(
            lambda x: goertzel_bin_amplitudes_jax(x, 0.002,
                                                  GRID_CRITICAL_HZ), xf),
        "warmstart_mlp": jaxpr_costs(warmstart_forward, params, xb),
        "ballast": jaxpr_costs(lambda a, b: ballast_ref(a, b, 64), a, b),
    }
    for name, c in costs.items():
        c["intensity_flops_per_byte"] = round(c["flops"] / c["bytes"], 3)
    return costs


def check_primitives() -> Dict[str, Dict[str, int]]:
    """Assert the registered hot paths' jaxpr primitive mixes match the
    pinned histograms; a mismatch fails with a named primitive diff."""
    from repro.analysis.jaxpr_checks import primitive_counts, primitive_diff
    from repro.analysis.registry import ENTRY_BY_NAME

    got_all: Dict[str, Dict[str, int]] = {}
    failures = []
    for name, (entry, expected) in KERNEL_PRIMITIVES.items():
        got = dict(primitive_counts(ENTRY_BY_NAME[entry]))
        got_all[name] = dict(sorted(got.items()))
        diff = primitive_diff(expected, got)
        if diff:
            failures.append(f"{name} ({entry}):\n    " + "\n    ".join(diff))
        emit(f"roofline/prims_{name}", 0.0, {
            "primitives": sum(got.values()), "distinct": len(got),
            "drift": len(diff)})
    assert not failures, (
        "hot-path primitive-mix regression (fusion structure changed; "
        "re-pin KERNEL_PRIMITIVES only if the change is intentional):\n  "
        + "\n  ".join(failures))
    return got_all


def measured_bandwidth(merged: Dict) -> Dict:
    """The before/after roofline for the monitor fusion, at the exact
    shape ``kernels_bench`` times (1e6 samples, win=8000, 4 bins): derive
    the jaxpr-exact bytes each monitor arm moves — the fused v2 Pallas
    path (worst/levels straight from VMEM) vs the two-pass jnp path
    (materialize the [n, K] amplitude matrix, then a separate
    amps -> escalation scan) — and divide by the wall times recorded in
    ``BENCH_kernels.json`` to get achieved bandwidth.  Matching achieved
    GB/s with ~2x fewer bytes is the attribution the fused speedup
    claims: less data moved, not a faster pipe."""
    import jax.numpy as jnp

    from benchmarks.kernels_bench import _monitor_two_pass
    from repro.kernels.goertzel.ops import sliding_monitor_fused
    from repro.launch.hlo_analysis import jaxpr_costs

    n, win, freqs = 1_000_000, 8000, (0.5, 1.0, 2.0, 9.0)
    thr, rel = jnp.float32(2e5), jnp.float32(1.5e5)
    sustain_n, cool_n = max(win // 40, 1), max(win // 25, 1)
    x = jnp.zeros(n, jnp.float32)
    fused = jaxpr_costs(
        lambda x: sliding_monitor_fused(
            x, 0.001, freqs, win=win, threshold=thr, release=rel,
            sustain_n=sustain_n, cool_n=cool_n, interpret=True), x)
    two_pass = jaxpr_costs(
        lambda x: _monitor_two_pass(
            x, dt=0.001, freqs=freqs, win=win, threshold=thr, release=rel,
            sustain_n=sustain_n, cool_n=cool_n, interpret=True,
            use_jnp_amps=True), x)
    fm = merged.get("fused_monitor", {})
    out = {
        "shape": {"n_samples": n, "win": win, "bins": len(freqs)},
        "fused_bytes": fused["bytes"],
        "two_pass_jnp_bytes": two_pass["bytes"],
        "bytes_ratio_two_pass_over_fused":
            round(two_pass["bytes"] / fused["bytes"], 2),
    }
    for arm, bts, key in (("fused", fused["bytes"], "pallas_ms"),
                          ("two_pass_jnp", two_pass["bytes"], "jnp_path_ms")):
        ms = fm.get(key)
        if ms:                      # wall times come from the full bench run
            out[f"{arm}_wall_ms"] = ms
            out[f"{arm}_achieved_gb_per_s"] = round(bts / (ms / 1e3) / 1e9, 3)
    emit("roofline/measured_bandwidth", 0.0, {
        "bytes_ratio": out["bytes_ratio_two_pass_over_fused"],
        "fused_gbps": out.get("fused_achieved_gb_per_s", "n/a"),
        "two_pass_gbps": out.get("two_pass_jnp_achieved_gb_per_s", "n/a")})
    return out


def check_kernels() -> None:
    """Derive the hot-kernel costs, gate them against the budgets and the
    pinned primitive mixes (a breach fails CI), merge into
    BENCH_kernels.json."""
    costs = kernel_costs()
    failures = []
    for name, c in costs.items():
        budget = KERNEL_BUDGETS[name]
        if c["flops"] > budget["max_flops"]:
            failures.append(f"{name}: flops {c['flops']:.3g} > budget "
                            f"{budget['max_flops']:.3g}")
        if c["bytes"] > budget["max_bytes"]:
            failures.append(f"{name}: bytes {c['bytes']:.3g} > budget "
                            f"{budget['max_bytes']:.3g}")
        emit(f"roofline/kernel_{name}", 0.0, {
            "flops": f"{c['flops']:.4g}", "bytes": f"{c['bytes']:.4g}",
            "intensity": c["intensity_flops_per_byte"]})
    assert not failures, "hot-path cost regression:\n  " + \
        "\n  ".join(failures)
    prims = check_primitives()

    merged: Dict = {}
    if os.path.exists(KERNELS_OUT):
        with open(KERNELS_OUT) as fh:
            merged = json.load(fh)
    merged["per_kernel"] = costs
    merged["per_kernel_primitives"] = prims
    merged["measured_bandwidth"] = measured_bandwidth(merged)
    with open(KERNELS_OUT, "w") as fh:
        json.dump(merged, fh, indent=2)
        fh.write("\n")
    print(f"kernels OK: {len(costs)} hot paths inside budget, "
          f"{len(prims)} primitive mixes pinned; merged into "
          f"{os.path.abspath(KERNELS_OUT)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernels", action="store_true",
                    help="hot-path FLOPs/bytes regression gate (CI mode); "
                         "skips the dry-run roofline table")
    args = ap.parse_args()
    if args.kernels:
        check_kernels()
        return
    rows = []
    for mesh in ("single", "multi"):
        for key, cell in sorted(load_cells(mesh).items()):
            r = analyze(cell)
            rows.append(r)
            if mesh == "single":  # the roofline table is single-pod only
                emit(f"roofline/{key}", 0.0, {
                    "comp_s": f"{r['t_compute_s']:.4f}",
                    "mem_s": f"{r['t_memory_s']:.4f}",
                    "coll_s": f"{r['t_collective_s']:.4f}",
                    "dom": r["dominant"],
                    "useful": f"{r['useful_ratio']:.3f}",
                    "roofline_frac": f"{r['roofline_fraction']:.3f}"})
    out = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                       "roofline.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    emit("roofline/written", 0.0, {"cells": len(rows), "path": out})


if __name__ == "__main__":
    main()

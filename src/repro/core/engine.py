"""Batched scenario engine: the whole waveform -> mitigation -> spec
pipeline as one jit/vmap-able JAX program.

The paper evaluates every mitigation "on the real waveform from Figure 1"
across a matrix of workloads, fleet sizes and (MPF, battery) configurations.
StratoSim's ``simulate`` runs one scenario at a time; this module runs a
*grid* of scenarios in a single compiled call:

  ``simulate_batch``  vmaps (timeline or campus levels x n_chips x
                      mitigation config x jitter seed x PRNG key)
                      through synthesis, aggregation, mitigation scans,
                      swing/band metrics and utility-spec validation —
                      no host round-trips inside.
  ``sweep``           cartesian product over workloads / fleet sizes /
                      configs / seeds, bucketed by waveform length (each
                      bucket is one compiled call), returning flat records.
  ``stream_batches``  chunked fixed-memory iteration of the scenario
                      axis: per-chunk compiled pipeline + in-jit
                      reduction to metrics (waveforms never leave the
                      device unless asked), donated input buffers,
                      chunk k+1 dispatched while chunk k transfers.
  ``apply_batch``     one waveform through a stack of mitigation configs
                      (the Fig. 6 MPF sweep in one call).
  ``analyze_batch``   frequency reports + spec validation for same-length
                      waveforms (the finalize stage behind ``core.study``).
  ``design_grid``     the batched grid search behind
                      ``smoothing.design_mitigation``.
  ``design_gradient`` jitted gradient descent on (MPF, capacity): Adam via
                      ``lax.scan`` through the smooth-relaxed mitigations
                      (``smooth_tau``) and the spec's hinge loss
                      (``UtilitySpec.loss_jax``), vmapped multi-start,
                      hard re-validation of every candidate.
  ``design``          the one design entry point:
                      method="grid" | "gradient" | "hybrid".

This module is the *compile target*; the declarative public surface is
``repro.core.study`` (``Study``/``StudyResult``), which drives it with
per-scenario PRNG keys, pad-and-mask fusion of mixed-length workloads
(``pad_to``), and optional sharding of the scenario axis across devices.

Only the timeline -> sample-count expansion (``phase_levels``) and the
jitter-shift draw stay in numpy: they fix array shapes.  Everything with a
static shape is traced, so mitigation parameter grids ride through ``vmap``
as stacked pytree leaves (see ``stack_mitigations``).  Mixed
enabled/disabled rows batch too: ``_normalize_mits`` carries disabled rows
as structural placeholders plus an on/off mask, and the pipeline selects
the unmitigated waveform for masked-off rows after the vmapped apply.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.hardware import DEFAULT_HW, Hardware
from repro.core.campus import n_samples, n_tenants, row_inputs
from repro.core.optim import adam_init, adam_update, clip_by_global_norm
from repro.core.phases import IterationTimeline
from repro.parallel.collectives import gather_rows, host_allgather
from repro.parallel.sharding import ScenarioShardPlan, scenario_plan
from repro.core.smoothing.base import (Mitigation, apply_mitigation,
                                       energy_overhead_jax, materialize_aux)
from repro.core.smoothing.battery import RackBattery
from repro.core.smoothing.gpu_floor import GpuPowerSmoothing
from repro.core.spec import SpecReport, UtilitySpec, report_from_arrays
from repro.core.spectrum import critical_band_report_jax
from repro.core.stratosim import SimResult
from repro.core.waveform import (WaveformConfig, aggregate_jax,
                                 chip_waveform_jax, jitter_reach,
                                 phase_levels, swing_stats_jax)


# ---------------------------------------------------------------------------
# config batching
# ---------------------------------------------------------------------------

def stack_mitigations(mitigations: Sequence) -> object:
    """Stack structurally-identical mitigation pytrees into one batched
    pytree (leaves gain a leading config axis) for ``vmap``.

    All entries must be the same class with identical static metadata
    (hardware spec, telemetry config, windows); continuous parameters may
    differ per entry — that is the grid being swept.

    Each leaf is one ``jnp.asarray`` of its rows: Python or NumPy values
    are stacked in NumPy and go to the device in one transfer, where an
    eager array per row and leaf cost ~0.7 ms a scalar on a TPU host.
    ``jax.Array`` or traced rows are stacked on the device.  Either way
    the leaf is a strong float32 with the rows leading.
    """
    mitigations = list(mitigations)
    if not mitigations:
        raise ValueError("empty mitigation list")
    return jax.tree.map(lambda *xs: jnp.asarray(xs, jnp.float32),
                        *mitigations)


def _tile(values, B: int, what: str) -> list:
    values = list(values)
    if len(values) == 1:
        return values * B
    if len(values) != B:
        raise ValueError(f"{what}: got {len(values)} entries, expected 1 or {B}")
    return values


def _normalize_mits(mits, B: int, what: str):
    """None | Mitigation | sequence (None rows allowed) ->
    ``(batched pytree | None, on-mask [B] | None)``.

    Disabled (None) rows batch alongside enabled ones: they ride through
    the vmapped apply as a structural placeholder (a copy of the first
    enabled config — its parameters never reach the output) and the
    returned on-mask selects the *unmitigated* waveform for them
    afterwards.  The mask is None when every row is enabled.  This is the
    generalization of the design-grid gpu_on/bat_on masking: one batch can
    mix baselines and mitigated configs (the Table-I matrix in one call).
    """
    if mits is None:
        return None, None
    if not isinstance(mits, (list, tuple)):
        mits = [mits]
    mits = _tile(mits, B, what)
    enabled = [m for m in mits if m is not None]
    if not enabled:
        return None, None
    if len(enabled) == len(mits):
        return stack_mitigations(mits), None
    placeholder = enabled[0]
    on = jnp.asarray([0.0 if m is None else 1.0 for m in mits], jnp.float32)
    return stack_mitigations([placeholder if m is None else m for m in mits]), on


def _normalize_keys(keys, B: int):
    """None | key | sequence of keys | stacked [B, ...] array -> [B] keys."""
    if keys is None:
        return None
    if isinstance(keys, (list, tuple)):
        rows = list(keys)
    else:
        arr = jnp.asarray(keys)
        rows = [keys] if arr.ndim <= 1 else list(arr)
    rows = _tile(rows, B, "keys")
    return jnp.stack([jnp.asarray(k) for k in rows])


# ---------------------------------------------------------------------------
# the compiled pipeline
# ---------------------------------------------------------------------------

def _mask_helpers(n: int, n_valid):
    """(fill_edge, fill_mean, msum, mask) for pad-and-mask mode; identity
    functions when ``n_valid`` is None (unpadded)."""
    if n_valid is None:
        ident = lambda w: w
        return ident, ident, jnp.sum, None
    mask = jnp.arange(n) < n_valid
    last = jnp.asarray(n_valid, jnp.int32) - 1

    def fill_edge(w):
        return jnp.where(mask, w, w[last])

    def msum(w):
        return jnp.sum(jnp.where(mask, w, 0.0))

    def fill_mean(w):
        return jnp.where(mask, w, msum(w) / n_valid)

    return fill_edge, fill_mean, msum, mask


def _each_tenant(fn, x, *rest):
    """``fn(x, *rest)`` for each tenant of a row.  A campus row's inputs
    lead with its tenant axis (``x`` [K, n]), which ``fn`` is mapped
    over; a single job's row is one tenant (``x`` [n]) and ``fn`` runs on
    it as it is."""
    return fn(x, *rest) if x.ndim == 1 else jax.vmap(fn)(x, *rest)


def _tenant_sum(w):
    """The point of coupling's waveform: per-tenant waveforms [K, n]
    summed, or a single job's [n] as it is."""
    return w if w.ndim == 1 else jnp.sum(w, axis=0)


def _synth_one(levels, shifts, n_chips, n_valid, cfg: WaveformConfig,
               hw: Hardware, reach: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mitigation-independent prefix: levels -> (chip, dc_raw), the
    chip waveform of each tenant and the sum of their jittered
    aggregates.  Depends only on (workload or campus, fleet, seed) — the
    Study layer dedupes it across the config axis.  ``reach`` is
    ``aggregate_jax``'s band half-width (``jitter_reach``)."""
    fill_edge, _, _, _ = _mask_helpers(levels.shape[-1], n_valid)

    def one(lv, sh, nc):
        chip = fill_edge(chip_waveform_jax(lv, cfg.dt, hw,
                                           edp_spikes=cfg.edp_spikes,
                                           include_host=cfg.include_host))
        return chip, aggregate_jax(chip, nc, sh, hw, reach=reach)

    chip, dc = _each_tenant(one, levels, shifts, n_chips)
    return chip, _tenant_sum(dc)


def _mitigate_one(chip, dc_raw, shifts, n_chips, dev, rack, dev_on, rack_on,
                  key, n_valid, limits, cfg: WaveformConfig, hw: Hardware,
                  spec: Optional[UtilitySpec], spectra: bool,
                  chip_outputs: bool, reach: int) -> Dict:
    """Per-config suffix of one scenario inside vmap.

    The device stage runs on each tenant's chip waveform (every tenant
    under the row's parameters, a campus tenant on a key of its own), and
    each tenant's jittered aggregate adds to the point of coupling's
    waveform; the rack stage and everything after it see only the sum.

    ``n_valid`` (traced scalar or None) activates pad-and-mask mode: the
    row's true waveform occupies the first ``n_valid`` samples of a padded
    array.  Masking keeps the valid region *exact* against an unpadded run:
    levels arrive edge-padded, mitigated chip waveforms are re-filled with
    their boundary sample (so the jittered aggregation sees the same
    clip-to-edge semantics as an unpadded call), mean-sensitive rack
    stages see the pad region filled with the valid-region mean, and every
    scalar metric is a masked reduction.  Frequency metrics need the true
    FFT length, so padded calls defer them to ``analyze_batch``.
    """
    n = chip.shape[-1]
    fill_edge, fill_mean, msum, mask = _mask_helpers(n, n_valid)

    k_dev = k_rack = None
    if key is not None:
        k_dev = jax.random.fold_in(key, 0)
        k_rack = jax.random.fold_in(key, 1)
        if chip.ndim == 2:
            k_dev = jax.vmap(lambda t: jax.random.fold_in(k_dev, t))(
                jnp.arange(chip.shape[0]))

    out: Dict = {"dc_raw": dc_raw}
    if chip_outputs:
        out["chip_raw"] = chip
    aux: Dict = {}
    dc = dc_raw
    if dev is not None:
        def floor(c, sh, nc, k):
            c_m, aux_d = apply_mitigation(dev, c, cfg.dt, k)
            c_m = fill_edge(c_m)
            if dev_on is not None:
                c_m = jnp.where(dev_on > 0, c_m, c)
            return c_m, aux_d, aggregate_jax(c_m, nc, sh, hw, reach=reach)

        chip_m, aux["device"], dc = _each_tenant(floor, chip, shifts,
                                                 n_chips, k_dev)
        if chip_outputs:
            out["chip_mitigated"] = chip_m
        dc = _tenant_sum(dc)
    if rack is not None:
        rack_in = fill_mean(dc)
        dc_r, aux_r = apply_mitigation(rack, rack_in, cfg.dt, k_rack)
        if rack_on is not None:
            dc_r = jnp.where(rack_on > 0, dc_r, rack_in)
        aux["rack"] = aux_r
        dc = dc_r
    out["dc_mitigated"] = dc

    if mask is not None:
        e_in = msum(dc_raw)
        out["energy_overhead"] = (msum(dc) - e_in) / jnp.maximum(e_in, 1e-12)
        out["swing"] = _swing_stats_masked(dc_raw, mask, n_valid)
        out["swing_mitigated"] = _swing_stats_masked(dc, mask, n_valid)
    else:
        out["energy_overhead"] = energy_overhead_jax(dc_raw, dc)
        out["swing"] = swing_stats_jax(dc_raw)
        out["swing_mitigated"] = swing_stats_jax(dc)
    if spectra:
        out["bands"] = critical_band_report_jax(dc_raw, cfg.dt)
        out["bands_mitigated"] = critical_band_report_jax(dc, cfg.dt)
    if spec is not None:
        ok, flags, metrics = spec.validate_jax(dc, cfg.dt, limits)
        out["spec_ok"] = ok
        out["spec_flags"] = flags
        out["spec_metrics"] = metrics
    out["aux"] = aux
    return out


def _swing_stats_masked(w, mask, n_valid) -> Dict[str, jnp.ndarray]:
    """``swing_stats_jax`` over the valid prefix of a padded waveform."""
    peak = jnp.max(jnp.where(mask, w, -jnp.inf))
    trough = jnp.min(jnp.where(mask, w, jnp.inf))
    return {
        "peak_w": peak,
        "trough_w": trough,
        "swing_w": peak - trough,
        "mean_w": jnp.sum(jnp.where(mask, w, 0.0)) / n_valid,
        "swing_frac": (peak - trough) / jnp.maximum(peak, 1e-9),
    }


def _simulate_one(levels, shifts, n_chips, dev, rack, dev_on, rack_on, key,
                  n_valid, limits, cfg, hw, spec, spectra,
                  reach: int) -> Dict:
    chip, dc_raw = _synth_one(levels, shifts, n_chips, n_valid, cfg, hw,
                              reach)
    return _mitigate_one(chip, dc_raw, shifts, n_chips, dev, rack, dev_on,
                         rack_on, key, n_valid, limits, cfg, hw, spec,
                         spectra, True, reach)


def _over_rows(rows, plan: Optional[ScenarioShardPlan], shared, row_args):
    """``rows(*shared, *row_args)``, where ``rows`` vmaps over the leading
    scenario axis of ``row_args``.  Under a plan of more than one shard
    it runs inside a ``shard_map`` over the scenario axis, so each device
    runs its own rows (``shared`` is replicated): the Pallas kernels of a
    row (the backstop's monitor) cannot be partitioned automatically.
    The body is row-local and has no collectives, so the varying-axis
    check is off: it would only ask every scan's constant initial carry
    to be cast to varying."""
    if plan is None or plan.n_shards <= 1:
        return rows(*shared, *row_args)
    rows_spec = jax.sharding.PartitionSpec(plan.axis)
    return jax.shard_map(
        rows, mesh=plan.mesh,
        in_specs=((jax.sharding.PartitionSpec(),) * len(shared)
                  + (rows_spec,) * len(row_args)),
        out_specs=rows_spec, check_vma=False)(*shared, *row_args)


# ``levels`` (argnum 0) is the one O(B*n) host->device input of every
# pipeline call; donating it lets XLA reuse its buffer for the same-shape
# waveform outputs, so a streaming chunk holds one buffer fewer in flight.
# ``spec`` is the spec's *family* (shape structure only — static) and
# ``limits`` its traced thresholds, so same-family specs share the
# executable (see UtilitySpec.family()).  ``reach`` (static) is the
# jittered aggregation's band half-width, ``jitter_reach`` of the call's
# shifts.
@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("cfg", "hw", "spec", "spectra", "plan",
                                    "reach"))
def _simulate_vmapped(levels, shifts, n_chips, dev, rack, dev_on, rack_on,
                      keys, n_valid, limits, *, cfg: WaveformConfig,
                      hw: Hardware, spec: Optional[UtilitySpec],
                      spectra: bool, plan: Optional[ScenarioShardPlan],
                      reach: int):
    def rows(limits, *row_args):
        return jax.vmap(
            lambda L, S, N, D, R, Do, Ro, K, V: _simulate_one(
                L, S, N, D, R, Do, Ro, K, V, limits, cfg, hw, spec, spectra,
                reach)
        )(*row_args)
    return _over_rows(rows, plan, (limits,),
                      (levels, shifts, n_chips, dev, rack, dev_on, rack_on,
                       keys, n_valid))


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("cfg", "hw", "reach"))
def _synth_vmapped(levels, shifts, n_chips, n_valid, *, cfg: WaveformConfig,
                   hw: Hardware, reach: int):
    return jax.vmap(
        lambda L, S, N, V: _synth_one(L, S, N, V, cfg, hw, reach)
    )(levels, shifts, n_chips, n_valid)


@functools.partial(jax.jit, static_argnames=("cfg", "hw", "spec", "spectra",
                                             "chip_outputs", "plan", "reach"))
def _mitigate_vmapped(chip_u, dcraw_u, u_idx, shifts, n_chips, dev, rack,
                      dev_on, rack_on, keys, n_valid, limits, *,
                      cfg: WaveformConfig, hw: Hardware,
                      spec: Optional[UtilitySpec], spectra: bool,
                      chip_outputs: bool, plan: Optional[ScenarioShardPlan],
                      reach: int):
    """Per-scenario suffix over rows that *share* synthesized prefixes:
    ``chip_u``/``dcraw_u`` hold one entry per unique (workload, fleet,
    seed) and ``u_idx`` maps each scenario row to its prefix."""
    def rows(chip_u, dcraw_u, limits, *row_args):
        return jax.vmap(
            lambda U, S, N, D, R, Do, Ro, K, V: _mitigate_one(
                chip_u[U], dcraw_u[U], S, N, D, R, Do, Ro, K, V, limits,
                cfg, hw, spec, spectra, chip_outputs, reach)
        )(*row_args)
    return _over_rows(rows, plan, (chip_u, dcraw_u, limits),
                      (u_idx, shifts, n_chips, dev, rack, dev_on, rack_on,
                       keys, n_valid))


# ---------------------------------------------------------------------------
# scenario-axis sharding
# ---------------------------------------------------------------------------

def _resolve_plan(plan: Optional[ScenarioShardPlan],
                  shard_devices: bool) -> Optional[ScenarioShardPlan]:
    """An explicit mesh plan wins; ``shard_devices=True`` keeps its old
    meaning as shorthand for the all-local-devices plan."""
    if plan is not None:
        return plan
    return scenario_plan() if shard_devices else None


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchResult:
    """One row per scenario; waveforms are [B, n], metrics are [B].

    In pad-and-mask mode (``pad_to``), row ``i``'s true waveform is the
    first ``n_valid[i]`` samples (the remainder is padding); scalar metrics
    are already masked, and frequency/spec analysis is deferred to
    ``analyze_batch`` on the sliced rows.
    """
    t: np.ndarray
    dc_raw: np.ndarray
    dc_mitigated: np.ndarray
    chip_raw: Optional[np.ndarray]
    chip_mitigated: Optional[np.ndarray]
    energy_overhead: np.ndarray
    swing: Dict[str, np.ndarray]
    swing_mitigated: Dict[str, np.ndarray]
    bands: Optional[Dict[str, np.ndarray]]
    bands_mitigated: Optional[Dict[str, np.ndarray]]
    spec_ok: Optional[np.ndarray]
    spec_flags: Optional[Dict[str, np.ndarray]]
    spec_metrics: Optional[Dict[str, np.ndarray]]
    aux: Dict
    n_valid: Optional[np.ndarray] = None
    dev_on: Optional[np.ndarray] = None
    rack_on: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return self.dc_raw.shape[0]

    def length(self, i: int) -> int:
        return (self.dc_raw.shape[1] if self.n_valid is None
                else int(self.n_valid[i]))

    def report(self, i: int) -> Optional[SpecReport]:
        if self.spec_ok is None:
            return None
        row = jax.tree.map(lambda a: a[i], (self.spec_flags, self.spec_metrics))
        return report_from_arrays(self.spec_ok[i], row[0], row[1])

    def scenario(self, i: int) -> SimResult:
        """Rebuild the per-scenario ``SimResult`` (API compat with
        ``stratosim.simulate``) for row ``i``; padded rows are sliced back
        to their true length."""
        n = self.length(i)
        row = lambda d: {k: float(v[i]) for k, v in d.items()}
        chip_m = self.chip_mitigated
        aux_row = jax.tree.map(lambda a: a[i], self.aux)
        # masked-off rows ran a structural placeholder config whose output
        # was discarded — drop its aux too, matching the serial reference
        if self.dev_on is not None and not self.dev_on[i]:
            chip_m = None
            aux_row.pop("device", None)
        if self.rack_on is not None and not self.rack_on[i]:
            aux_row.pop("rack", None)
        return SimResult(
            t=self.t[:n],
            dc_raw=self.dc_raw[i, :n], dc_mitigated=self.dc_mitigated[i, :n],
            chip_raw=(None if self.chip_raw is None
                      else self.chip_raw[i, ..., :n]),
            chip_mitigated=(None if chip_m is None else chip_m[i, ..., :n]),
            energy_overhead=float(self.energy_overhead[i]),
            swing=row(self.swing), swing_mitigated=row(self.swing_mitigated),
            bands=(row(self.bands) if self.bands is not None else {}),
            bands_mitigated=(row(self.bands_mitigated)
                             if self.bands_mitigated is not None else {}),
            spec_report=self.report(i),
            aux=materialize_aux(aux_row))


def _prepare_rows(timelines, n_chips, seeds, device_mitigation,
                  rack_mitigation, levels, cfg: WaveformConfig, hw: Hardware):
    """Broadcast every batched argument to a common row count B, and
    count each row's samples (``lens``); a row's levels stay None unless
    ``levels`` gives them, for ``_tenant_inputs`` to make.  The shared
    prologue of ``simulate_batch`` and the chunked ``stream_batches``
    executor."""
    tls = timelines if isinstance(timelines, (list, tuple)) else [timelines]
    chips = n_chips if isinstance(n_chips, (list, tuple)) else [n_chips]
    seed_list = seeds if isinstance(seeds, (list, tuple)) else [seeds]
    dev_list = (device_mitigation if isinstance(device_mitigation, (list, tuple))
                else [device_mitigation])
    rack_list = (rack_mitigation if isinstance(rack_mitigation, (list, tuple))
                 else [rack_mitigation])

    B = max(len(tls), len(chips), len(seed_list), len(dev_list), len(rack_list))
    tls = _tile(tls, B, "timelines")
    chips = _tile(chips, B, "n_chips")
    seed_list = _tile(seed_list, B, "seeds")
    dev_list = _tile(dev_list, B, "device_mitigation")
    rack_list = _tile(rack_list, B, "rack_mitigation")

    level_rows = ([None] * B if levels is None
                  else _tile(list(levels), B, "levels"))
    len_of: Dict[int, int] = {}
    for tl, r in zip(tls, level_rows):
        if r is None and id(tl) not in len_of:
            len_of[id(tl)] = n_samples(tl, cfg, hw)
    lens = [len_of[id(tl)] if r is None else r.shape[-1]
            for tl, r in zip(tls, level_rows)]
    return tls, chips, seed_list, dev_list, rack_list, level_rows, lens, B


def _tenant_inputs(tls, chips, seeds, level_rows, cfg: WaveformConfig,
                   hw: Hardware, sample_chips: int):
    """Each row's per-tenant levels, chips and jitter shifts
    (``campus.row_inputs``), made once for the rows of one workload,
    fleet and seed, which share one levels array: the dedup keys on
    it."""
    if len({n_tenants(tl) for tl in tls}) > 1:
        raise ValueError("rows of one call must have the same number of "
                         "tenants")
    cache: Dict[Tuple[int, int, int, float], Tuple] = {}
    for tl, n, s, lv in zip(tls, chips, seeds, level_rows):
        key = (id(tl), id(lv), s, n)
        if key not in cache:
            cache[key] = row_inputs(tl, s, n, cfg, hw, sample_chips, lv)
    return [list(x) for x in zip(*(
        cache[id(tl), id(lv), s, n]
        for tl, n, s, lv in zip(tls, chips, seeds, level_rows)))]


def simulate_batch(
        timelines: Union[IterationTimeline, Sequence[IterationTimeline]],
        n_chips: Union[int, Sequence[int]],
        wave_cfg: Optional[WaveformConfig] = None,
        *, device_mitigation=None, rack_mitigation=None,
        spec: Optional[UtilitySpec] = None, hw: Hardware = DEFAULT_HW,
        seeds: Union[int, Sequence[int]] = 0,
        keys=None,
        sample_chips: int = 64,
        levels: Optional[Sequence[np.ndarray]] = None,
        pad_to: Optional[int] = None,
        spectra: bool = True,
        shard_devices: bool = False,
        plan: Optional[ScenarioShardPlan] = None,
        dedup: bool = False,
        chip_outputs: bool = True,
        host_arrays: bool = True) -> BatchResult:
    """Simulate a batch of scenarios in one compiled call.

    Each batched argument (timelines, n_chips, device/rack mitigation
    configs, seeds, keys) is a singleton (broadcast) or a length-B
    sequence.  Mitigation rows may mix None (disabled) and enabled configs
    — disabled rows produce the unmitigated waveform.  ``keys`` threads a
    per-scenario PRNG key into mitigations that consume randomness
    (telemetry noise), so noisy rows get independent draws.

    Without ``pad_to``, all timelines must expand to the same sample count
    (``sweep`` buckets mixed-length workloads).  With ``pad_to=N``, rows
    are edge-padded to N and masked — mixed lengths fuse into ONE compiled
    call; frequency/spec analysis then runs per true length via
    ``analyze_batch`` (``spec`` must be None and ``spectra`` False).

    A ``timelines`` entry may be a ``Campus`` (``core/campus.py``):
    several jobs behind one grid connection, its ``n_chips`` the
    campus's total.  Its row carries a tenant axis (levels [K, n], chips
    [K], shifts [K, S]) through synthesis and the device stage, and the
    rack stage and every metric see the tenants' sum; rows of one call
    share a tenant count.  A campus of one tenant is a single-job row.

    ``levels`` optionally supplies per-row ``phase_levels`` arrays
    precomputed; ``plan`` (a ``ScenarioShardPlan``) partitions the
    scenario axis across its mesh — ``shard_devices=True`` is shorthand
    for the default all-local-devices plan.  ``dedup`` splits the
    pipeline in two: the mitigation-
    independent prefix (chip synthesis + raw aggregation) runs once per
    unique (workload, fleet, seed) and the per-config suffix gathers it —
    the declarative Study layer enables this because it knows which axes a
    row's physics actually depends on.
    """
    cfg = wave_cfg or WaveformConfig()
    (tls, chips, seed_list, dev_list, rack_list, level_rows, _,
     B) = _prepare_rows(timelines, n_chips, seeds, device_mitigation,
                        rack_mitigation, levels, cfg, hw)

    with obs.span("repro.engine.tenants"):
        level_rows, tenant_chips, shift_rows = _tenant_inputs(
            tls, chips, seed_list, level_rows, cfg, hw, sample_chips)
    with obs.span("repro.engine.inputs"):
        src_ids = [id(r) for r in level_rows]   # pre-padding row identity
        n_valid_arr = None
        if pad_to is not None:
            if spec is not None or spectra:
                raise ValueError(
                    "pad_to defers frequency/spec analysis to analyze_batch "
                    "on the sliced rows: call with spec=None, spectra=False")
            lens = [r.shape[-1] for r in level_rows]
            if max(lens) > pad_to:
                raise ValueError(
                    f"pad_to={pad_to} < longest workload {max(lens)}")
            n_valid_arr = jnp.asarray(lens, jnp.float32)
            level_rows = [np.pad(r, [(0, 0)] * (r.ndim - 1)
                                 + [(0, pad_to - r.shape[-1])], mode="edge")
                          for r in level_rows]
        else:
            n0 = level_rows[0].shape[-1]
            if any(r.shape[-1] != n0 for r in level_rows):
                raise ValueError(
                    "all timelines in one simulate_batch call must expand to "
                    "the same sample count (got "
                    f"{sorted({len(r) for r in level_rows})}); use "
                    "sweep()/Study to bucket, or pad_to to fuse")
        n = level_rows[0].shape[-1]
        shift_rows = np.stack(shift_rows)
        # one band for every row of the call, the dedup prefix included
        reach = jitter_reach(shift_rows, cfg, n)
        shifts = jnp.asarray(shift_rows)
        chips_f = jnp.asarray(np.asarray(tenant_chips, np.float32))
        keys_arr = _normalize_keys(keys, B)
        # family/limits split: the spec's *structure* is the static jit
        # key, its numeric thresholds ride in as traced scalars — every
        # same-family spec (lenient/moderate/tight at any job power)
        # shares one executable
        family = None if spec is None else spec.family()
        limits = None if spec is None else spec.limits()
        shard = _resolve_plan(plan, shard_devices)
        if dedup:
            # synthesis once per unique (workload, fleet, seed); the
            # per-config suffix gathers its prefix by index
            uniq: Dict[Tuple, int] = {}
            u_rows: List[int] = []
            u_idx: List[int] = []
            for i, k in enumerate(zip(src_ids, chips, seed_list)):
                if k not in uniq:
                    uniq[k] = len(u_rows)
                    u_rows.append(i)
                u_idx.append(uniq[k])
            sel = np.asarray(u_rows)
            synth_in = (jnp.asarray(np.stack([level_rows[i] for i in u_rows]),
                                    jnp.float32),
                        shifts[sel], chips_f[sel],
                        None if n_valid_arr is None else n_valid_arr[sel])
            u_idx = jnp.asarray(u_idx, jnp.int32)
    with obs.span("repro.engine.stack_mits", stage="device"):
        dev, dev_on = _normalize_mits(dev_list, B, "device_mitigation")
    with obs.span("repro.engine.stack_mits", stage="rack"):
        rack, rack_on = _normalize_mits(rack_list, B, "rack_mitigation")

    out_B = B
    with obs.span("repro.engine.enqueue", aggregate="band", reach=reach):
        if dedup:
            if shard is not None and shard.n_processes > 1:
                # global arrays only compose with global arrays in one
                # SPMD program: commit the unique-row prefix to the
                # scenario mesh too (pad rows are duplicates no ``u_idx``
                # ever references)
                synth_in, _ = shard.shard_batch(synth_in, len(u_rows))
            chip_u, dcraw_u = _synth_vmapped(*synth_in, cfg=cfg, hw=hw,
                                             reach=reach)
            row_args = (u_idx, shifts, chips_f, dev, rack, dev_on, rack_on,
                        keys_arr, n_valid_arr)
            if shard is not None:
                row_args, out_B = shard.shard_batch(row_args, B)
            res = _mitigate_vmapped(chip_u, dcraw_u, *row_args, limits,
                                    cfg=cfg, hw=hw, spec=family,
                                    spectra=spectra,
                                    chip_outputs=chip_outputs, plan=shard,
                                    reach=reach)
        else:
            args = (jnp.asarray(np.stack(level_rows), jnp.float32), shifts,
                    chips_f, dev, rack, dev_on, rack_on, keys_arr,
                    n_valid_arr)
            if shard is not None:
                args, out_B = shard.shard_batch(args, B)
            res = _simulate_vmapped(*args, limits, cfg=cfg, hw=hw,
                                    spec=family, spectra=spectra, plan=shard,
                                    reach=reach)
    if host_arrays:
        # single-process this is the plain np.asarray(+slice) host pull;
        # multi-process it is one replicate-all collective first
        res = host_allgather(res, shard, take=None if out_B == B else B)
    elif out_B != B and (shard is None or shard.n_processes <= 1):
        # keep waveforms on device (callers like Study slice them straight
        # into the analysis jit without a host round-trip).  Multi-process
        # keeps the shard padding too — an eager slice would re-replicate
        # the array; downstream gathers never touch the pad rows.
        res = jax.tree.map(lambda a: a[:B], res)
    with obs.span("repro.stream.pull"):
        n_valid = (None if n_valid_arr is None
                   else np.asarray(n_valid_arr, np.int64))
        dev_on = None if dev_on is None else np.asarray(dev_on) > 0
        rack_on = None if rack_on is None else np.asarray(rack_on) > 0
    return BatchResult(
        t=np.arange(n) * cfg.dt,
        dc_raw=res["dc_raw"], dc_mitigated=res["dc_mitigated"],
        chip_raw=res.get("chip_raw"),
        chip_mitigated=res.get("chip_mitigated"),
        energy_overhead=res["energy_overhead"],
        swing=res["swing"], swing_mitigated=res["swing_mitigated"],
        bands=res.get("bands"), bands_mitigated=res.get("bands_mitigated"),
        spec_ok=res.get("spec_ok"), spec_flags=res.get("spec_flags"),
        spec_metrics=res.get("spec_metrics"), aux=res["aux"],
        n_valid=n_valid, dev_on=dev_on, rack_on=rack_on)


# ---------------------------------------------------------------------------
# streaming chunked execution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamChunk:
    """Per-chunk *metrics* of a ``stream_batches`` run.

    Rows ``start:stop`` of the stream's scenario axis.  Everything here
    is a small host array of one entry per row — the waveforms stayed on
    device and were reduced to metrics inside jit; they are only present
    (``dc_raw``/``dc_mitigated``) when the stream was asked to keep them.
    ``spec_ok`` / ``spec_flags`` / ``spec_metrics`` align with the
    stream's ``specs`` sequence (None entries for a None spec);
    ``spec_metrics`` rows are per-row dicts because the metric key set
    depends on each row's true waveform length.
    """
    start: int
    stop: int
    n: int                                   # common (padded) sample count
    n_valid: Optional[np.ndarray]            # [C] true lengths (None = n)
    energy_overhead: np.ndarray              # [C]
    swing: Dict[str, np.ndarray]             # each [C]
    swing_mitigated: Dict[str, np.ndarray]
    bands_mitigated: Optional[Dict[str, np.ndarray]]
    spec_ok: List[Optional[np.ndarray]]      # per spec: [C] bool
    spec_flags: List[Optional[Dict[str, np.ndarray]]]
    spec_metrics: List[Optional[List[Dict[str, float]]]]
    dc_raw: Optional[np.ndarray] = None      # [C, n] (keep_waveforms only)
    dc_mitigated: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return self.stop - self.start

    def length(self, i: int) -> int:
        return self.n if self.n_valid is None else int(self.n_valid[i])

    def report(self, si: int, i: int) -> Optional[SpecReport]:
        """SpecReport of row ``i`` under spec ``si`` (None if that spec
        slot was None)."""
        if self.spec_ok[si] is None:
            return None
        flags = {k: v[i] for k, v in self.spec_flags[si].items()}
        return report_from_arrays(self.spec_ok[si][i], flags,
                                  self.spec_metrics[si][i])


def _pow2_pad(idx: List[int]) -> List[int]:
    """Pad an index list to the next power of two (repeating the last
    entry) so vmapped analysis calls compile for O(log B) distinct batch
    sizes instead of one per chunk composition."""
    m = 1
    while m < len(idx):
        m <<= 1
    return idx + [idx[-1]] * (m - len(idx))


def stream_batches(
        timelines: Union[IterationTimeline, Sequence[IterationTimeline]],
        n_chips: Union[int, Sequence[int]],
        wave_cfg: Optional[WaveformConfig] = None,
        *, device_mitigation=None, rack_mitigation=None,
        specs=None, hw: Hardware = DEFAULT_HW,
        seeds: Union[int, Sequence[int]] = 0,
        keys=None,
        sample_chips: int = 64,
        levels: Optional[Sequence[np.ndarray]] = None,
        pad_to: Optional[int] = None,
        chunk_size: int = 1024,
        bands: bool = True,
        keep_waveforms: bool = False,
        dedup: bool = True,
        chip_outputs: bool = True,
        shard_devices: bool = False,
        plan: Optional[ScenarioShardPlan] = None,
        skip_rows: int = 0):
    """Iterate a scenario batch in fixed-size chunks of compiled work,
    yielding one metrics-only ``StreamChunk`` per chunk.

    The streaming core behind ``Study.run(stream=...)``: each chunk runs
    the ``simulate_batch`` pipeline (waveforms kept on device, the
    chunk's stacked ``levels`` buffer donated to XLA) and then reduces
    straight to metrics inside jit — per-row swing/overhead from the
    pipeline, plus frequency bands and spec verdicts via vmapped
    analysis calls grouped by true waveform length (analysis batches are
    padded to powers of two so compile count stays O(log chunk) however
    lengths mix).  Only O(chunk)-sized metric arrays ever reach the
    host; device memory is O(chunk_size * n) regardless of how many
    scenarios the grid declares.

    Chunk ``k+1`` is dispatched *before* chunk ``k``'s metrics are
    pulled to host, so host transfer overlaps device compute.  Tail
    chunks are padded to ``chunk_size`` by repeating the last row (and
    sliced back), keeping every chunk the same compiled shape.

    ``specs`` is None, one ``UtilitySpec``, or a sequence (None entries
    allowed — that slot yields no verdicts); all specs judge every row.
    ``pad_to`` fixes the padded length (defaults to the longest row when
    lengths mix); ``plan`` / ``shard_devices`` compose scenario-axis
    sharding with the chunking — each chunk is padded to a shard
    multiple and committed to the plan's mesh.  Per-row results are
    bit-identical to a one-shot ``simulate_batch`` over the same rows:
    chunking, tail padding, analysis-batch padding and sharding only
    ever add rows that are sliced away.

    ``skip_rows`` drops every chunk whose rows are entirely below it
    without dispatching any work — the resume fast-path (``ckpt/resume``
    restores those chunks from disk).  It must land on a chunk boundary;
    because per-row values are chunk-composition independent, the
    surviving chunks are bit-identical to the same chunks of a full run.
    """
    cfg = wave_cfg or WaveformConfig()
    with obs.span("repro.stream.prepare") as prep:
        (tls, chips, seed_list, dev_list, rack_list, level_rows, lens,
         B) = _prepare_rows(timelines, n_chips, seeds, device_mitigation,
                            rack_mitigation, levels, cfg, hw)
        prep.attrs["rows"] = B
        spec_list = (list(specs) if isinstance(specs, (list, tuple))
                     else [specs])
        # per-slot family/limits split, computed once for the whole stream
        fam_lims = [(None, None) if sp is None
                    else (sp.family(), sp.limits()) for sp in spec_list]
        keys_arr = _normalize_keys(keys, B)

        if pad_to is None and len(set(lens)) > 1:
            pad_to = max(lens)
        chunk_size = max(1, min(chunk_size, B))
        n_chunks = -(-B // chunk_size)
        shard = _resolve_plan(plan, shard_devices)

    def dispatch(lo: int, hi: int, tail: int):
        C = hi - lo

        def sl(xs):
            return xs[lo:hi] + [xs[hi - 1]] * tail

        ks = None
        if keys_arr is not None:
            ks = keys_arr[lo:hi]
            if tail:
                ks = jnp.concatenate([ks, jnp.repeat(ks[-1:], tail, axis=0)])
        res = simulate_batch(
            sl(tls), sl(chips), cfg,
            device_mitigation=sl(dev_list), rack_mitigation=sl(rack_list),
            spec=None, hw=hw, seeds=sl(seed_list), keys=ks,
            sample_chips=sample_chips, levels=sl(level_rows),
            pad_to=pad_to, spectra=False, plan=shard, dedup=dedup,
            chip_outputs=chip_outputs, host_arrays=False)
        # in-jit reduction to metrics: one vmapped analysis call per
        # (true length, spec) group on device-resident waveform slices
        groups: Dict[int, List[int]] = {}
        for i in range(C):
            groups.setdefault(lens[lo + i], []).append(i)
        gres = []
        mult = (shard.n_shards
                if shard is not None and shard.n_processes > 1 else 1)
        with obs.span("repro.stream.analyze", groups=len(groups)):
            for L, g in sorted(groups.items()):
                # pow2 padding buys bounded compile counts across chunks;
                # a single-chunk (one-shot) run has one fixed shape either
                # way, so analyze at exact size and skip the wasted lanes
                sel = list(_pow2_pad(g) if n_chunks > 1 else g)
                if len(sel) % mult:
                    # multi-process analysis stays sharded: pad the
                    # gather to a shard multiple (pow2 sizes usually
                    # already are)
                    sel += [sel[-1]] * (mult - len(sel) % mult)
                mit = gather_rows(res.dc_mitigated, sel, shard, length=L)
                per_spec = []
                for si, sp in enumerate(spec_list):
                    do_bands = bands and si == 0
                    if sp is None and not do_bands:
                        per_spec.append(None)
                        continue
                    fam, lim = fam_lims[si]
                    per_spec.append(_analyze_vmapped(
                        None, mit, lim, spec=fam, dt=cfg.dt,
                        bands=do_bands))
                gres.append((g, per_spec))
        return lo, hi, res, gres

    def materialize(pending) -> StreamChunk:
        lo, hi, res, gres = pending
        C = hi - lo
        S = len(spec_list)
        # the chunk's blocking wait: one host pull for all per-row metric
        # fields, then one per analysis call; multi-process each is the
        # cross-process merge (replicate-all, then np.asarray)
        with obs.span("repro.stream.pull"):
            direct = host_allgather(
                {"eo": res.energy_overhead, "sw": res.swing,
                 "swm": res.swing_mitigated,
                 "raw": res.dc_raw if keep_waveforms else None,
                 "mit": res.dc_mitigated if keep_waveforms else None},
                shard, take=C)
            gres = [(g, [None if a is None
                         else host_allgather(a, shard, take=len(g))
                         for a in per_spec])
                    for g, per_spec in gres]
        chunk = StreamChunk(
            start=lo, stop=hi,
            n=res.dc_mitigated.shape[1],
            n_valid=None if res.n_valid is None else res.n_valid[:C],
            energy_overhead=direct["eo"],
            swing=direct["sw"],
            swing_mitigated=direct["swm"],
            bands_mitigated=None,
            spec_ok=[None] * S, spec_flags=[None] * S,
            spec_metrics=[None] * S,
            dc_raw=direct["raw"], dc_mitigated=direct["mit"])
        bands_cols: Dict[str, np.ndarray] = {}
        for g, per_spec in gres:
            for si, a in enumerate(per_spec):
                if a is None:
                    continue
                if "bands_mitigated" in a:
                    for k, v in a["bands_mitigated"].items():
                        bands_cols.setdefault(
                            k, np.empty(C, v.dtype))[g] = v
                if spec_list[si] is None:
                    continue
                if chunk.spec_ok[si] is None:
                    chunk.spec_ok[si] = np.zeros(C, bool)
                    chunk.spec_flags[si] = {
                        k: np.zeros(C, bool) for k in a["spec_flags"]}
                    chunk.spec_metrics[si] = [None] * C
                chunk.spec_ok[si][g] = a["spec_ok"]
                for k, v in a["spec_flags"].items():
                    chunk.spec_flags[si][k][g] = v
                for j, i in enumerate(g):
                    chunk.spec_metrics[si][i] = {
                        k: float(v[j]) for k, v in a["spec_metrics"].items()}
        if bands_cols:
            chunk.bands_mitigated = bands_cols
        return chunk

    def pulled(pending) -> StreamChunk:
        with obs.span("repro.stream.materialize",
                      rows=pending[1] - pending[0]):
            return materialize(pending)

    if skip_rows % chunk_size and skip_rows < B:
        raise ValueError(
            f"skip_rows={skip_rows} is not a chunk boundary of "
            f"chunk_size={chunk_size}")
    pending = None
    for lo in range(0, B, chunk_size):
        hi = min(lo + chunk_size, B)
        if hi <= skip_rows:
            continue
        tail = chunk_size - (hi - lo) if n_chunks > 1 else 0
        with obs.span("repro.stream.dispatch", lo=lo, rows=hi - lo,
                      tail_pad=tail,
                      tenant_rows=sum(map(n_tenants, tls[lo:hi]))):
            cur = dispatch(lo, hi, tail)
        if pending is not None:
            yield pulled(pending)
        pending = cur
    if pending is not None:
        yield pulled(pending)


# ---------------------------------------------------------------------------
# cartesian sweep
# ---------------------------------------------------------------------------

def sweep(workloads,
          n_chips: Sequence[int],
          configs: Sequence[Tuple[Optional[Mitigation], Optional[Mitigation]]],
          wave_cfg: Optional[WaveformConfig] = None,
          *, spec: Optional[UtilitySpec] = None, hw: Hardware = DEFAULT_HW,
          seeds: Sequence[int] = (0,), sample_chips: int = 64) -> List[Dict]:
    """Cartesian (workload x fleet size x config x seed) sweep.

    ``workloads`` is a dict name -> IterationTimeline (or a sequence, named
    by index); each config is a ``(device_mitigation, rack_mitigation)``
    pair (either side may be None — including per-row, so baselines batch
    with mitigated configs).  Workloads are bucketed by sample count; each
    bucket runs as ONE compiled vmapped call.  Returns one flat record dict
    per scenario.  (The declarative front-end over this is ``core.study``.)
    """
    cfg = wave_cfg or WaveformConfig()
    if isinstance(workloads, dict):
        names, tls = list(workloads.keys()), list(workloads.values())
    else:
        tls = list(workloads)
        names = [f"workload{i}" for i in range(len(tls))]
    combos = [(ti, ni, ci, si)
              for ti in range(len(tls)) for ni in n_chips
              for ci in range(len(configs)) for si in seeds]
    tl_levels = [phase_levels(tl, cfg, hw) for tl in tls]  # once per workload
    buckets: Dict[int, List[Tuple[int, Tuple]]] = {}
    for pos, combo in enumerate(combos):
        buckets.setdefault(len(tl_levels[combo[0]]), []).append((pos, combo))

    records: List[Optional[Dict]] = [None] * len(combos)
    for _, items in sorted(buckets.items()):
        idxs = [combo for _, combo in items]
        res = simulate_batch(
            [tls[ti] for ti, _, _, _ in idxs],
            [ni for _, ni, _, _ in idxs],
            cfg,
            device_mitigation=[configs[ci][0] for _, _, ci, _ in idxs],
            rack_mitigation=[configs[ci][1] for _, _, ci, _ in idxs],
            spec=spec, hw=hw, seeds=[si for _, _, _, si in idxs],
            sample_chips=sample_chips,
            levels=[tl_levels[ti] for ti, _, _, _ in idxs])
        for b, (pos, (ti, ni, ci, si)) in enumerate(items):
            rec = {
                "workload": names[ti],
                "n_chips": ni,
                "config": ci,
                "seed": si,
                "period_s": tls[ti].period_s,
                "mean_mw": float(res.swing["mean_w"][b]) / 1e6,
                "swing_mw": float(res.swing["swing_w"][b]) / 1e6,
                "swing_mitigated_mw":
                    float(res.swing_mitigated["swing_w"][b]) / 1e6,
                "energy_overhead": float(res.energy_overhead[b]),
                "paper_band_frac":
                    float(res.bands_mitigated["paper_band_0p2_3hz"][b]),
            }
            if res.spec_ok is not None:
                rec["spec_ok"] = bool(res.spec_ok[b])
                rec["violations"] = res.report(b).violations
            records[pos] = rec
    return records


# ---------------------------------------------------------------------------
# chip-level config batches (Fig. 6 style sweeps)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dt",))
def _apply_vmapped(mits, w, *, dt: float):
    return jax.vmap(lambda m: m.apply_jax(w, dt))(mits)


def apply_batch(mitigations: Sequence, w: np.ndarray, dt: float
                ) -> Tuple[np.ndarray, Dict]:
    """Apply B structurally-identical mitigation configs to ONE waveform in
    a single vmapped call: (outs [B, n], aux dict with leading B axis)."""
    batched = stack_mitigations(mitigations)
    outs, aux = _apply_vmapped(batched, jnp.asarray(w, jnp.float32), dt=dt)
    return np.asarray(outs), jax.tree.map(np.asarray, aux)


# ---------------------------------------------------------------------------
# batched spec validation + frequency reports
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def _validate_vmapped(ws, limits, *, spec: UtilitySpec, dt: float):
    return jax.vmap(lambda w: spec.validate_jax(w, dt, limits))(ws)


def validate_many(ws: np.ndarray, spec: UtilitySpec, dt: float
                  ) -> Tuple[np.ndarray, List[SpecReport]]:
    """Validate B same-length waveforms [B, n] against one spec in a single
    vmapped call: (ok [B], per-row SpecReports)."""
    ok, flags, metrics = _validate_vmapped(
        jnp.asarray(np.asarray(ws), jnp.float32), spec.limits(),
        spec=spec.family(), dt=dt)
    ok = np.asarray(ok)
    flags, metrics = jax.tree.map(np.asarray, (flags, metrics))
    reports = [report_from_arrays(ok[i],
                                  {k: v[i] for k, v in flags.items()},
                                  {k: v[i] for k, v in metrics.items()})
               for i in range(len(ok))]
    return ok, reports


@functools.partial(jax.jit, static_argnames=("spec", "dt", "bands"))
def _analyze_vmapped(raw, mit, limits, *, spec: Optional[UtilitySpec],
                     dt: float, bands: bool):
    """``spec`` is the family (static structure); ``limits`` the traced
    thresholds — see ``UtilitySpec.family()``."""
    def one(r, m):
        out: Dict = {}
        if bands:
            if r is not None:
                out["bands"] = critical_band_report_jax(r, dt)
            out["bands_mitigated"] = critical_band_report_jax(m, dt)
        if spec is not None:
            ok, flags, metrics = spec.validate_jax(m, dt, limits)
            out["spec_ok"], out["spec_flags"] = ok, flags
            out["spec_metrics"] = metrics
        return out

    return jax.vmap(one)(raw, mit)


def analyze_batch(dc_raw: Optional[np.ndarray], dc_mitigated: np.ndarray,
                  dt: float, spec: Optional[UtilitySpec] = None, *,
                  bands: bool = True) -> Dict:
    """Frequency reports (on raw + mitigated) and spec validation (on
    mitigated) for B same-length waveform pairs in one vmapped call — the
    finalize stage a padded pipeline run defers, grouped by true length.
    ``dc_raw=None`` skips the raw-waveform band report (callers that only
    consume mitigated bands, like the Study record table, save one FFT
    per row)."""
    res = _analyze_vmapped(
        None if dc_raw is None else jnp.asarray(dc_raw, jnp.float32),
        jnp.asarray(dc_mitigated, jnp.float32),
        None if spec is None else spec.limits(),
        spec=None if spec is None else spec.family(), dt=dt, bands=bands)
    return jax.tree.map(np.asarray, res)


# ---------------------------------------------------------------------------
# batched (MPF x battery) design search
# ---------------------------------------------------------------------------

def _select_on(on, yes, no):
    """Row-masked select; ``on`` None means the stage is always enabled."""
    return yes if on is None else jnp.where(on > 0, yes, no)


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def _design_eval(gpu_b, bat_b, gpu_on, bat_on, w, n_chips, limits, *,
                 spec: UtilitySpec, dt: float):
    """``spec`` is the family; ``limits`` the traced thresholds — one
    executable serves every same-structure spec the serve path designs
    against."""
    def one(gpu, bat, g_on, b_on):
        out = w
        if gpu is not None:
            smoothed, _ = gpu.apply_jax(w / n_chips, dt)
            out = _select_on(g_on, smoothed * n_chips, out)
        if bat is not None:
            out_b, _ = bat.apply_jax(out, dt)
            out = _select_on(b_on, out_b, out)
        ok, flags, metrics = spec.validate_jax(out, dt, limits)
        return out, ok, energy_overhead_jax(w, out), flags, metrics

    return jax.vmap(one)(gpu_b, bat_b, gpu_on, bat_on)


def _rank_feasible(ok: np.ndarray, overhead: np.ndarray,
                   candidates: Sequence[Tuple[float, float]]) -> np.ndarray:
    """Feasible candidate indices ranked by (energy overhead, capacity,
    MPF) — minimal waste first, then minimal capacity (cost / embodied
    carbon), the serial solver's preference order."""
    feasible = np.flatnonzero(np.asarray(ok))
    caps = np.asarray([candidates[i][1] for i in feasible])
    mpfs = np.asarray([candidates[i][0] for i in feasible])
    # round overhead so float noise cannot outrank a smaller battery
    oh = np.round(np.asarray(overhead)[feasible], 6)
    return feasible[np.lexsort((mpfs, caps, oh))]


def _design_pair(spec: UtilitySpec, mpf: float, cap: float, n_chips: int,
                 swing: float, hw: Hardware,
                 target_tau_s: Optional[float] = None
                 ) -> Tuple[Optional[GpuPowerSmoothing],
                            Optional[RackBattery]]:
    """The concrete (device, rack) mitigation objects a design candidate
    stands for — the single construction point shared by the grid search,
    the gradient refiner's hard re-validation, and the winner handed back
    to callers.  ``mpf`` / ``cap`` of 0 mean the stage is off.
    ``target_tau_s`` optionally overrides the battery's grid-target EMA
    horizon (the warm-start predictor's third output — response latency);
    it is a pytree leaf, so mixed-tau candidates still stack."""
    gpu = (GpuPowerSmoothing(
        mpf_frac=mpf, hw=hw,
        ramp_up_w_per_s=spec.time.ramp_up_w_per_s / n_chips,
        ramp_down_w_per_s=spec.time.ramp_down_w_per_s / n_chips)
        if mpf > 0 else None)
    tau_kw = {} if target_tau_s is None else {
        "target_tau_s": float(target_tau_s)}
    bat = (RackBattery(capacity_j=cap, max_discharge_w=swing,
                       max_charge_w=swing, **tau_kw) if cap > 0 else None)
    return gpu, bat


def _eval_candidates(spec: UtilitySpec, w: np.ndarray, dt: float,
                     n_chips: int, candidates: Sequence[Tuple[float, float]],
                     *, swing: float, hw: Hardware,
                     target_tau_s: Optional[Sequence[Optional[float]]] = None):
    """Hard (exact-semantics) evaluation of ``(mpf, cap)`` candidates in
    one vmapped call: ``(outs, ok, overhead, flags, metrics)``.
    ``target_tau_s`` optionally carries one battery-latency override per
    candidate (None entries keep the default)."""
    B = len(candidates)
    taus = [None] * B if target_tau_s is None else list(target_tau_s)
    pairs = [_design_pair(spec, m, c, n_chips, swing, hw, target_tau_s=t)
             for (m, c), t in zip(candidates, taus)]
    gpus, gpu_on = _normalize_mits([g for g, _ in pairs], B,
                                   "design gpu candidates")
    bats, bat_on = _normalize_mits([b for _, b in pairs], B,
                                   "design battery candidates")
    return _design_eval(gpus, bats, gpu_on, bat_on,
                        jnp.asarray(w, jnp.float32),
                        jnp.asarray(float(n_chips), jnp.float32),
                        spec.limits(), spec=spec.family(), dt=dt)


def design_grid(spec: UtilitySpec, w: np.ndarray, dt: float, n_chips: int,
                mpf_grid: Sequence[float], cap_grid: Sequence[float],
                *, swing: float, hw: Hardware = DEFAULT_HW,
                top_k: int = 1) -> Optional[Dict]:
    """Evaluate every (MPF, capacity) candidate in one vmapped call and
    return the first passing one in grid order (MPF-major ascending — the
    serial search's minimal-waste-then-minimal-capacity preference).

    Disabled stages (MPF or capacity 0) ride through ``_normalize_mits``
    masking, the same path that lets ``simulate_batch`` mix baseline and
    mitigated rows in one batch.

    ``top_k`` > 1 additionally ranks the feasible candidates by energy
    overhead and returns the best ``top_k`` under ``"alternatives"`` —
    the seeds for ``design_gradient`` multi-start and the ranked answer
    list the compliance service serves.  The winner stays the grid-order
    pick regardless of ``top_k``.
    """
    candidates = [(m, c) for m in mpf_grid for c in cap_grid]
    outs, ok, overhead, flags, metrics = _eval_candidates(
        spec, w, dt, n_chips, candidates, swing=swing, hw=hw)
    ok = np.asarray(ok)
    if not ok.any():
        return None
    idx = int(np.argmax(ok))
    mpf, cap = candidates[idx]
    overhead = np.asarray(overhead)
    ranked = _rank_feasible(ok, overhead, candidates)[:top_k]
    alternatives = [{
        "mpf_frac": candidates[i][0],
        "battery_capacity_j": candidates[i][1],
        "energy_overhead": float(overhead[i]),
    } for i in ranked]
    row = jax.tree.map(lambda a: np.asarray(a)[idx], (flags, metrics))
    # the winner as concrete mitigation objects — the single construction
    # point callers (design_mitigation, demos) reuse instead of rebuilding
    gpu_sel, bat_sel = _design_pair(spec, mpf, cap, n_chips, swing, hw)
    return {
        "mpf_frac": mpf,
        "battery_capacity_j": cap,
        "energy_overhead": float(overhead[idx]),
        "report": report_from_arrays(ok[idx], row[0], row[1]),
        "device_mitigation": gpu_sel,
        "rack_mitigation": bat_sel,
        "mitigated": np.asarray(outs)[idx],
        "grid_ok": ok.reshape(len(mpf_grid), len(cap_grid)),
        "alternatives": alternatives,
        "method": "grid",
        "aux": {},
    }


# ---------------------------------------------------------------------------
# gradient-based (MPF x battery) design
# ---------------------------------------------------------------------------

# below this fraction of mpf_max the relaxed device stage is (mostly)
# gated off and the hard re-validation snaps mpf to exactly 0 (stage off)
_GPU_GATE_PIVOT = 0.15


@functools.partial(jax.jit, static_argnames=("spec", "dt", "steps"))
def _design_descend(x0, gpu_t, bat_t, w, n_chips, lo, hi, hyper, limits, *,
                    spec: UtilitySpec, dt: float, steps: int):
    """Vmapped multi-start Adam descent on the smooth design objective.

    ``x0`` is ``{"mpf": [S], "cap": [S]}`` (capacity in units of
    ``hyper["cap_scale"]`` joules so both coordinates are O(1) and one
    learning rate conditions both); ``gpu_t``/``bat_t`` are smooth-relaxed
    (``smooth_tau`` > 0) templates whose (mpf_frac, capacity_j) leaves get
    replaced by the iterate each step.  The objective is the spec's hinge
    loss (margin-shrunk limits) plus an energy-overhead regularizer and an
    L1 sizing regularizer; each Adam step is followed by a projection onto
    the physical box ``[lo, hi]``.  Returns (final iterates [S], loss
    history [S, steps]).

    The grid search treats mpf=0 as "device stage off"; the relaxation
    mirrors that with a sigmoid on-gate driven by mpf itself (pivot at
    ``_GPU_GATE_PIVOT`` of mpf_max), so the battery-only design is inside
    the search space — without it the spec-derived per-chip ramp limiter
    flattens the waveform at *any* mpf and the landscape plateaus.  The
    battery's off-limit (cap -> 0 => passthrough) is already natural.
    """
    mpf_max = gpu_t.hw.chip.mpf_max
    tau = gpu_t.smooth_tau

    def objective(x):
        gpu = dataclasses.replace(gpu_t, mpf_frac=x["mpf"])
        bat = dataclasses.replace(bat_t,
                                  capacity_j=x["cap"] * hyper["cap_scale"])
        per_chip = w / n_chips
        smoothed, _ = gpu.apply_jax(per_chip, dt)
        g_on = jax.nn.sigmoid((x["mpf"] - _GPU_GATE_PIVOT * mpf_max)
                              / (tau * mpf_max))
        chip_out = g_on * smoothed + (1.0 - g_on) * per_chip
        out, _ = bat.apply_jax(chip_out * n_chips, dt)
        viol, _ = spec.loss_jax(out, dt, margin=hyper["margin"],
                                limits=limits)
        overhead = energy_overhead_jax(w, out)
        return (viol + hyper["overhead_weight"] * jnp.maximum(overhead, 0.0)
                + hyper["size_weight"] * (x["cap"] + 0.25 * x["mpf"]))

    value_and_grad = jax.value_and_grad(objective)

    def one_start(x0_row):
        def step(carry, _):
            x, st = carry
            loss, g = value_and_grad(x)
            g, _ = clip_by_global_norm(g, 100.0)      # blowup hygiene
            x2, st2 = adam_update(x, g, st, hyper["lr"])
            x2 = jax.tree.map(jnp.clip, x2, lo, hi)   # box projection
            return (x2, st2), loss

        (xf, _), losses = jax.lax.scan(step, (x0_row, adam_init(x0_row)),
                                       None, length=steps)
        return xf, losses

    return jax.vmap(one_start)(x0)


def design_gradient(spec: UtilitySpec, w: np.ndarray, dt: float,
                    n_chips: int, *, swing: Optional[float] = None,
                    hw: Hardware = DEFAULT_HW,
                    seeds: Optional[Sequence[Tuple[float, float]]] = None,
                    steps: int = 120, lr: float = 0.08,
                    smooth_tau: float = 0.05, margin: float = 0.05,
                    overhead_weight: float = 0.5,
                    size_weight: float = 0.02,
                    period_hint_s: float = 2.0,
                    top_k: int = 4,
                    cap_scale: Optional[float] = None,
                    mpf_bounds: Optional[Tuple[float, float]] = None,
                    cap_bounds_j: Optional[Tuple[float, float]] = None
                    ) -> Optional[Dict]:
    """Jitted gradient descent on (MPF fraction, battery capacity).

    The forward model is the same gated gpu->battery stack the grid search
    evaluates, but run through the mitigations' ``smooth_tau`` relaxation
    so every step gate carries a gradient; the objective is
    ``UtilitySpec.loss_jax`` (smooth hinge compliance, margin-shrunk) plus
    an energy-overhead regularizer.  ``seeds`` are (mpf_frac, capacity_j)
    starts — pass a coarse grid's ``alternatives`` to refine it (the
    ``design(method="hybrid")`` path); default is a fixed 6-point lattice
    over the box.  All starts descend in one vmapped ``lax.scan``.

    The *answer* is still exact: every final iterate (plus a small
    escalation ladder above it, plus the seeds) is re-validated under the
    hard tau=0 semantics in one vmapped call, and the minimal-overhead
    passing candidate wins.  Returns the same solution dict shape as
    ``design_grid`` (plus ``loss_history`` [S, steps]), or None when no
    candidate passes the hard spec.
    """
    w = np.asarray(w, np.float32)
    swing = float(w.max() - w.min()) if swing is None else float(swing)
    cap_scale = float(cap_scale or swing * period_hint_s)
    mpf_lo, mpf_hi = mpf_bounds or (0.0, hw.chip.mpf_max)
    cap_lo_j, cap_hi_j = cap_bounds_j or (0.0, 4.0 * cap_scale)
    # caller seeds (e.g. the grid's top-k) are augmented with a fixed
    # lattice over the box: a degenerate seed set — say, only MPF-only
    # configs with cap ~ 0, where the saturated battery's capacity
    # gradient vanishes — cannot climb out on its own, and extra vmapped
    # lanes are nearly free
    lattice = [(m, f * cap_scale) for m in (0.3, 0.6, 0.85)
               for f in (0.25, 1.0)]
    seeds = lattice if seeds is None else list(seeds) + lattice
    seeds = list(dict.fromkeys(
        (float(np.clip(m, mpf_lo, mpf_hi)),
         float(np.clip(c, cap_lo_j, cap_hi_j))) for m, c in seeds))
    # the descent itself stays above a small capacity floor: at cap -> 0
    # the SoC fraction's reverse-mode terms scale like 1/cap^2 and
    # overflow f32 (NaN-poisoning the lane).  A 0.1%-of-scale battery is
    # physically a passthrough, and the raw (possibly cap=0) seeds are
    # still hard-validated verbatim below.
    cap_floor_j = max(cap_lo_j, 1e-3 * cap_scale)

    gpu_t = GpuPowerSmoothing(
        mpf_frac=0.5, hw=hw,
        ramp_up_w_per_s=spec.time.ramp_up_w_per_s / n_chips,
        ramp_down_w_per_s=spec.time.ramp_down_w_per_s / n_chips,
        smooth_tau=smooth_tau)
    bat_t = RackBattery(capacity_j=cap_scale, max_discharge_w=swing,
                        max_charge_w=swing, smooth_tau=smooth_tau)
    x0 = {"mpf": jnp.asarray([m for m, _ in seeds], jnp.float32),
          "cap": jnp.asarray([max(c, cap_floor_j) / cap_scale
                              for _, c in seeds], jnp.float32)}
    lo = {"mpf": jnp.asarray(mpf_lo, jnp.float32),
          "cap": jnp.asarray(cap_floor_j / cap_scale, jnp.float32)}
    hi = {"mpf": jnp.asarray(mpf_hi, jnp.float32),
          "cap": jnp.asarray(cap_hi_j / cap_scale, jnp.float32)}
    hyper = {"lr": jnp.asarray(lr, jnp.float32),
             "margin": jnp.asarray(margin, jnp.float32),
             "overhead_weight": jnp.asarray(overhead_weight, jnp.float32),
             "size_weight": jnp.asarray(size_weight, jnp.float32),
             "cap_scale": jnp.asarray(cap_scale, jnp.float32)}
    xf, losses = _design_descend(
        x0, gpu_t, bat_t, jnp.asarray(w), jnp.asarray(float(n_chips),
                                                      jnp.float32),
        lo, hi, hyper, spec.limits(), spec=spec.family(), dt=dt, steps=steps)

    # hard re-validation: each final iterate with a geometric capacity
    # ladder around it (the margin leaves the iterate a little above the
    # true feasibility boundary — the sub-1.0 rungs walk back down to it
    # at ~7% resolution; the >1.0 rungs cover a too-thin margin), its
    # battery-only variant (the relaxed on-gate may sit between hard on
    # and off), and the seeds themselves (so a refined answer can never
    # be worse than its grid seed)
    finals = list(zip(np.asarray(xf["mpf"]).tolist(),
                      (np.asarray(xf["cap"]) * cap_scale).tolist()))
    candidates: List[Tuple[float, float]] = []
    for m, c in finals:
        for f in (0.75, 0.8, 0.87, 0.93, 1.0, 1.08, 1.25, 1.6):
            ck = float(np.clip(c * f, cap_lo_j, cap_hi_j))
            candidates.append((m, ck))
            candidates.append((0.0, ck))
    candidates += seeds
    # snap a mostly-gated-off device stage to an exactly-off one (the
    # same pivot the descent's on-gate uses, in hw units — not mpf_hi,
    # which a caller may have narrowed)
    candidates = [(0.0 if m < _GPU_GATE_PIVOT * hw.chip.mpf_max else m,
                   0.0 if c < 1e-6 * cap_scale else c)
                  for m, c in candidates]
    candidates = list(dict.fromkeys(candidates))
    outs, ok, overhead, flags, metrics = _eval_candidates(
        spec, w, dt, n_chips, candidates, swing=swing, hw=hw)
    ok = np.asarray(ok)
    if not ok.any():
        return None
    overhead = np.asarray(overhead)
    ranked = _rank_feasible(ok, overhead, candidates)
    idx = int(ranked[0])
    mpf, cap = candidates[idx]
    row = jax.tree.map(lambda a: np.asarray(a)[idx], (flags, metrics))
    gpu_sel, bat_sel = _design_pair(spec, mpf, cap, n_chips, swing, hw)
    return {
        "mpf_frac": mpf,
        "battery_capacity_j": cap,
        "energy_overhead": float(overhead[idx]),
        "report": report_from_arrays(ok[idx], row[0], row[1]),
        "device_mitigation": gpu_sel,
        "rack_mitigation": bat_sel,
        "mitigated": np.asarray(outs)[idx],
        "alternatives": [{
            "mpf_frac": candidates[i][0],
            "battery_capacity_j": candidates[i][1],
            "energy_overhead": float(overhead[i]),
        } for i in ranked[:top_k]],
        "loss_history": np.asarray(losses),
        "method": "gradient",
        "aux": {},
    }


# capacity rungs the warm-start fast path walks around a predicted seed:
# sub-1.0 rungs reclaim an over-provisioned prediction, the >1.0 rungs
# rescue an under-provisioned one without falling back to the polisher
_WARMSTART_CAP_LADDER = (0.8, 0.9, 1.0, 1.15, 1.4, 2.0)


def design_warmstart(spec: UtilitySpec, w: np.ndarray, dt: float,
                     n_chips: int, *, predictor,
                     swing: Optional[float] = None,
                     hw: Hardware = DEFAULT_HW,
                     features=None,
                     period_hint_s: float = 2.0,
                     top_k: int = 4,
                     polish_steps: int = 40,
                     **gradient_kwargs) -> Optional[Dict]:
    """Amortized (MPF, capacity, battery-latency) design from a learned
    seed — milliseconds warm instead of the solver's seconds, with the
    answer still exactly verified.

    ``predictor(spec, w, dt, n_chips, features=features)`` returns
    ``[(mpf_frac, capacity_j, target_tau_s), ...]`` seeds (the serve
    layer's ``WarmStartPredictor``).  The fast path expands each seed
    into a small capacity ladder (plus battery-only variants) and runs
    ONE vmapped hard tau=0 evaluation — a passing rung is ranked by the
    solvers' (overhead, capacity, mpf) preference and returned.  Only
    when the whole ladder misses does it escalate: a short gradient
    polish seeded from the predictions, then the full ``hybrid`` solver —
    so the verdict (feasible or not) always matches the solver this path
    replaces, and every returned config is hard-revalidated.
    ``aux["warmstart_path"]`` records which tier answered.
    """
    w = np.asarray(w, np.float32)
    swing = float(w.max() - w.min()) if swing is None else float(swing)
    preds = predictor(spec, w, dt, n_chips, features=features)
    dedup: Dict[Tuple[float, float], float] = {}
    for mpf, cap, tau in preds:
        mpf = float(np.clip(mpf, 0.0, hw.chip.mpf_max))
        if mpf < _GPU_GATE_PIVOT * hw.chip.mpf_max:
            mpf = 0.0                       # snap a gated-off device stage
        cap = max(float(cap), 0.0)
        tau = float(tau)
        for f in _WARMSTART_CAP_LADDER:
            ck = round(cap * f, 3)
            if mpf == 0.0 and ck <= 0.0:
                continue            # no-mitigation rung: nothing to verify
            dedup.setdefault((mpf, ck), tau)
            if mpf > 0 and ck > 0:          # battery-only variant
                dedup.setdefault((0.0, ck), tau)
    candidates = list(dedup)
    taus = [dedup[c] for c in candidates]
    if candidates:
        outs, ok, overhead, flags, metrics = _eval_candidates(
            spec, w, dt, n_chips, candidates, swing=swing, hw=hw,
            target_tau_s=taus)
        ok = np.asarray(ok)
        if ok.any():
            overhead = np.asarray(overhead)
            ranked = _rank_feasible(ok, overhead, candidates)
            idx = int(ranked[0])
            mpf, cap = candidates[idx]
            row = jax.tree.map(lambda a: np.asarray(a)[idx],
                               (flags, metrics))
            gpu_sel, bat_sel = _design_pair(spec, mpf, cap, n_chips, swing,
                                            hw, target_tau_s=taus[idx])
            return {
                "mpf_frac": mpf,
                "battery_capacity_j": cap,
                "target_tau_s": taus[idx],
                "energy_overhead": float(overhead[idx]),
                "report": report_from_arrays(ok[idx], row[0], row[1]),
                "device_mitigation": gpu_sel,
                "rack_mitigation": bat_sel,
                "mitigated": np.asarray(outs)[idx],
                "alternatives": [{
                    "mpf_frac": candidates[i][0],
                    "battery_capacity_j": candidates[i][1],
                    "energy_overhead": float(overhead[i]),
                } for i in ranked[:top_k]],
                "method": "warmstart",
                "aux": {"warmstart_path": "fast"},
            }
    # ladder missed: short polish from the predicted seeds, then the full
    # solver — feasibility verdicts stay identical to method="hybrid"
    sol = design_gradient(spec, w, dt, n_chips, swing=swing, hw=hw,
                          seeds=[(m, c) for m, c, _ in preds] or None,
                          steps=polish_steps, period_hint_s=period_hint_s,
                          top_k=top_k, **gradient_kwargs)
    path = "polish"
    if sol is None:
        sol = design(spec, w, dt, n_chips, method="hybrid", hw=hw,
                     period_hint_s=period_hint_s, top_k=top_k,
                     **gradient_kwargs)
        path = "hybrid_fallback"
    if sol is None:
        return None
    sol = dict(sol)
    sol["method"] = "warmstart"
    sol["aux"] = dict(sol.get("aux") or {}, warmstart_path=path)
    return sol


def design(spec: UtilitySpec, w: np.ndarray, dt: float, n_chips: int, *,
           method: str = "hybrid", hw: Hardware = DEFAULT_HW,
           period_hint_s: float = 2.0,
           mpf_grid: Optional[Sequence[float]] = None,
           cap_grid: Optional[Sequence[float]] = None,
           top_k: int = 4,
           warmstart=None,
           features=None,
           polish_steps: int = 40,
           **gradient_kwargs) -> Optional[Dict]:
    """The one (MPF, battery-capacity) design entry point.

    method="grid"      the batched coarse grid search (``design_grid``);
    method="gradient"  jitted Adam through the smooth-relaxed pipeline
                       (``design_gradient``), lattice-seeded;
    method="hybrid"    coarse grid first, gradient refinement seeded from
                       its top-k feasible configs — never worse than the
                       grid (the seeds are re-validated candidates), and
                       finds the compliance frontier *between* grid points;
    method="warmstart" learned-seed fast path (``design_warmstart``) —
                       pass the predictor via ``warmstart=`` (and
                       optionally precomputed ``features=``); falls back
                       through gradient polish to hybrid, so verdicts
                       match the solver it amortizes.

    ``smoothing.design_mitigation`` remains the public face over this.
    """
    w = np.asarray(w, np.float32)
    swing = float(w.max() - w.min())
    if method == "warmstart":
        if warmstart is None:
            raise ValueError(
                "method='warmstart' needs a predictor: design(..., "
                "warmstart=WarmStartPredictor.load(...))")
        return design_warmstart(spec, w, dt, n_chips, predictor=warmstart,
                                swing=swing, hw=hw, features=features,
                                period_hint_s=period_hint_s, top_k=top_k,
                                polish_steps=polish_steps, **gradient_kwargs)
    if mpf_grid is None:
        # the hardware caps how high a floor is programmable
        mpf_grid = [m for m in (0.0, 0.5, 0.65, 0.8, 0.9)
                    if m <= hw.chip.mpf_max + 1e-9]
    if cap_grid is None:
        cap_grid = [0.0] + [swing * period_hint_s * f for f in
                            (0.125, 0.25, 0.5, 1.0, 2.0)]
    if method == "grid":
        return design_grid(spec, w, dt, n_chips, mpf_grid, cap_grid,
                           swing=swing, hw=hw, top_k=top_k)
    if method == "gradient":
        return design_gradient(spec, w, dt, n_chips, swing=swing, hw=hw,
                               period_hint_s=period_hint_s, top_k=top_k,
                               **gradient_kwargs)
    if method != "hybrid":
        raise ValueError(f"method must be grid|gradient|hybrid, got {method!r}")
    grid_sol = design_grid(spec, w, dt, n_chips, mpf_grid, cap_grid,
                           swing=swing, hw=hw, top_k=top_k)
    seeds = None
    if grid_sol is not None:
        seeds = [(a["mpf_frac"], a["battery_capacity_j"])
                 for a in grid_sol["alternatives"]]
        seeds.append((grid_sol["mpf_frac"], grid_sol["battery_capacity_j"]))
    grad_sol = design_gradient(spec, w, dt, n_chips, swing=swing, hw=hw,
                               period_hint_s=period_hint_s, seeds=seeds,
                               top_k=top_k, **gradient_kwargs)
    sols = [s for s in (grad_sol, grid_sol) if s is not None]
    if not sols:
        return None
    # the same rounded (overhead, capacity, mpf) preference _rank_feasible
    # applies within a solver — raw-float overhead comparison would let
    # ~1e-7 noise hand the win back to the grid's bigger battery
    best = min(sols, key=lambda s: (round(s["energy_overhead"], 6),
                                    s["battery_capacity_j"], s["mpf_frac"]))
    best = dict(best)
    best["method"] = "hybrid"
    return best

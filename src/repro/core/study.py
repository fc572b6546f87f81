"""Declarative Study API: declare scenario axes once, run the grid as a
handful of compiled calls, query the results.

This is the public surface over the batched engine (``core/engine.py``).
A ``Study`` declares its axes — workloads (iteration timelines), fleet
sizes, mitigation configs (disabled/None entries are first-class: the
unmitigated baseline batches with everything else), utility specs, and
jitter seeds — and ``run()`` compiles the cartesian grid down to the
streaming chunked executor (``engine.stream_batches``):

  study = Study(
      workloads={"dense_2s": synthetic_timeline(2.0, 0.19),
                 "moe_3s": synthetic_timeline(3.0, 0.25, moe_notch=True)},
      fleets=[256, 512],
      configs={"none": None, "mpf90+bat": (gpu, battery)},
      specs=example_specs(job_mw=100.0),
      seeds=[0, 1],
      key=0)
  result = study.run()
  result.passing().pivot("workload", "config", "energy_overhead")

Four scale levers live in this layer:

* **Keyed randomness** — every pipeline row gets its own PRNG key
  (``fold_in(root, row)``), threaded into mitigations that consume
  randomness (telemetry noise), so noisy-telemetry sweeps see independent
  draws and the same Study with the same root key is bit-reproducible.
* **Pad-and-mask fusion** — mixed-length workloads fuse into ONE compiled
  pipeline call per mitigation-structure group (edge-padded + masked,
  exact in the valid region); the frequency/spec analysis then runs per
  true length.  ``padding="auto"`` picks this whenever lengths are mixed;
  ``"bucket"`` keeps the one-call-per-length behavior.
* **Streaming chunked execution** — ``run(stream=chunk)`` iterates the
  scenario axis in fixed-size chunks of compiled work: each chunk's
  waveforms live only on device and are reduced to metrics inside jit,
  so a 10^4–10^5-scenario grid runs in O(chunk) waveform memory and
  O(records) metric columns.  Chunked and one-shot runs are
  bit-identical; ``on_chunk`` reports progress.
* **Scenario-axis sharding** — ``shard_devices=True`` (or an explicit
  ``plan=ScenarioShardPlan``) partitions the scenario axis over a device
  mesh; it composes with chunking (each chunk is padded to a shard
  multiple), and the plan's process-local slicing makes the same code
  multi-host ready.

Results come back as a ``StudyResult``: a *columnar* record store (dict
of numpy columns, one flat record dict per scenario materialized
lazily) with filter / pivot / export helpers, plus per-row ``SimResult``
access.  The spec axis is deduplicated against the pipeline: physics
runs once per (workload, fleet, config, seed) row, each spec then judges
every row.

Beyond judging *declared* configs, ``Study.optimize()`` runs the engine's
``design`` solver (grid / gradient / hybrid) per (workload, fleet, spec)
cell and returns the solved configurations as ``designed=True`` records
in the same schema — ``result.filter(designed=True)`` separates them.
"""
from __future__ import annotations

import dataclasses
import io
import json
import time
from typing import (Callable, Dict, Iterator, List, Mapping, Optional,
                    Sequence, Tuple, Union)

import jax
import numpy as np

from repro import obs
from repro.core.campus import Campus, n_samples, n_tenants
from repro.core.engine import StreamChunk, design, stream_batches
from repro.core.hardware import DEFAULT_HW, Hardware
from repro.core.phases import IterationTimeline
from repro.core.smoothing.base import Mitigation
from repro.core.spec import UtilitySpec
from repro.core.spectrum import critical_band_report
from repro.core.waveform import WaveformConfig, aggregate, chip_waveform
from repro.core.stratosim import SimResult
from repro.ckpt.resume import SweepCheckpoint
from repro.parallel.sharding import ScenarioShardPlan

PADDING_MODES = ("auto", "pad", "bucket")

# chunk size Study.run(stream=True) picks: big enough to keep the vmapped
# pipeline efficient, small enough that O(chunk * n) device waveforms stay
# tens of MB at typical trace lengths
DEFAULT_STREAM_CHUNK = 512


# ---------------------------------------------------------------------------
# axis declarations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MitigationConfig:
    """One named point on the mitigation axis.  Either stage may be None;
    the fully-disabled config is the unmitigated baseline."""
    name: str
    device: Optional[Mitigation] = None
    rack: Optional[Mitigation] = None

    @property
    def enabled(self) -> bool:
        return self.device is not None or self.rack is not None


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One fully-resolved cell of the study grid (records align by
    ``index``; ``row`` is the pipeline row — shared across the spec axis,
    and the input to ``Study.scenario_key``)."""
    index: int
    row: int
    workload: str
    n_chips: int
    config: MitigationConfig
    spec_name: Optional[str]
    spec: Optional[UtilitySpec]
    seed: int


def _one_config(name: str, entry) -> MitigationConfig:
    if entry is None:
        return MitigationConfig(name)
    if isinstance(entry, MitigationConfig):
        return entry if entry.name == name else dataclasses.replace(entry,
                                                                    name=name)
    if isinstance(entry, (tuple, list)) and len(entry) == 2:
        return MitigationConfig(name, device=entry[0], rack=entry[1])
    raise TypeError(
        f"config {name!r}: expected None, MitigationConfig, or a "
        f"(device_mitigation, rack_mitigation) pair, got {type(entry).__name__}"
        " — a bare mitigation is ambiguous between the per-chip device stage"
        " and the aggregate rack stage")


def _as_configs(configs) -> List[MitigationConfig]:
    if configs is None:
        return [MitigationConfig("none")]
    if isinstance(configs, MitigationConfig):
        return [configs]
    if isinstance(configs, Mapping):
        return [_one_config(name, entry) for name, entry in configs.items()]
    out = []
    for i, entry in enumerate(configs):
        default = "none" if entry is None else f"config{i}"
        name = entry.name if isinstance(entry, MitigationConfig) else default
        out.append(_one_config(name, entry))
    return out


def _as_workloads(workloads) -> Dict[str, Union[IterationTimeline, Campus]]:
    if isinstance(workloads, (IterationTimeline, Campus)):
        return {"workload0": workloads}
    if isinstance(workloads, Mapping):
        return dict(workloads)
    return {f"workload{i}": tl for i, tl in enumerate(workloads)}


def _as_specs(specs) -> List[Tuple[Optional[str], Optional[UtilitySpec]]]:
    if specs is None:
        return [(None, None)]
    if isinstance(specs, UtilitySpec):
        return [(specs.name, specs)]
    if isinstance(specs, Mapping):
        return [(name, s) for name, s in specs.items()]
    return [(s.name, s) for s in specs]


def _as_seq(x) -> list:
    return list(x) if isinstance(x, (list, tuple)) else [x]


# ---------------------------------------------------------------------------
# row-level execution (the core behind Study.run and the serve layer's
# cross-query coalescing)
# ---------------------------------------------------------------------------

def _is_primary() -> bool:
    """Process 0 owns side effects (progress callbacks, checkpoint
    writes); single-process runs are always primary.  Host-side only —
    never trace process identity (repro-lint RPR007)."""
    return jax.process_index() == 0


def _structure_groups(rows) -> List[List[int]]:
    """Row indices grouped by (device, rack) pytree structure.  A None
    stage is a wildcard: baseline rows batch with the first concrete
    structure (the engine masks them off row-wise)."""
    def struct(m):
        return None if m is None else jax.tree.structure(m)

    dev_first = next((struct(c.device) for _, _, c, _ in rows
                      if c.device is not None), None)
    rack_first = next((struct(c.rack) for _, _, c, _ in rows
                       if c.rack is not None), None)
    groups: Dict[Tuple, List[int]] = {}
    for r, (_, _, c, _) in enumerate(rows):
        k = (struct(c.device) if c.device is not None else dev_first,
             struct(c.rack) if c.rack is not None else rack_first)
        groups.setdefault(k, []).append(r)
    return list(groups.values())


def run_rows(workloads: Mapping[str, Union[IterationTimeline, Campus]],
             rows: Sequence[Tuple[str, int, MitigationConfig, int]],
             specs: Sequence[Tuple[Optional[str], Optional[UtilitySpec]]],
             *, wave_cfg: Optional[WaveformConfig] = None,
             hw: Hardware = DEFAULT_HW,
             keys: Optional[Sequence] = None,
             padding: str = "auto",
             stream: Union[None, bool, int] = None,
             sample_chips: int = 64,
             keep_waveforms: bool = False,
             shard_devices: bool = False,
             plan: Optional[ScenarioShardPlan] = None,
             on_chunk: Optional[Callable[[int, int, float], None]] = None,
             levels: Optional[Dict[str, np.ndarray]] = None,
             resume: Optional[str] = None
             ) -> "StudyResult":
    """Run an explicit list of pipeline rows through the streaming chunked
    executor and return the columnar ``StudyResult``.

    This is ``Study.run`` with the row list made explicit: each row is a
    ``(workload_name, n_chips, MitigationConfig, seed)`` tuple and ``keys``
    optionally supplies one PRNG key per row.  ``Study.run`` builds its
    cartesian grid and delegates here; the serve layer's ``handle_many``
    calls it directly with the *union* row list of N coalesced queries
    (each query's rows carrying the keys that query would draw alone, so
    coalescing is bit-identical to running the queries one at a time).
    ``levels`` optionally supplies precomputed ``phase_levels`` arrays per
    workload name (the serve layer's memoized synthesis).  A workload may
    be a ``Campus``: its row's ``n_chips`` is the campus's total, and its
    levels, which depend on the seed, are always made by the engine.

    Rows are grouped by mitigation *structure* (a GPU-floor grid and a
    Firefly grid cannot stack into one batched pytree; disabled rows join
    any group); ``padding="pad"`` fuses each structure group's mixed
    lengths into one padded call stream while ``"bucket"`` streams each
    length separately (``"auto"`` pads iff lengths mix).  ``stream``
    picks the chunk size as in ``Study.run``.

    ``resume=dir`` makes the stream restartable: after each chunk the
    primary process checkpoints that chunk's records into ``dir``
    (``ckpt/resume.SweepCheckpoint``), and a rerun with the same (or an
    append-extended) row list restores the finished chunks and only
    computes the rest — bit-identical to an uninterrupted run.  A
    mismatched grid, chunk size, or corrupt checkpoint raises
    ``ResumeError`` instead of merging wrong rows.  Requires streaming
    (``stream=``) and is exclusive with ``keep_waveforms``.

    ``on_chunk`` progress is **global** and primary-only: ``done`` /
    ``total`` count pipeline rows of the whole grid (every process runs
    every chunk of the global scenario axis, so the count is identical
    on all of them), and under a multi-process plan only process 0
    emits — worker processes stay silent.  Rows restored from a resume
    dir are reported in one leading callback per call stream.
    """
    with obs.span("repro.study.run") as run_span:
        cfg = wave_cfg or WaveformConfig()
        if padding not in PADDING_MODES:
            raise ValueError(f"padding must be one of {PADDING_MODES}")
        if stream is None or stream is False:
            chunk_size = None
        elif stream is True:
            chunk_size = DEFAULT_STREAM_CHUNK
        else:
            chunk_size = int(stream)
            if chunk_size < 1:
                raise ValueError(
                    f"stream chunk size must be >= 1, got {stream}")
        rows = list(rows)
        run_span.attrs.update(rows=len(rows), chunk=chunk_size)
        specs = list(specs)
        if levels is None:
            levels = {}
        needed = {w for w, _, _, _ in rows}
        lengths = {w: n_samples(workloads[w], cfg, hw) if w not in levels
                   else len(levels[w]) for w in needed}
        row_len = [lengths[w] for w, _, _, _ in rows]
        mode = padding
        if mode == "auto":
            mode = "pad" if len(set(row_len)) > 1 else "bucket"
        if keys is not None:
            keys = list(keys)
            if len(keys) != len(rows):
                raise ValueError(
                    f"keys: got {len(keys)}, expected {len(rows)}")

        primary = _is_primary()
        ckpt = None
        if resume is not None:
            if chunk_size is None:
                raise ValueError(
                    "resume= requires streaming (pass stream=True or "
                    "stream=N): chunk boundaries are the checkpoint points")
            if keep_waveforms:
                raise ValueError(
                    "resume= does not support keep_waveforms=True — "
                    "waveforms are not checkpointed, so a resumed result "
                    "would miss them")
            ckpt = SweepCheckpoint(resume)
            ckpt.validate_or_init(
                workloads=workloads, rows=rows, specs=specs, keys=keys,
                cfg=cfg, hw=hw, mode=mode, sample_chips=sample_chips,
                chunk_size=chunk_size, write=primary)

        emit = on_chunk if (on_chunk is not None and primary) else None
        cols = _empty_columns(len(rows) * len(specs))
        waveforms = [None] * len(rows) if keep_waveforms else None
        total, done = len(rows), 0
        t0 = time.perf_counter()
        for gi, sg_rows in enumerate(_structure_groups(rows)):
            # rows of one call stack: they share a number of tenants
            by_k: Dict[int, List[int]] = {}
            for r in sg_rows:
                by_k.setdefault(n_tenants(workloads[rows[r][0]]), []).append(r)
            calls = []
            for k, k_rows in sorted(by_k.items()):
                prefix = f"g{gi}" if k == 1 else f"g{gi}-t{k}"
                if mode == "pad":
                    calls.append((f"{prefix}-pad", k_rows))
                    continue
                by_len: Dict[int, List[int]] = {}
                for r in k_rows:
                    by_len.setdefault(row_len[r], []).append(r)
                calls += [(f"{prefix}-L{L}", idx)
                          for L, idx in sorted(by_len.items())]
            for call_key, idx in calls:
                lens = {row_len[r] for r in idx}
                cs_eff = max(1, min(chunk_size or len(idx), len(idx)))
                skip = 0
                if ckpt is not None:
                    skip = ckpt.restore_call(call_key, idx, cs_eff, cols,
                                             len(specs))
                    if skip:
                        done += skip
                        if emit is not None:
                            emit(done, total, time.perf_counter() - t0)
                    if skip >= len(idx):
                        continue
                chunks = stream_batches(
                    [workloads[rows[r][0]] for r in idx],
                    [rows[r][1] for r in idx], cfg,
                    device_mitigation=[rows[r][2].device for r in idx],
                    rack_mitigation=[rows[r][2].rack for r in idx],
                    specs=[sp for _, sp in specs],
                    hw=hw, seeds=[rows[r][3] for r in idx],
                    keys=None if keys is None else [keys[r] for r in idx],
                    sample_chips=sample_chips,
                    levels=[levels.get(rows[r][0]) for r in idx],
                    pad_to=max(lens) if len(lens) > 1 else None,
                    chunk_size=cs_eff,
                    bands=True, keep_waveforms=keep_waveforms,
                    dedup=True, shard_devices=shard_devices,
                    plan=plan, skip_rows=skip)
                for ch in chunks:
                    with obs.span("repro.study.fill_chunk", rows=len(ch)):
                        _fill_chunk(cols, waveforms, rows, row_len, idx, ch,
                                    specs=specs, workloads=workloads,
                                    dt=cfg.dt)
                    if ckpt is not None and primary:
                        ckpt.save_chunk(call_key, idx, ch.start, ch.stop,
                                        cols, len(specs))
                    done += len(ch)
                    if emit is not None:
                        emit(done, total, time.perf_counter() - t0)
        return StudyResult(columns=cols, waveforms=waveforms)


def _fill_chunk(cols: Dict[str, np.ndarray], waveforms, rows, row_len,
                idx: List[int], ch: StreamChunk, *, specs, workloads,
                dt: float) -> None:
    """Write one ``StreamChunk``'s metrics into the columnar record
    store (record position = pipeline row * n_specs + spec index)."""
    S = len(specs)
    for j in range(len(ch)):
        r = idx[ch.start + j]
        wname, n_chips, config, seed = rows[r]
        L = row_len[r]
        base = {
            "row": r, "workload": wname, "n_chips": n_chips,
            "config": config.name, "seed": seed,
            "period_s": float(workloads[wname].period_s),
            "n_samples": L,
            "mean_mw": float(ch.swing["mean_w"][j]) / 1e6,
            "swing_mw": float(ch.swing["swing_w"][j]) / 1e6,
            "swing_mitigated_mw":
                float(ch.swing_mitigated["swing_w"][j]) / 1e6,
            "energy_overhead": float(ch.energy_overhead[j]),
            "paper_band_frac":
                float(ch.bands_mitigated["paper_band_0p2_3hz"][j]),
            "designed": False,
        }
        for si, (spec_name, spec) in enumerate(specs):
            p = r * S + si
            for k, v in base.items():
                cols[k][p] = v
            cols["spec"][p] = spec_name
            if spec is not None:
                report = ch.report(si, j)
                cols["spec_ok"][p] = report.ok
                cols["violations"][p] = report.violations
                # spec metrics go into numeric side columns
                # ("metrics:<name>", NaN = not measured for this record)
                # instead of a per-record dict: at 10^6 records the dict
                # overhead alone is ~300 MB of host memory
                for mk, mv in report.metrics.items():
                    mc = cols.get("metrics:" + mk)
                    if mc is None:
                        mc = cols["metrics:" + mk] = np.full(
                            len(cols["index"]), np.nan)
                    mc[p] = mv
            else:
                cols["spec_ok"][p] = None
                cols["violations"][p] = ()
        if waveforms is not None:
            waveforms[r] = {
                "t": np.arange(L) * dt,
                "dc_raw": np.asarray(ch.dc_raw[j, :L]),
                "dc_mitigated": np.asarray(ch.dc_mitigated[j, :L]),
            }


# ---------------------------------------------------------------------------
# the study
# ---------------------------------------------------------------------------

class Study:
    """A declared scenario grid; ``run()`` compiles it to the engine.

    Axes (each a singleton or a collection):
      workloads  name -> IterationTimeline or Campus (dict, sequence, or
                 one of them)
      fleets     chip counts (a campus's total)
      configs    name -> None | MitigationConfig | (device, rack) pair
      specs      None | UtilitySpec | dict name -> spec | sequence
      seeds      jitter seeds (numpy side: per-chip phase jitter draws)

    ``key`` is the PRNG root for mitigation randomness (telemetry noise):
    pipeline row ``r`` draws from ``fold_in(PRNGKey(key), r)``.  ``None``
    reverts to the legacy shared-draw behavior.  ``padding`` and
    ``shard_devices`` select the scale levers (see module docstring).
    """

    def __init__(self, workloads, *,
                 fleets: Union[int, Sequence[int]] = (512,),
                 configs=None, specs=None,
                 seeds: Union[int, Sequence[int]] = (0,),
                 wave_cfg: Optional[WaveformConfig] = None,
                 hw: Hardware = DEFAULT_HW,
                 key: Union[int, jax.Array, None] = 0,
                 padding: str = "auto",
                 shard_devices: bool = False,
                 plan: Optional[ScenarioShardPlan] = None,
                 sample_chips: int = 64,
                 keep_waveforms: bool = False):
        if padding not in PADDING_MODES:
            raise ValueError(f"padding must be one of {PADDING_MODES}")
        self.workloads = _as_workloads(workloads)
        self.fleets = [int(n) for n in _as_seq(fleets)]
        self.configs = _as_configs(configs)
        self.specs = _as_specs(specs)
        self.seeds = [int(s) for s in _as_seq(seeds)]
        self.wave_cfg = wave_cfg or WaveformConfig()
        self.hw = hw
        self.key = key
        self.padding = padding
        self.shard_devices = shard_devices
        self.plan = plan
        self.sample_chips = sample_chips
        self.keep_waveforms = keep_waveforms
        names = [c.name for c in self.configs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate config names: {names}")

    # -- declaration accessors ----------------------------------------------

    @property
    def n_rows(self) -> int:
        """Pipeline rows: the grid without the (physics-free) spec axis."""
        return (len(self.workloads) * len(self.fleets) * len(self.configs)
                * len(self.seeds))

    def __len__(self) -> int:
        return self.n_rows * len(self.specs)

    def rows(self) -> List[Tuple[str, int, MitigationConfig, int]]:
        """Pipeline rows in study order: workload-major, then fleet,
        config, seed."""
        return [(w, n, c, s)
                for w in self.workloads for n in self.fleets
                for c in self.configs for s in self.seeds]

    def scenarios(self) -> List[Scenario]:
        out = []
        for r, (w, n, c, s) in enumerate(self.rows()):
            for sn, sp in self.specs:
                out.append(Scenario(index=len(out), row=r, workload=w,
                                    n_chips=n, config=c, spec_name=sn,
                                    spec=sp, seed=s))
        return out

    def scenario_key(self, row: int) -> Optional[jax.Array]:
        """The PRNG key pipeline row ``row`` draws mitigation randomness
        from (the serial parity reference passes this to ``simulate``)."""
        if self.key is None:
            return None
        root = (self.key if isinstance(self.key, jax.Array)
                else jax.random.PRNGKey(int(self.key)))
        return jax.random.fold_in(root, row)

    def describe(self) -> str:
        lens = sorted({n_samples(tl, self.wave_cfg, self.hw)
                       for tl in self.workloads.values()})
        return (f"Study: {len(self.workloads)} workloads x "
                f"{len(self.fleets)} fleets x {len(self.configs)} configs x "
                f"{len(self.seeds)} seeds = {self.n_rows} scenarios "
                f"({len(self.specs)} specs -> {len(self)} records); "
                f"waveform lengths {lens}, padding={self.padding}")

    # -- execution ----------------------------------------------------------

    def run(self, *, padding: Optional[str] = None,
            stream: Union[None, bool, int] = None,
            on_chunk: Optional[Callable[[int, int, float], None]] = None,
            resume: Optional[str] = None
            ) -> "StudyResult":
        """Run the whole grid through the streaming chunked executor.

        Rows are first grouped by mitigation *structure* (a GPU-floor
        grid and a Firefly grid cannot stack into one batched pytree;
        disabled rows join any group); pad mode fuses each structure
        group's mixed lengths into one padded call stream while bucket
        mode streams each length separately.  Each call stream runs as
        ``engine.stream_batches`` chunks: the compiled pipeline plus
        vmapped per-(length, spec) analysis reduce every chunk to metric
        arrays on device, and only those metrics reach the host, where
        they append to the columnar ``StudyResult``.

        ``stream`` picks the chunk size: ``None``/``False`` runs each
        call stream as one chunk (every scenario's waveforms in device
        memory at once — fine up to ~10^3 scenarios), ``True`` picks
        ``DEFAULT_STREAM_CHUNK``, an int is an explicit chunk size.
        Host memory is O(records) metric columns either way; device
        memory is O(chunk * padded length).  Chunked and one-shot runs
        are bit-identical — chunking only ever adds pipeline rows that
        are sliced away.

        ``on_chunk(done, total, elapsed_s)`` (optional) is called after
        every chunk with the number of pipeline scenarios finished, the
        grid total, and the wall-clock seconds since ``run`` started —
        the progress hook long sweeps (``sweep_bench``, the serve CLI)
        surface to operators.  Progress is global (done/total over the
        whole grid) and, under a multi-process plan, emitted only on
        process 0.

        ``resume=dir`` checkpoints every finished chunk into ``dir`` and
        restores them on rerun — kill-and-restart (or append-extending
        the grid) completes bit-identically to an uninterrupted run; see
        ``run_rows``.  Requires ``stream=``.

        The body is the module-level ``run_rows`` over this study's
        cartesian row list — callers with an explicit (possibly
        heterogeneous) row set, like the serve layer's coalesced
        ``handle_many``, drive ``run_rows`` directly.
        """
        rows = self.rows()
        keys = ([self.scenario_key(r) for r in range(len(rows))]
                if self.key is not None else None)
        return run_rows(
            self.workloads, rows, self.specs,
            wave_cfg=self.wave_cfg, hw=self.hw, keys=keys,
            padding=padding or self.padding, stream=stream,
            sample_chips=self.sample_chips,
            keep_waveforms=self.keep_waveforms,
            shard_devices=self.shard_devices, plan=self.plan,
            on_chunk=on_chunk, resume=resume)

    def optimize(self, *, method: str = "hybrid",
                 seed: Optional[int] = None,
                 **design_kwargs) -> "StudyResult":
        """Run a mitigation *design* per (workload, fleet, spec) cell.

        Where ``run()`` judges the study's declared configs, ``optimize()``
        asks the engine's ``design`` solver (method = "grid" | "gradient" |
        "hybrid") for a minimal-overhead (MPF, battery) configuration that
        passes each declared spec, and returns one record per cell with
        ``designed=True`` — the same record schema as ``run()`` (so
        designed rows query/pivot/export alongside declared ones via
        ``filter(designed=True)``) plus the solved ``mpf_frac`` /
        ``battery_capacity_j``.  Cells with no feasible design come back
        as ``spec_ok=False`` with ``violations=("infeasible",)``.

        ``seed`` picks the jitter draw the design waveform uses (default:
        the study's first seed).  Extra keyword arguments flow to
        ``engine.design`` (``steps``, ``smooth_tau``, ``top_k``, ...).
        """
        cfg, hw = self.wave_cfg, self.hw
        seed = self.seeds[0] if seed is None else int(seed)
        records: List[Dict] = []
        for wname, tl in self.workloads.items():
            if isinstance(tl, Campus):
                raise ValueError(f"optimize() designs for one job; "
                                 f"{wname!r} is a Campus")
            chip = chip_waveform(tl, cfg, hw)
            for n_chips in self.fleets:
                w = aggregate(chip, n_chips, cfg, hw, seed=seed,
                              sample_chips=self.sample_chips)
                for spec_name, spec in self.specs:
                    if spec is None:
                        continue
                    sol = design(spec, w, cfg.dt, n_chips, method=method,
                                 hw=hw, **design_kwargs)
                    rec = {
                        "index": len(records),
                        "row": -1,           # no pipeline row backs a design
                        "workload": wname,
                        "n_chips": n_chips,
                        "config": f"designed[{method}]",
                        "spec": spec_name,
                        "seed": seed,
                        "period_s": float(tl.period_s),
                        "n_samples": len(w),
                        "mean_mw": float(np.mean(w)) / 1e6,
                        "swing_mw": float(w.max() - w.min()) / 1e6,
                        "designed": True,
                    }
                    if sol is None:
                        rec.update({
                            "swing_mitigated_mw": rec["swing_mw"],
                            "energy_overhead": 0.0,
                            "paper_band_frac": None,
                            "spec_ok": False,
                            "violations": ("infeasible",),
                            "metrics": {},
                            "mpf_frac": None,
                            "battery_capacity_j": None,
                        })
                    else:
                        mit = np.asarray(sol["mitigated"])
                        rec.update({
                            "swing_mitigated_mw":
                                float(mit.max() - mit.min()) / 1e6,
                            "energy_overhead": float(sol["energy_overhead"]),
                            "paper_band_frac": float(critical_band_report(
                                mit, cfg.dt)["paper_band_0p2_3hz"]),
                            "spec_ok": sol["report"].ok,
                            "violations": sol["report"].violations,
                            "metrics": sol["report"].metrics,
                            "mpf_frac": sol["mpf_frac"],
                            "battery_capacity_j": sol["battery_capacity_j"],
                        })
                    records.append(rec)
        return StudyResult(records=records)

    # row grouping by mitigation structure (module-level; kept as a
    # staticmethod alias for existing callers)
    _structure_groups = staticmethod(_structure_groups)



# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

# the columnar record schema (field order = record dict key order)
_COLUMN_DTYPES = (
    ("index", np.int64), ("row", np.int64), ("workload", object),
    ("n_chips", np.int64), ("config", object), ("spec", object),
    ("seed", np.int64), ("period_s", np.float64), ("n_samples", np.int64),
    ("mean_mw", np.float64), ("swing_mw", np.float64),
    ("swing_mitigated_mw", np.float64), ("energy_overhead", np.float64),
    ("paper_band_frac", np.float64), ("designed", np.bool_),
    ("spec_ok", object), ("violations", object),
)


def _empty_columns(n: int) -> Dict[str, np.ndarray]:
    cols = {k: np.empty(n, dtype=dt) for k, dt in _COLUMN_DTYPES}
    cols["index"] = np.arange(n, dtype=np.int64)
    return cols


def _to_py(v):
    """numpy scalar -> the python scalar the list-of-dicts records held."""
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return v


class StudyResult:
    """Flat scenario records with query helpers, stored columnar.

    Each record is one (workload, fleet, config, seed, spec) cell:
    identity fields, swing/overhead/band metrics, and — when a spec was
    declared — ``spec_ok`` / ``violations`` / the spec's metric dict.
    ``designed`` distinguishes ``Study.optimize()`` records (solved
    configurations, carrying ``mpf_frac``/``battery_capacity_j``) from
    ``run()`` records (declared configurations); ``filter(designed=True)``
    selects them.  ``waveforms`` (when the study kept them) is indexed by
    ``record["row"]``.

    Storage is a dict of per-field numpy columns (``columns=``; how the
    streaming executor appends chunk after chunk in O(records) memory —
    numeric fields cost 8 bytes per record instead of a dict slot);
    record *dicts* are materialized lazily per row (``result[i]``,
    iteration, ``.records``) and are bit-identical to the list-of-dicts
    form this class used to hold.  Constructing from ``records=`` (a
    list of dicts, e.g. ``optimize()`` output or concatenated results)
    keeps the list verbatim — both representations answer the same
    query API.
    """

    def __init__(self, records: Optional[List[Dict]] = None,
                 waveforms: Optional[List[Dict]] = None, *,
                 columns: Optional[Dict[str, np.ndarray]] = None):
        if columns is not None and records is not None:
            raise ValueError("pass records= or columns=, not both")
        self._cols = columns
        self._rows = None if columns is not None else list(records or [])
        self._n = (len(next(iter(columns.values()))) if columns
                   else len(self._rows))
        self.waveforms = waveforms

    # -- record materialization ---------------------------------------------

    def _row(self, i: int) -> Dict:
        if self._rows is not None:
            return self._rows[i]
        rec = {k: _to_py(col[i]) for k, col in self._cols.items()
               if not k.startswith("metrics:")}
        # spec metrics are stored as numeric side columns (NaN = this
        # record's spec did not measure that key); the per-record dict
        # materializes here, not in the store
        rec["metrics"] = {k[8:]: _to_py(col[i])
                          for k, col in self._cols.items()
                          if k.startswith("metrics:")
                          and not np.isnan(col[i])}
        return rec

    @property
    def records(self) -> List[Dict]:
        """All records as plain dicts (materialized from the columns on
        first access — the O(records) dict cost is only paid by callers
        that ask for it).  The returned list becomes the authoritative
        storage, like the old list-of-dicts field: callers that mutate
        it see coherent ``len``/``filter``/iteration afterwards."""
        if self._rows is None:
            self._rows = [self._row(i) for i in range(self._n)]
            self._cols = None
        return self._rows

    def _field(self, name: str):
        """One field's values across records, without building dicts."""
        if self._rows is not None:
            return [r.get(name) for r in self._rows]
        col = self._cols.get(name)
        if col is None:
            if name == "metrics":
                m = {k[8:]: c for k, c in self._cols.items()
                     if k.startswith("metrics:")}
                if m:
                    return [{mk: _to_py(c[i]) for mk, c in m.items()
                             if not np.isnan(c[i])}
                            for i in range(len(self))]
            return [None] * len(self)
        return col

    def _subset(self, keep: Sequence[int]) -> "StudyResult":
        if self._rows is not None:
            return StudyResult([self._rows[i] for i in keep], self.waveforms)
        idx = np.asarray(keep, dtype=np.int64)
        return StudyResult(columns={k: col[idx]
                                    for k, col in self._cols.items()},
                           waveforms=self.waveforms)

    def __len__(self) -> int:
        return len(self._rows) if self._rows is not None else self._n

    def __iter__(self) -> Iterator[Dict]:
        return (self._row(i) for i in range(len(self)))

    def __getitem__(self, i: int) -> Dict:
        return self._row(i)

    # -- querying -----------------------------------------------------------

    def filter(self, **where) -> "StudyResult":
        """Records whose field equals the given value (or is contained in
        it, when a list/tuple/set is given): ``filter(workload="moe_3s",
        config=["none", "mpf90"])``."""
        fields = {k: self._field(k) for k in where}
        keep = []
        for i in range(len(self)):
            for k, v in where.items():
                got = _to_py(fields[k][i])
                if isinstance(v, (list, tuple, set, frozenset)):
                    if got not in v:
                        break
                elif got != v:
                    break
            else:
                keep.append(i)
        return self._subset(keep)

    def passing(self) -> "StudyResult":
        ok = self._field("spec_ok")
        return self._subset([i for i in range(len(self)) if ok[i]])

    def failing(self) -> "StudyResult":
        ok = self._field("spec_ok")
        return self._subset([i for i in range(len(self)) if ok[i] is False])

    def unique(self, field: str) -> List:
        seen: Dict = {}
        for v in self._field(field):
            seen.setdefault(_to_py(v), None)
        return list(seen)

    def best(self, by: str = "energy_overhead",
             among_passing: bool = True) -> Optional[Dict]:
        """The minimal-``by`` record (among spec-passing ones by default)."""
        pool = self.passing() if among_passing else self
        if not len(pool):
            return None
        vals = pool._field(by)
        return pool._row(int(np.argmin([_to_py(v) for v in vals])))

    def passing_configs(self, **where) -> List[str]:
        """Config names every matching scenario of which passes its spec,
        ordered by worst-case energy overhead (the serve-path answer)."""
        sub = self.filter(**where)
        configs, oks = sub._field("config"), sub._field("spec_ok")
        overheads = sub._field("energy_overhead")
        worst: Dict[str, float] = {}
        ok: Dict[str, bool] = {}
        for i in range(len(sub)):
            c = configs[i]
            ok[c] = ok.get(c, True) and bool(oks[i])
            worst[c] = max(worst.get(c, -np.inf), overheads[i])
        return sorted((c for c, good in ok.items() if good),
                      key=lambda c: worst[c])

    def pivot(self, index: str, columns: str,
              values: str = "spec_ok") -> Dict:
        """Nested dict table: ``pivot("workload", "config",
        "energy_overhead")[w][c]``.  Cells with several matching records
        keep the first (slice with ``filter`` for one record per cell)."""
        idx_v, col_v = self._field(index), self._field(columns)
        val_v = self._field(values)
        out: Dict = {}
        for i in range(len(self)):
            out.setdefault(_to_py(idx_v[i]), {}).setdefault(
                _to_py(col_v[i]), _to_py(val_v[i]))
        return out

    # -- export -------------------------------------------------------------

    def table(self, columns: Optional[Sequence[str]] = None) -> str:
        """Records as a markdown table (spec verdicts rendered PASS/fail)."""
        if not self.records:
            return "(no records)"
        columns = list(columns or [
            "workload", "n_chips", "config", "spec", "seed", "swing_mw",
            "swing_mitigated_mw", "energy_overhead", "spec_ok"])

        def cell(r, c):
            v = r.get(c)
            if c == "spec_ok" and v is not None:
                return "PASS" if v else ",".join(r["violations"]) or "FAIL"
            if isinstance(v, float):
                return f"{v:.4g}"
            return str(v)

        lines = ["| " + " | ".join(columns) + " |",
                 "|" + "---|" * len(columns)]
        lines += ["| " + " | ".join(cell(r, c) for c in columns) + " |"
                  for r in self.records]
        return "\n".join(lines)

    def to_records(self) -> List[Dict]:
        """JSON-safe copies (tuples -> lists) of every record."""
        return json.loads(self.to_json())

    def to_json(self, path: Optional[str] = None) -> str:
        text = json.dumps(self.records, indent=2, default=list)
        if path is not None:
            with open(path, "w") as fh:
                fh.write(text + "\n")
        return text

    def to_csv(self, path: Optional[str] = None) -> str:
        """Scalar record fields as CSV (nested metric dicts are flattened
        with a ``metrics.`` prefix)."""
        import csv

        rows = []
        for r in self.records:
            flat = {k: v for k, v in r.items()
                    if not isinstance(v, (dict, tuple, list))}
            flat["violations"] = ";".join(r.get("violations", ()))
            for k, v in r.get("metrics", {}).items():
                flat[f"metrics.{k}"] = v
            rows.append(flat)
        fields = list(dict.fromkeys(k for row in rows for k in row))
        buf = io.StringIO()
        writer = csv.DictWriter(buf, fieldnames=fields)
        writer.writeheader()
        writer.writerows(rows)
        text = buf.getvalue()
        if path is not None:
            with open(path, "w") as fh:
                fh.write(text)
        return text

    def sim_result(self, row: int) -> SimResult:
        """Rebuild the per-row ``SimResult`` waveform view (requires the
        study to have been run with ``keep_waveforms=True``)."""
        if self.waveforms is None:
            raise ValueError("run the Study with keep_waveforms=True")
        w = self.waveforms[row]
        rec = next(r for r in self.records if r["row"] == row)
        return SimResult(
            t=w["t"], dc_raw=w["dc_raw"], dc_mitigated=w["dc_mitigated"],
            chip_raw=None, chip_mitigated=None,
            energy_overhead=rec["energy_overhead"],
            swing={}, swing_mitigated={}, bands={}, bands_mitigated={},
            spec_report=None, aux={})

"""Jit'd wrappers: telemetry trace -> critical-bin amplitudes.

``bin_power`` — non-overlapping windows (coarse streaming granularity).
``sliding_bin_power`` — every-sample sliding window on the streaming
lane-major v2 Pallas kernel: the telemetry backstop's product hot path.
Pass ``carry=`` (from ``sliding_carry_init``) to run the same monitor
*incrementally* over a chunked stream: the call consumes one chunk,
returns ``(amps, carry')``, and the concatenated chunked outputs are
bit-identical to one offline call on the concatenated trace — the
control plane's online detector is built on this.  Both directions run
the *same* Pallas program: the v2 kernels stream their prefix-state
tables in and out, so a chunked caller resumes from exactly the state
the offline kernel would hold.

``sliding_monitor_fused`` — the fused monitor: amplitudes are reduced to
the per-sample worst bin and its escalation class *inside* the kernel
(``core.telemetry.escalation_classify`` semantics), the class stream
runs through the blocked ``core.telemetry.escalation_scan``, and the
``[n, K]`` amplitude matrix never exists.  The jnp mirror
(``use_pallas=False``) is the structurally identical oracle the tests
pin bitwise.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.goertzel.goertzel import (bin_amps_lane_major,
                                             goertzel_pallas,
                                             sliding_goertzel_pallas,
                                             sliding_goertzel_v2_pallas,
                                             sliding_monitor_pallas)

#: sublane multiple the v2 lane-major tables pad K up to (f32 tile is
#: (8, 128); rows k..KP-1 are zero and never read by the kernels)
SUBLANES = 8


@functools.lru_cache(maxsize=None)
def interpret_default() -> bool:
    """Compile the Pallas kernels only on real TPU backends; everywhere
    else (CPU CI, tests, the vmapped engine) they run in interpret mode."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("win", "block_w", "interpret"))
def bin_power(x: jax.Array, dt: float, freqs: jax.Array, *, win: int,
              block_w: int = 8, interpret: bool = False) -> jax.Array:
    """x: [n] power samples -> [ceil(n/win), K] bin amplitudes
    (non-overlapping windows).  The trailing partial window (``n % win``
    samples) is zero-padded after its own DC removal and normalized by
    its true sample count, so the tail of the trace is monitored too
    instead of being silently dropped."""
    n = x.shape[0]
    W = -(-n // win)
    pad_n = W * win - n
    if pad_n:
        x = jnp.concatenate([x, jnp.zeros((pad_n,), x.dtype)])
    windows = x.reshape(W, win)
    counts = np.full((W,), float(win), np.float32)
    if pad_n:
        counts[-1] = float(win - pad_n)
    counts = jnp.asarray(counts)
    valid = jnp.arange(win)[None, :] < counts[:, None]
    # remove the per-window DC component: near-DC resonator states otherwise
    # grow to win*mean and the terminal power formula cancels catastrophically
    # in f32 (the bins of interest are >= 0.1 Hz, unaffected by this).
    # Means use the true sample counts; pad samples stay exactly zero.
    means = (jnp.sum(jnp.where(valid, windows, 0.0), axis=1, keepdims=True)
             / counts[:, None])
    windows = jnp.where(valid, windows - means, 0.0)
    pad = (-W) % block_w
    if pad:
        windows = jnp.concatenate(
            [windows, jnp.zeros((pad, win), windows.dtype)], axis=0)
    coef = 2.0 * jnp.cos(2 * jnp.pi * jnp.asarray(freqs) * dt)
    out = goertzel_pallas(windows, coef, block_w=block_w, interpret=interpret)
    # the kernel normalizes by 2/win; partial windows rescale to 2/count
    return out[:W] * (float(win) / counts)[:, None]


@functools.lru_cache(maxsize=None)
def _phase_tables(freqs: Tuple[float, ...], dt: float, win: int):
    """Host-float64 phase tables in the v1 (bin-minor) ``[win, K]``
    layout.  Only the benchmark A/B baseline (``sliding_goertzel_pallas``
    in ``benchmarks/kernels_bench.py``) still consumes this; product
    paths use ``_phase_tables_v2``."""
    omega = 2.0 * np.pi * np.asarray(freqs, np.float64) * dt
    p = np.arange(win, dtype=np.float64)[:, None]
    cosp = np.cos(omega[None, :] * p).astype(np.float32)
    sinp = np.sin(omega[None, :] * p).astype(np.float32)
    rot = np.stack([np.cos(omega * win),
                    np.sin(omega * win)]).astype(np.float32)
    return cosp, sinp, rot


@functools.lru_cache(maxsize=None)
def _phase_tables_v2(freqs: Tuple[float, ...], dt: float, win: int):
    """Host-float64 sliding-Goertzel phase tables in the lane-major v2
    layout, shared by the offline full-trace path and the online carry
    path so both consume bitwise identical operands: ``cosp``/``sinp``
    ``[KP, win]`` (K sublane-padded to ``SUBLANES``; pad rows zero and
    unread) and the ``[KP, 2]`` segment rotation ``[cos, sin]`` of
    ``omega_k * win``.  Returned as host numpy (jnp.asarray at the use
    site) so the cache never captures jit-trace constants."""
    k = len(freqs)
    kp = -(-k // SUBLANES) * SUBLANES
    omega = 2.0 * np.pi * np.asarray(freqs, np.float64) * dt
    p = np.arange(win, dtype=np.float64)[None, :]
    cosp = np.zeros((kp, win), np.float32)
    sinp = np.zeros((kp, win), np.float32)
    rott = np.zeros((kp, 2), np.float32)
    cosp[:k] = np.cos(omega[:, None] * p)
    sinp[:k] = np.sin(omega[:, None] * p)
    rott[:k, 0] = np.cos(omega * win)
    rott[:k, 1] = np.sin(omega * win)
    return cosp, sinp, rott


@functools.lru_cache(maxsize=None)
def _phase_tables_v2_dev(freqs: Tuple[float, ...], dt: float, win: int):
    """Device-resident ``_phase_tables_v2``, for the concrete online
    carry paths: one device_put per (freqs, dt, win) instead of three
    per tick (re-uploading the [KP, win] tables dominated the per-tick
    detector cost).  Traced callers keep the host variant so jit caches
    never capture live buffers."""
    return tuple(jnp.asarray(t) for t in _phase_tables_v2(freqs, dt, win))


def _params_row(threshold, release, n, seg0) -> jax.Array:
    """The kernels' [1, 4] runtime-parameter row
    [threshold, release, n, seg0] (all f32; threshold may be traced).
    Concrete inputs build on the host — the online carry path calls this
    once per segment, and four eager jnp ops per tick are measurable."""
    vals = (threshold, release, n, seg0)
    if not any(isinstance(v, jax.core.Tracer) for v in vals):
        return np.asarray(vals, np.float32).reshape(1, 4)
    return jnp.stack([jnp.asarray(v, jnp.float32)
                      for v in vals]).reshape(1, 4)


@jax.jit
def trace_mean(x: jax.Array) -> jax.Array:
    """The monitor's f32 DC operating point: the first sample plus the
    mean of the residual about it.  The offline monitor removes exactly
    this value in-graph; pass ``float(trace_mean(x_full))`` to
    ``sliding_carry_init(mean=...)`` when chunked output must match the
    offline call bitwise.  A plain f32 mean of a 5e8 W trace is off by
    hundreds of watts, and by a different amount in every program that
    reduces it in another order; the residual's mean is at oscillation
    scale, so its rounding stays far below one ulp of the operating
    point, and a constant trace gives exactly its own value."""
    x = jnp.asarray(x, jnp.float32)
    return x[0] + jnp.mean(x - x[0])


@functools.partial(jax.jit,
                   static_argnames=("dt", "freqs", "win", "block_s",
                                    "interpret"))
def _sliding_bin_power_full(x: jax.Array, dt: float, freqs, *, win: int,
                            block_s: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Whole-trace sliding monitor (see ``sliding_bin_power``)."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    xc = x - trace_mean(x)
    S = -(-n // win)
    if block_s <= 0:
        # a few segments per grid cell amortizes cell overhead while the
        # per-bin [block_s, win] intermediates stay VMEM-sized
        block_s = max(1, min(8, S))
    S_pad = S + ((-S) % block_s)
    pad_n = S_pad * win - n
    if pad_n:
        xc = jnp.concatenate([xc, jnp.zeros((pad_n,), jnp.float32)])
    xseg = xc.reshape(S_pad, win)

    cosp, sinp, rott = (jnp.asarray(t) for t in
                        _phase_tables_v2(tuple(freqs), dt, win))
    zeros = jnp.zeros_like(cosp)
    amps, _, _ = sliding_goertzel_v2_pallas(
        xseg, cosp, sinp, rott, _params_row(0.0, 0.0, n, 0.0), zeros, zeros,
        k=len(freqs), block_s=block_s, interpret=interpret)
    # the kernel applies both the 2/win normalization and the warm-up
    # ramp (core.telemetry.warmup_scale) in VMEM
    return jnp.stack(amps, axis=-1).reshape(S_pad * win, -1)[:n]


class SlidingCarry(NamedTuple):
    """Explicit cross-chunk state of the sliding-Goertzel monitor.

    ``seg`` is the *window residue*: the current (mean-removed,
    zero-padded) window-sized segment buffer with ``fill`` valid samples;
    ``prev_re``/``prev_im`` are the *rotation-phase state*: the previous
    segment's modulated prefix tables (lane-major ``[KP, win]`` — the
    exact tables the v2 kernel streams in and out).  ``offset`` counts
    samples already emitted (global index of the next sample); ``mean``
    is the DC operating point removed from every sample — pass the trace
    mean for offline parity, the known fleet operating point for live
    streams.  Treat as opaque: build with ``sliding_carry_init``, thread
    through ``sliding_bin_power(..., carry=)``.
    """
    offset: int
    fill: int
    seg: jax.Array        # [win] f32
    prev_re: jax.Array    # [KP, win] f32
    prev_im: jax.Array    # [KP, win] f32
    mean: float


def sliding_carry_init(dt: float, freqs, *, win: int,
                       mean: float = 0.0) -> SlidingCarry:
    """Fresh monitor state for chunked ``sliding_bin_power`` calls.

    ``mean`` is the DC level subtracted from every incoming sample.  For
    bit-parity with the offline path on a known trace, pass
    ``float(trace_mean(x_full))``; for live streams, the fleet's known
    operating point (the monitor's AC amplitudes are insensitive to
    small DC error — it shifts only the near-DC bins).
    """
    k = len(tuple(freqs))
    kp = -(-k // SUBLANES) * SUBLANES
    zeros = jnp.zeros((kp, win), jnp.float32)
    return SlidingCarry(offset=0, fill=0,
                        seg=jnp.zeros((win,), jnp.float32),
                        prev_re=zeros, prev_im=zeros,
                        mean=float(np.float32(mean)))


@functools.partial(jax.jit, static_argnames=("win", "k", "interpret"))
def _sliding_seg_v2(seg, prev_re, prev_im, cosp, sinp, rott, seg0, *,
                    win: int, k: int, interpret: bool):
    """One segment of the sliding monitor *on the v2 Pallas kernel*
    (single-segment grid, carried prefix state streamed in/out) — the
    online carry path runs the same kernel program as the offline call,
    so chunked amplitudes are bit-identical by construction.  ``seg0``
    is the segment's global index (f32).  Returns
    (scaled [win, K] amplitudes, new prefix tables [KP, win] x2)."""
    amps, nre, nim = sliding_goertzel_v2_pallas(
        seg[None], cosp, sinp, rott, _params_row(0.0, 0.0, 0.0, seg0),
        prev_re, prev_im, k=k, block_s=1, interpret=interpret)
    return jnp.stack(amps, axis=-1)[0], nre, nim


def _sliding_bin_power_carry(x, dt: float, freqs, *, win: int,
                             carry: SlidingCarry, interpret: bool):
    """Consume one concrete chunk, emitting its [m, K] amplitudes and the
    advanced carry.  A partial segment is recomputed on its zero-padded
    window buffer each call (cumsum prefixes at index b are unaffected by
    the zero tail), and only the newly-valid rows are emitted — so uneven
    tick sizes, ticks smaller than one window, and a final partial tick
    all reproduce the offline output bitwise."""
    cosp, sinp, rott = _phase_tables_v2_dev(tuple(freqs), dt, win)
    K = len(tuple(freqs))
    xc = np.asarray(x, np.float32) - np.float32(carry.mean)
    m = xc.shape[0]
    offset, fill = carry.offset, carry.fill
    seg = np.asarray(carry.seg)
    prev_re, prev_im = carry.prev_re, carry.prev_im
    outs = []
    pos = 0
    while pos < m:
        take = min(win - fill, m - pos)
        if take:
            seg = seg.copy()
            seg[fill:fill + take] = xc[pos:pos + take]
        new_fill = fill + take
        seg0 = (offset - fill) // win         # global index of the segment
        out, pr, pi = _sliding_seg_v2(seg, prev_re, prev_im,
                                      cosp, sinp, rott, np.float32(seg0),
                                      win=win, k=K, interpret=interpret)
        outs.append(np.asarray(out)[fill:new_fill])
        if new_fill == win:                   # segment complete: hop
            prev_re, prev_im = pr, pi
            seg = np.zeros((win,), np.float32)
            fill = 0
        else:
            fill = new_fill
        offset += take
        pos += take
    amps = (np.concatenate(outs, axis=0) if outs
            else np.zeros((0, K), np.float32))
    new_carry = SlidingCarry(offset=offset, fill=fill, seg=seg,
                             prev_re=prev_re, prev_im=prev_im,
                             mean=carry.mean)
    return amps, new_carry


def sliding_bin_power(x, dt: float, freqs, *, win: int, block_s: int = 0,
                      interpret: Optional[bool] = None,
                      carry: SlidingCarry = None):
    """x: [n] power samples -> [n, K] every-sample sliding-window bin
    amplitudes via the streaming lane-major v2 Pallas kernel (``freqs``
    must be a hashable static sequence of Hz; ``dt``/``win`` static).

    Semantics match the corrected float64 oracle
    (``ref.sliding_bin_power_ref``): the trace's DC operating point
    (``trace_mean``) is removed before accumulation — see ``ref.py`` for
    the numerics rationale — and the first ``win - 1`` outputs are
    partial-window estimates normalized by the true sample count (the
    warm-up ramp is applied *in-kernel*).
    The phase tables are built in float64 on the host, so bin phases
    stay exact at any trace length.  ``block_s=0`` picks a segment block
    size automatically; ``interpret=None`` compiles on TPU backends and
    interprets elsewhere.

    With ``carry=`` (a ``SlidingCarry`` from ``sliding_carry_init``), x
    is one *chunk* of a longer stream: the call returns
    ``(amps [len(x), K], carry')`` instead, resuming mid-window from the
    carried residue/rotation state rather than re-priming — chunked
    outputs concatenate bit-identically to one offline call on the
    concatenated trace (given ``mean=trace_mean(full)``), because both
    run the same kernel program with the same streamed state.  The
    carry path requires concrete (non-traced) input.
    """
    if interpret is None:
        interpret = interpret_default()
    if carry is None:
        return _sliding_bin_power_full(x, dt, tuple(freqs), win=win,
                                       block_s=block_s, interpret=interpret)
    return _sliding_bin_power_carry(x, dt, tuple(freqs), win=win,
                                    carry=carry, interpret=interpret)


# ---------------------------------------------------------------------------
# fused monitor: worst bin + escalation class in-kernel, blocked escalation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("win", "k"))
def _monitor_scan_jnp(xseg, cosp, sinp, rott, params, re0, im0, *,
                      win: int, k: int):
    """jnp mirror of ``sliding_monitor_pallas``: one ``lax.scan`` over
    segments whose body runs the kernel's own core
    (``bin_amps_lane_major``, with ``jnp.roll`` for the lane rolls), so
    it is *bitwise* equal to the interpret-mode kernel at any
    ``block_s`` (pinned in tests/test_kernels.py)."""
    S = xseg.shape[0]
    kp = cosp.shape[0]
    thr, rel, n, seg0 = (params[0, i] for i in range(4))
    pos = jax.lax.broadcasted_iota(jnp.float32, (1, win), 1)

    def seg_body(carry, inp):
        pre_re, pre_im = carry
        xs, sidx = inp
        x = xs[None]                                          # [1, win]
        idx = (seg0 + sidx) * win + pos
        scale = float(win) / jnp.minimum(idx + 1.0, float(win))
        live = (idx >= win - 1) & (idx < n)
        worst = None
        nre, nim, ppk = [], [], []
        for _, amp, last_r, last_i in bin_amps_lane_major(
                x, cosp, sinp, rott, pre_re, pre_im, scale, win=win, k=k,
                roll=jnp.roll):
            ppk.append(jnp.where(live, amp, 0.0).max(axis=1))
            worst = amp if worst is None else jnp.maximum(worst, amp)
            nre.append(last_r)
            nim.append(last_i)
        hit = (worst > thr) & live
        clear = jnp.logical_not((worst > rel) & live)
        band = jnp.logical_and(~hit, ~clear)
        cls = (2 * hit.astype(jnp.int32)
               + band.astype(jnp.int32)).astype(jnp.int8)
        peaks = jnp.concatenate(ppk + [jnp.zeros((kp - k,), jnp.float32)])
        new_re = jnp.concatenate(nre + [pre_re[k:]], axis=0)
        new_im = jnp.concatenate(nim + [pre_im[k:]], axis=0)
        return (new_re, new_im), (worst[0], cls[0], peaks)

    (nre, nim), (worsts, clss, peaks) = jax.lax.scan(
        seg_body, (re0, im0),
        (xseg, jnp.arange(S, dtype=jnp.float32)))
    return worsts, clss, peaks, nre, nim


class MonitorCarry(NamedTuple):
    """Cross-chunk state of the *fused* monitor: the sliding-Goertzel
    carry plus the escalation machine's ``(level, above, below, detect)``
    counters.  Build with ``monitor_carry_init``, thread through
    ``sliding_monitor_fused(..., carry=)``."""
    sliding: SlidingCarry
    esc: Tuple[jax.Array, ...]


def monitor_carry_init(dt: float, freqs, *, win: int,
                       mean: float = 0.0) -> MonitorCarry:
    """Fresh fused-monitor state for chunked ``sliding_monitor_fused``
    calls (see ``sliding_carry_init`` for ``mean``)."""
    from repro.core.telemetry import escalation_init  # lazy: import cycle
    return MonitorCarry(
        sliding=sliding_carry_init(dt, freqs, win=win, mean=mean),
        esc=escalation_init())


@functools.partial(jax.jit, static_argnames=("win", "k", "interpret",
                                             "use_pallas"))
def _monitor_seg_v2(seg, prev_re, prev_im, cosp, sinp, rott, params, *,
                    win: int, k: int, interpret: bool,
                    use_pallas: bool):
    """One segment of the fused monitor (single-segment grid) — the
    online fused path.  Returns (worst [win], cls [win], peaks [KP],
    new prefix tables)."""
    if use_pallas:
        worst, cls, peaks, nre, nim = sliding_monitor_pallas(
            seg[None], cosp, sinp, rott, params, prev_re, prev_im,
            k=k, block_s=1, interpret=interpret)
    else:
        worst, cls, peaks, nre, nim = _monitor_scan_jnp(
            seg[None], cosp, sinp, rott, params, prev_re, prev_im,
            win=win, k=k)
    return worst[0], cls[0], peaks[0], nre, nim


@functools.partial(jax.jit, static_argnames=("win", "k"))
def _amps_at(nre, nim, prev_re, prev_im, rott, b, idx, *, win: int, k: int):
    """Per-bin sliding amplitudes at one sample, recombined from the
    fused kernel's streamed prefix state: ``nre``/``nim`` are the
    *current* segment's prefix tables (the kernel's state output),
    ``prev_re``/``prev_im`` the previous segment's, ``b`` the in-segment
    position and ``idx`` the global sample index.  O(K) work — this is
    how the fused online detector reports per-bin amplitudes without
    materializing any [win, K] block."""
    from repro.core.telemetry import warmup_scale  # lazy: import cycle
    pr = nre[:k, b]
    pi = nim[:k, b]
    dr = prev_re[:k, win - 1] - prev_re[:k, b]
    di = prev_im[:k, win - 1] - prev_im[:k, b]
    rr = rott[:k, 0]
    ri = rott[:k, 1]
    mr = pr + rr * dr - ri * di
    mi = pi + rr * di + ri * dr
    amp = (2.0 / win) * jnp.sqrt(mr * mr + mi * mi)
    return amp * warmup_scale(idx, win)


@functools.partial(jax.jit, static_argnames=("win", "k", "sustain_n",
                                             "cool_n", "max_level"))
def _monitor_tail(cls_cat, idx0, esc, nre, nim, prev_re, prev_im, rott,
                  b, idx, *, win: int, k: int, sustain_n: int, cool_n: int,
                  max_level: int):
    """The online chunk's post-kernel tail in one dispatch: advance the
    blocked escalation machine over the chunk's class stream and
    recombine the last sample's per-bin amplitudes from the streamed
    prefix state (the per-tick serve path is dispatch-bound on CPU, so
    the two steps share a jit)."""
    from repro.core.telemetry import escalation_scan  # lazy: import cycle
    esc2, levels = escalation_scan(cls_cat, idx0, esc, sustain_n=sustain_n,
                                   cool_n=cool_n, max_level=max_level)
    amps = _amps_at(nre, nim, prev_re, prev_im, rott, b, idx, win=win, k=k)
    return esc2, levels, amps


@functools.partial(jax.jit,
                   static_argnames=("dt", "freqs", "win", "sustain_n",
                                    "cool_n", "max_level", "block_s",
                                    "interpret", "use_pallas"))
def _sliding_monitor_full(x, threshold, release, dt: float, freqs, *,
                          win: int, sustain_n: int, cool_n: int,
                          max_level: int, block_s: int, interpret: bool,
                          use_pallas: bool):
    """Whole-trace fused monitor (see ``sliding_monitor_fused``)."""
    from repro.core.telemetry import (escalation_init,  # lazy: import cycle
                                      escalation_scan)
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    k = len(freqs)
    xc = x - trace_mean(x)
    S = -(-n // win)
    if block_s <= 0:
        block_s = max(1, min(8, S))
    S_pad = S + ((-S) % block_s)
    pad_n = S_pad * win - n
    if pad_n:
        xc = jnp.concatenate([xc, jnp.zeros((pad_n,), jnp.float32)])
    xseg = xc.reshape(S_pad, win)
    cosp, sinp, rott = (jnp.asarray(t) for t in
                        _phase_tables_v2(tuple(freqs), dt, win))
    zeros = jnp.zeros_like(cosp)
    params = _params_row(threshold, release, n, 0.0)
    if use_pallas:
        worst2, cls2, peaks2, _, _ = sliding_monitor_pallas(
            xseg, cosp, sinp, rott, params, zeros, zeros,
            k=k, block_s=block_s, interpret=interpret)
    else:
        worst2, cls2, peaks2, _, _ = _monitor_scan_jnp(
            xseg, cosp, sinp, rott, params, zeros, zeros, win=win, k=k)
    worst = worst2.reshape(-1)[:n]
    cls = cls2.reshape(-1)[:n]
    (_, _, _, detect), levels = escalation_scan(
        cls, jnp.int32(0), escalation_init(),
        sustain_n=sustain_n, cool_n=cool_n, max_level=max_level)
    return worst, levels, detect, peaks2[:S, :k]


def _sliding_monitor_carry(x, threshold, release, dt: float, freqs, *,
                           win: int, sustain_n: int, cool_n: int,
                           max_level: int, interpret: bool,
                           use_pallas: bool, carry: MonitorCarry):
    """Consume one concrete chunk through the fused monitor (same
    recompute-partial-segment strategy as ``_sliding_bin_power_carry``).
    Returns ``(worst [m], levels [m], amps_last [K], carry')`` where
    ``amps_last`` are the per-bin amplitudes at the chunk's final sample
    (recombined from the streamed prefix state)."""
    cosp, sinp, rott = _phase_tables_v2_dev(tuple(freqs), dt, win)
    K = len(tuple(freqs))
    sl = carry.sliding
    xc = np.asarray(x, np.float32) - np.float32(sl.mean)
    m = xc.shape[0]
    offset0 = sl.offset
    offset, fill = sl.offset, sl.fill
    seg = np.asarray(sl.seg)
    prev_re, prev_im = sl.prev_re, sl.prev_im
    worsts, clss = [], []
    last = None                     # (nre, nim, prev_re, prev_im, b, seg0)
    pos = 0
    while pos < m:
        take = min(win - fill, m - pos)
        if take:
            seg = seg.copy()
            seg[fill:fill + take] = xc[pos:pos + take]
        new_fill = fill + take
        seg0 = (offset - fill) // win
        params = _params_row(threshold, release, np.inf, seg0)
        worst, cls, _, pr, pi = _monitor_seg_v2(
            seg, prev_re, prev_im, cosp, sinp, rott, params,
            win=win, k=K, interpret=interpret, use_pallas=use_pallas)
        worsts.append(np.asarray(worst)[fill:new_fill])
        clss.append(np.asarray(cls)[fill:new_fill])
        last = (pr, pi, prev_re, prev_im, new_fill - 1, seg0)
        if new_fill == win:                   # segment complete: hop
            prev_re, prev_im = pr, pi
            seg = np.zeros((win,), np.float32)
            fill = 0
        else:
            fill = new_fill
        offset += take
        pos += take
    if worsts:
        worst_cat = np.concatenate(worsts)
        cls_cat = np.concatenate(clss)
        pr, pi, pre, pim, b, seg0 = last
        esc, levels, amps_last = _monitor_tail(
            cls_cat, np.int32(offset0), carry.esc, pr, pi, pre, pim, rott,
            np.int32(b), np.float32(seg0 * win + b), win=win, k=K,
            sustain_n=sustain_n, cool_n=cool_n, max_level=max_level)
        levels = np.asarray(levels)
        amps_last = np.asarray(amps_last)
    else:
        worst_cat = np.zeros((0,), np.float32)
        levels = np.zeros((0,), np.int32)
        esc = carry.esc
        amps_last = np.zeros((K,), np.float32)
    new_carry = MonitorCarry(
        sliding=SlidingCarry(offset=offset, fill=fill, seg=seg,
                             prev_re=prev_re, prev_im=prev_im,
                             mean=sl.mean),
        esc=esc)
    return worst_cat, levels, amps_last, new_carry


def sliding_monitor_fused(x, dt: float, freqs, *, win: int, threshold,
                          sustain_n: int, cool_n: int, max_level: int = 3,
                          release=None, block_s: int = 0,
                          interpret: Optional[bool] = None,
                          use_pallas: bool = True,
                          carry: MonitorCarry = None):
    """The fused sliding monitor: worst-bin amplitude + escalation state
    straight from the trace, without ever materializing the [n, K]
    amplitude matrix.

    Offline (``carry=None``): returns ``(worst [n], levels [n], detect,
    peaks [S, K])`` — the per-sample worst-bin amplitude, escalation
    levels (``core.telemetry`` machine: ``threshold``/``release`` with
    ``sustain_n``/``cool_n`` hysteresis, warm-up and pad gated), the
    first-escalation sample index (-1 if never), and per-window per-bin
    peak amplitudes.  ``threshold`` (and ``release``, default
    ``threshold``) may be traced — they enter the kernel as runtime
    scalars.  ``use_pallas=False`` selects the structurally identical
    jnp ``lax.scan`` mirror (bitwise equal to the interpret-mode kernel;
    the differentiable path).

    Online (``carry=`` a ``MonitorCarry`` from ``monitor_carry_init``):
    consumes one concrete chunk and returns ``(worst [m], levels [m],
    amps_last [K], carry')``; chunked ``worst``/``levels`` concatenate
    bit-identically to the offline call on the concatenated trace (given
    ``mean=trace_mean(full)`` and matching ``threshold``), and
    ``amps_last`` reports per-bin amplitudes at the chunk's last sample,
    recombined in O(K) from the kernel's streamed prefix state.
    """
    if interpret is None:
        interpret = interpret_default()
    rel = threshold if release is None else release
    if carry is None:
        return _sliding_monitor_full(
            x, threshold, rel, dt, tuple(freqs), win=win,
            sustain_n=sustain_n, cool_n=cool_n, max_level=max_level,
            block_s=block_s, interpret=interpret, use_pallas=use_pallas)
    return _sliding_monitor_carry(
        x, threshold, rel, dt, tuple(freqs), win=win, sustain_n=sustain_n,
        cool_n=cool_n, max_level=max_level, interpret=interpret,
        use_pallas=use_pallas, carry=carry)

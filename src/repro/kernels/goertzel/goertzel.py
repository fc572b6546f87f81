"""Goertzel FFT-bin power kernels (telemetry backstop hot path, Sec. IV-E).

Kernels over power telemetry:

``goertzel_pallas`` — non-overlapping windows [W, win]: each grid cell
loads a block of windows into VMEM and runs K Goertzel resonators (one
per critical frequency) across the window with a single fori_loop —
O(win*K) multiply-adds per window vs O(win log win) for a full FFT, and
only K bins of output.  The [Bw, K] resonator states live in VREGs; the
window block is the only VMEM traffic.

``sliding_goertzel_pallas`` — every-sample sliding window (the
backstop's streaming granularity): the trace is processed in
window-sized segments with *hop-and-overlap* state.  Each grid cell
computes modulated within-segment prefix sums

    P_b = sum_{p<=b} x[p] * e^{-j*omega*p}        (restarted per segment)

and assembles the window ending at segment offset ``b`` from the head of
the current segment plus the suffix of the previous one:

    |window DFT| = |P_b + e^{j*omega*win} * (P^{prev}_{win-1} - P^{prev}_b)|

The per-segment restart is the numerics fix: every partial sum is
bounded by win*max|x| (oscillation scale once the wrapper removes the
trace mean), instead of the O(n*mean) global cumulative sums whose f32
rounding buries the ~1e5 W signals the backstop guards against.  The
previous segment's prefix state is carried across grid cells in VMEM
scratch (grid dims are sequential by default), so the trace streams
through VMEM exactly once.  The phase tables and the segment rotation
e^{j*omega*win} are small operands precomputed in float64 on the host.

**v1 vs v2 layout.**  The v1 kernel (``sliding_goertzel_pallas``, kept
as the benchmark baseline) works on ``[win, K]`` tables and a
``[Bs, win, K]`` amplitude block: with K=4 bins minor-most, every
vector register and VMEM tile wastes 124/128 lanes (the baselined
RPR203 finding).  The v2 kernels are *lane-major*: tables come in as
``[KP, win]`` (KP = K sublane-padded to 8; the kernel reads rows
``0..K-1``), the window axis — thousands of samples — sits on lanes,
and the K bins unroll into per-bin ``[Bs, win]`` row computations, so
every at-least-tile-sized block is lane-full and sublane-aligned.  The
warm-up renormalization (``core.telemetry.warmup_scale``) is applied
in-kernel from the global sample index.

``sliding_goertzel_v2_pallas`` materializes per-bin amplitudes (the
amps-facing API: online detector parity, counterfactual replay).
``sliding_monitor_pallas`` goes further and fuses the amps ->
escalation *decision* into the kernel: per sample it keeps only the
worst-bin amplitude and its escalation class
(``core.telemetry.escalation_classify`` semantics, threshold/release
passed as runtime scalars), plus per-window per-bin peak amplitudes —
the ``[S, win, K]`` amplitude tensor never leaves VMEM, collapsing
output traffic from 16 to 5 bytes per sample.

Outputs are bin amplitudes in the volts/watts units of the input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _goertzel_kernel(x_ref, coef_ref, o_ref, *, win: int):
    x = x_ref[...].astype(jnp.float32)          # [Bw, win]
    coef = coef_ref[...].astype(jnp.float32)    # [K]  2*cos(w)
    Bw = x.shape[0]
    K = coef.shape[0]

    def body(t, carry):
        s1, s2 = carry                           # [Bw, K]
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, 1)  # [Bw, 1]
        s0 = xt + coef[None, :] * s1 - s2
        return (s0, s1)

    s1, s2 = jax.lax.fori_loop(
        0, win, body,
        (jnp.zeros((Bw, K), jnp.float32), jnp.zeros((Bw, K), jnp.float32)))
    # amplitude via the standard Goertzel terminal formula
    power = s1 * s1 + s2 * s2 - coef[None, :] * s1 * s2
    o_ref[...] = (2.0 / win) * jnp.sqrt(jnp.maximum(power, 0.0))


def goertzel_pallas(windows: jax.Array, coef: jax.Array,
                    *, block_w: int = 8, interpret: bool = False) -> jax.Array:
    """windows: [W, win] f32; coef: [K] = 2*cos(2*pi*f*dt). -> [W, K]."""
    W, win = windows.shape
    K = coef.shape[0]
    assert W % block_w == 0, (W, block_w)
    return pl.pallas_call(
        functools.partial(_goertzel_kernel, win=win),
        grid=(W // block_w,),
        in_specs=[
            pl.BlockSpec((block_w, win), lambda i: (i, 0)),
            pl.BlockSpec((K,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_w, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((W, K), jnp.float32),
        interpret=interpret,
    )(windows.astype(jnp.float32), coef.astype(jnp.float32))


def _sliding_kernel(x_ref, cosp_ref, sinp_ref, rot_ref, o_ref,
                    pre_re, pre_im, *, win: int):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        pre_re[...] = jnp.zeros_like(pre_re)
        pre_im[...] = jnp.zeros_like(pre_im)

    x = x_ref[...].astype(jnp.float32)           # [Bs, win]
    cosp = cosp_ref[...]                          # [win, K]  cos(omega*p)
    sinp = sinp_ref[...]                          # [win, K]  sin(omega*p)
    # hop-and-overlap state: modulated prefix sums restarted every segment
    pr = jnp.cumsum(x[:, :, None] * cosp[None], axis=1)      # [Bs, win, K]
    pi = jnp.cumsum(x[:, :, None] * (-sinp[None]), axis=1)
    # previous segment's prefix state: within the block it is the row
    # above; the first row streams in from the previous grid cell's carry
    prev_r = jnp.concatenate([pre_re[...][None], pr[:-1]], axis=0)
    prev_i = jnp.concatenate([pre_im[...][None], pi[:-1]], axis=0)
    # suffix of the previous segment = its total minus its prefix
    dr = prev_r[:, -1:, :] - prev_r
    di = prev_i[:, -1:, :] - prev_i
    rr = rot_ref[0:1, :]                          # [1, K]  cos(omega*win)
    ri = rot_ref[1:2, :]                          # [1, K]  sin(omega*win)
    mr = pr + rr[None] * dr - ri[None] * di
    mi = pi + rr[None] * di + ri[None] * dr
    o_ref[...] = (2.0 / win) * jnp.sqrt(mr * mr + mi * mi)
    pre_re[...] = pr[-1]
    pre_im[...] = pi[-1]


def sliding_goertzel_pallas(xseg: jax.Array, cosp: jax.Array,
                            sinp: jax.Array, rot: jax.Array,
                            *, block_s: int = 1,
                            interpret: bool = False) -> jax.Array:
    """Streaming sliding-window Goertzel — the v1 (bin-minor) layout.

    Kept as the A/B baseline for ``benchmarks/kernels_bench.py``; the
    product paths run the lane-major v2 kernels below.

    xseg: [S, win] — the (mean-removed, zero-padded) trace reshaped into
    window-sized segments; cosp/sinp: [win, K] phase tables cos/sin of
    omega_k * p; rot: [2, K] = [cos, sin] of omega_k * win (the segment
    rotation).  Returns [S, win, K]: the sliding bin amplitude ending at
    every sample, normalized by 2/win (the caller rescales the warm-up
    ramp).  ``block_s`` segments are processed per grid cell; the
    cross-segment prefix state is carried in VMEM scratch, which relies
    on the (default) sequential grid execution order.
    """
    S, win = xseg.shape
    K = cosp.shape[1]
    assert S % block_s == 0, (S, block_s)
    return pl.pallas_call(
        functools.partial(_sliding_kernel, win=win),
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, win), lambda i: (i, 0)),
            pl.BlockSpec((win, K), lambda i: (0, 0)),
            pl.BlockSpec((win, K), lambda i: (0, 0)),
            pl.BlockSpec((2, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, win, K), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, win, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((win, K), jnp.float32),
                        pltpu.VMEM((win, K), jnp.float32)],
        interpret=interpret,
    )(xseg.astype(jnp.float32), cosp, sinp, rot)


# ---------------------------------------------------------------------------
# v2: lane-major layout, per-bin unrolled, optional in-kernel escalation
# ---------------------------------------------------------------------------

def _rounded(p):
    """The product ``p`` itself (a zero's sign aside), behind a select.
    XLA's CPU backend contracts a multiply and an add that land in one
    fusion into an FMA, and whether they land in one fusion depends on
    the program around them.  Every product that feeds an add goes
    through here, so the interpret-mode kernel at any ``block_s``, the
    online single-segment calls and the jnp mirror round alike and stay
    bitwise equal.  On the chip it costs a compare and a select."""
    return jnp.where(p != 0.0, p, 0.0)


def _lane_cumsum(v, roll=pltpu.roll):
    """Inclusive prefix sum along the lanes (axis 1) of a ``[rows, win]``
    block: a log-step scan of ``ceil(log2(win))`` rolls and lane-masked
    f32 adds.  Mosaic has no cumsum lowering, and a triangular matmul
    would run its default bf16 MXU passes over MW-scale sums.  The mask
    also hides whatever a roll over a lane-padded width rotates into
    lanes ``< shift``.  ``roll`` is ``pltpu.roll`` inside a kernel and
    ``jnp.roll`` (the same op graph in interpret mode) in the jnp
    mirror."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v = _rounded(v)
    shift = 1
    while shift < v.shape[1]:
        v = v + jnp.where(lane >= shift, roll(v, shift, 1), 0.0)
        shift *= 2
    return v


def bin_amps_lane_major(x, cosp, sinp, rott, pre_re, pre_im, scale, *,
                        win: int, k: int, roll=pltpu.roll):
    """Shared v2 core: per-bin sliding amplitudes on ``[Bs, win]``
    lane-major rows.  Yields ``(bin index, warm-up-scaled amp block,
    last prefix rows re/im)``; the caller stores the last rows as the
    next block's prefix state.  The K bins unroll as separate
    ``[Bs, win]`` computations — the long window axis stays on lanes,
    and the tables' padded sublane rows (k..KP-1) are never read.
    ``cosp``/``sinp``/``rott``/``pre_*`` are kernel refs or, in the jnp
    mirror (``roll=jnp.roll``), arrays: both index the same way.
    """
    for kk in range(k):
        pr = _lane_cumsum(x * cosp[kk:kk + 1, :], roll)        # [Bs, win]
        pi = _lane_cumsum(x * (-sinp[kk:kk + 1, :]), roll)
        # previous segment's prefix state: within the block the row
        # above; row 0 streams in from the previous grid cell's carry
        prev_r = pre_re[kk:kk + 1, :]
        prev_i = pre_im[kk:kk + 1, :]
        if x.shape[0] > 1:
            prev_r = jnp.concatenate([prev_r, pr[:-1]], axis=0)
            prev_i = jnp.concatenate([prev_i, pi[:-1]], axis=0)
        # suffix of the previous segment = its total minus its prefix
        dr = prev_r[:, -1:] - prev_r
        di = prev_i[:, -1:] - prev_i
        rr = rott[kk, 0]                  # cos(omega_k * win)
        ri = rott[kk, 1]                  # sin(omega_k * win)
        mr = pr + _rounded(rr * dr) - _rounded(ri * di)
        mi = pi + _rounded(rr * di) + _rounded(ri * dr)
        amp = ((2.0 / win)
               * jnp.sqrt(_rounded(mr * mr) + _rounded(mi * mi)) * scale)
        yield kk, amp, pr[-1:], pi[-1:]


def _global_idx_scale(x, s0, seg0, *, win: int):
    """Global sample index of every element of the [Bs, win] block (f32 —
    exact below 2**24 samples) and its warm-up renormalization.  ``seg0``
    is the global index of the call's first segment (0 offline; the
    stream position for chunked carry calls).  Mosaic lowers only
    integer iotas, hence the int32 -> f32 conversion."""
    bs = x.shape[0]
    segb = jax.lax.broadcasted_iota(jnp.int32, (bs, win), 0)
    pos = jax.lax.broadcasted_iota(jnp.int32, (bs, win), 1)
    idx = ((seg0 + (s0 * bs + segb).astype(jnp.float32)) * win
           + pos.astype(jnp.float32))
    scale = float(win) / jnp.minimum(idx + 1.0, float(win))
    return idx, scale


def _sliding_kernel_v2(x_ref, cosp_ref, sinp_ref, rot_ref, par_ref,
                       re0_ref, im0_ref, *refs, win: int, k: int):
    """Amps-materializing v2 kernel: K outputs of [Bs, win] per-bin
    warm-up-scaled amplitudes, plus the final prefix-state tables (the
    last two outputs; the trailing two refs are the prefix-state
    scratch).  The state streams in through ``re0``/``im0`` (zeros for a
    fresh trace) and out through the state outputs, so a chunked caller
    can resume bit-identically — offline and online run this same
    program."""
    o_refs, (nre_ref, nim_ref), (pre_re, pre_im) = \
        refs[:-4], refs[-4:-2], refs[-2:]
    s0 = pl.program_id(0)

    @pl.when(s0 == 0)
    def _():
        pre_re[...] = re0_ref[...]
        pre_im[...] = im0_ref[...]

    x = x_ref[...].astype(jnp.float32)                        # [Bs, win]
    _, scale = _global_idx_scale(x, s0, par_ref[0, 3], win=win)
    for kk, amp, last_r, last_i in bin_amps_lane_major(
            x, cosp_ref, sinp_ref, rot_ref, pre_re, pre_im, scale,
            win=win, k=k):
        o_refs[kk][...] = amp
        pre_re[kk:kk + 1, :] = last_r
        pre_im[kk:kk + 1, :] = last_i
    # every grid cell rewrites the same state block; the last write — the
    # final segment's prefix tables — is what the caller carries forward
    nre_ref[...] = pre_re[...]
    nim_ref[...] = pre_im[...]


def sliding_goertzel_v2_pallas(xseg: jax.Array, cosp: jax.Array,
                               sinp: jax.Array, rott: jax.Array,
                               params: jax.Array, re0: jax.Array,
                               im0: jax.Array, *, k: int, block_s: int = 1,
                               interpret: bool = False):
    """Lane-major sliding Goertzel (amps-materializing v2 variant).

    xseg: [S, win] mean-removed segments; cosp/sinp: [KP, win] lane-major
    phase tables (KP = k sublane-padded to 8; rows >= k are zero and
    unread); rott: [KP, 2] segment rotation [cos, sin] per bin; params:
    [1, 4] f32 [_, _, _, seg0] (the monitor kernel's layout; only
    ``seg0`` — the global index of ``xseg[0]``'s segment — is read
    here); re0/im0: [KP, win] incoming prefix-state
    tables (zeros for a fresh trace).  Returns
    ``(amps: K-tuple of [S, win], nre [KP, win], nim [KP, win])`` —
    warm-up-scaled per-bin amplitudes and the final prefix state
    (bit-identical to the ``ops._sliding_seg_v2`` jnp mirror at any
    ``block_s``).
    """
    S, win = xseg.shape
    kp = cosp.shape[0]
    assert S % block_s == 0, (S, block_s)
    outs = pl.pallas_call(
        functools.partial(_sliding_kernel_v2, win=win, k=k),
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, win), lambda i: (i, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
            pl.BlockSpec((kp, 2), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
        ],
        out_specs=([pl.BlockSpec((block_s, win), lambda i: (i, 0))
                    for _ in range(k)]
                   + [pl.BlockSpec((kp, win), lambda i: (0, 0)),
                      pl.BlockSpec((kp, win), lambda i: (0, 0))]),
        out_shape=([jax.ShapeDtypeStruct((S, win), jnp.float32)
                    for _ in range(k)]
                   + [jax.ShapeDtypeStruct((kp, win), jnp.float32),
                      jax.ShapeDtypeStruct((kp, win), jnp.float32)]),
        scratch_shapes=[pltpu.VMEM((kp, win), jnp.float32),
                        pltpu.VMEM((kp, win), jnp.float32)],
        interpret=interpret,
    )(xseg.astype(jnp.float32), cosp, sinp, rott, params, re0, im0)
    return tuple(outs[:k]), outs[k], outs[k + 1]


def _monitor_kernel(x_ref, cosp_ref, sinp_ref, rot_ref, par_ref,
                    re0_ref, im0_ref, ow_ref, oc_ref, op_ref,
                    nre_ref, nim_ref, pre_re, pre_im, *, win: int, k: int):
    """Fused monitor kernel: v2 amplitudes reduced in VMEM to the
    per-sample worst-bin amplitude, its escalation class
    (``escalation_classify`` semantics — par_ref carries
    [threshold, release, n, seg0] as runtime scalars), and per-window
    per-bin peak amplitudes.  The [Bs, win] per-bin amplitude blocks
    never leave VMEM.  Prefix state streams in/out as in
    ``_sliding_kernel_v2``."""
    s0 = pl.program_id(0)

    @pl.when(s0 == 0)
    def _():
        pre_re[...] = re0_ref[...]
        pre_im[...] = im0_ref[...]

    x = x_ref[...].astype(jnp.float32)                        # [Bs, win]
    idx, scale = _global_idx_scale(x, s0, par_ref[0, 3], win=win)
    thr = par_ref[0, 0]
    rel = par_ref[0, 1]
    n = par_ref[0, 2]
    live = (idx >= win - 1) & (idx < n)
    op_ref[...] = jnp.zeros_like(op_ref)      # padded bin columns stay 0
    worst = None
    for kk, amp, last_r, last_i in bin_amps_lane_major(
            x, cosp_ref, sinp_ref, rot_ref, pre_re, pre_im, scale,
            win=win, k=k):
        op_ref[:, kk] = jnp.where(live, amp, 0.0).max(axis=1)
        worst = amp if worst is None else jnp.maximum(worst, amp)
        pre_re[kk:kk + 1, :] = last_r
        pre_im[kk:kk + 1, :] = last_i
    # escalation_classify, inlined on the in-VMEM worst block
    hit = (worst > thr) & live
    clear = jnp.logical_not((worst > rel) & live)
    band = jnp.logical_and(~hit, ~clear)
    ow_ref[...] = worst
    oc_ref[...] = (2 * hit.astype(jnp.int32)
                   + band.astype(jnp.int32)).astype(jnp.int8)
    nre_ref[...] = pre_re[...]
    nim_ref[...] = pre_im[...]


def sliding_monitor_pallas(xseg: jax.Array, cosp: jax.Array,
                           sinp: jax.Array, rott: jax.Array,
                           params: jax.Array, re0: jax.Array,
                           im0: jax.Array, *, k: int, block_s: int = 1,
                           interpret: bool = False):
    """Fused sliding monitor: amps -> escalation decision in one kernel.

    Operands as ``sliding_goertzel_v2_pallas`` except ``params`` is a
    [1, 4] f32 row [threshold, release, n, seg0] (runtime values —
    threshold is a differentiable pytree leaf upstream; ``n`` gates
    trailing pad samples dead, exact as f32 below 2**24 samples; pass
    ``n = +inf`` for open-ended streams).  Returns
    ``(worst [S, win] f32, cls [S, win] int8, peaks [S, KP] f32,
    nre [KP, win], nim [KP, win])``: per-sample worst-bin amplitude, its
    escalation class, per-window per-bin peaks over live samples (bin
    columns >= k are zero), and the final prefix state.
    """
    S, win = xseg.shape
    kp = cosp.shape[0]
    assert S % block_s == 0, (S, block_s)
    return pl.pallas_call(
        functools.partial(_monitor_kernel, win=win, k=k),
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, win), lambda i: (i, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
            pl.BlockSpec((kp, 2), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
            pl.BlockSpec((kp, win), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((block_s, win), lambda i: (i, 0)),
                   pl.BlockSpec((block_s, win), lambda i: (i, 0)),
                   pl.BlockSpec((block_s, kp), lambda i: (i, 0)),
                   pl.BlockSpec((kp, win), lambda i: (0, 0)),
                   pl.BlockSpec((kp, win), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, win), jnp.float32),
                   jax.ShapeDtypeStruct((S, win), jnp.int8),
                   jax.ShapeDtypeStruct((S, kp), jnp.float32),
                   jax.ShapeDtypeStruct((kp, win), jnp.float32),
                   jax.ShapeDtypeStruct((kp, win), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((kp, win), jnp.float32),
                        pltpu.VMEM((kp, win), jnp.float32)],
        interpret=interpret,
    )(xseg.astype(jnp.float32), cosp, sinp, rott, params, re0, im0)

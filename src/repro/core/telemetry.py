"""In-band power/activity telemetry emulation (paper Sec. IV-A Monitoring).

Datacenter GPUs expose instantaneous/averaged power at 1-100 ms minimum
latency depending on counter reliability; the controllers consume this
class so the latency/period trade-off is first-class in every simulation.

This module also holds the *shared monitor gating* helpers — the warm-up
denominator ramp (``warmup_scale``) and the sustain/cooldown escalation
state machine (``escalation_init`` / ``escalation_step``) — extracted
from the telemetry backstop so the offline monitor
(``TelemetryBackstop``, ``kernels/goertzel/ops.sliding_bin_power``) and
the online control-plane detector (``repro.control``) run the exact same
gating math and cannot drift.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# shared monitor gating: warm-up ramp + escalation state machine
# ---------------------------------------------------------------------------

def warmup_scale(idx, win: int) -> jnp.ndarray:
    """The sliding monitor's warm-up renormalization ``win / min(i+1, win)``.

    The kernel normalizes every output by ``2/win``; outputs before one
    full window has streamed (``i < win - 1``) are partial-window
    estimates and rescale to their true sample count.  ``idx`` is the
    global sample index (any integer/float dtype); shared by the offline
    ``sliding_bin_power`` paths and the online chunked detector so the
    two ramps are bit-identical.
    """
    denom = jnp.minimum(jnp.asarray(idx, jnp.float32) + 1.0, float(win))
    return float(win) / denom


def escalation_init() -> Tuple[jnp.ndarray, ...]:
    """Initial ``(level, above, below, detect)`` escalation carry."""
    zero = jnp.asarray(0, jnp.int32)
    return (zero, zero, zero, jnp.asarray(-1, jnp.int32))


def escalation_step(carry, amp, idx, *, threshold, win: int, n: int,
                    sustain_n: int, cool_n: int, max_level: int = 3,
                    release=None):
    """One step of the threshold-with-hysteresis escalation state machine.

    ``carry`` is ``(level, above, below, detect)`` from
    ``escalation_init``; ``amp`` the monitored amplitude at global sample
    index ``idx``.  Triggering is warm-up gated (no escalation off
    partial-window estimates, ``idx >= win - 1``) and pad-gated
    (``idx < n``).  ``amp > threshold`` sustained for ``sustain_n`` steps
    escalates one level (up to ``max_level``); staying at or below
    ``release`` (default: ``threshold`` — the backstop's exact historical
    behavior) for ``cool_n`` steps de-escalates one level.  ``detect``
    latches the first escalation index.  Pure jnp, so it runs identically
    inside the backstop's ``lax.scan`` and eagerly in the control plane's
    per-tick loop.
    """
    cls = escalation_classify(amp, idx, threshold=threshold, win=win, n=n,
                              release=release)
    return escalation_class_step(carry, cls, idx, sustain_n=sustain_n,
                                 cool_n=cool_n, max_level=max_level)


#: escalation sample classes: the amp -> decision reduction the fused
#: monitor kernel emits instead of amplitudes.  CLS_PAD is an identity
#: transition (used to pad partial blocks in ``escalation_scan``).
CLS_CLEAR, CLS_BAND, CLS_HIT, CLS_PAD = 0, 1, 2, 3


def escalation_classify(amp, idx, *, threshold, win: int, n,
                        release=None):
    """Reduce an amplitude sample to its escalation class (int8).

    ``CLS_HIT`` (2): above trigger and live; ``CLS_CLEAR`` (0): at/below
    release or not live (warm-up ``idx < win - 1`` / pad ``idx >= n``);
    ``CLS_BAND`` (1): in the hysteresis band.  This is the *only* place
    amplitudes enter the escalation machine — the state transition
    itself (``escalation_class_step`` / ``escalation_scan``) consumes
    classes, so the fused monitor kernel can classify in VMEM and never
    materialize per-sample amplitudes.  Requires ``release <= threshold``
    (hit and clear must be exclusive; the default ``release=None`` means
    ``release == threshold``).
    """
    live = (idx >= win - 1) & (idx < n)
    hit = (amp > threshold) & live
    rel = threshold if release is None else release
    clear = ~((amp > rel) & live)
    band = jnp.logical_and(~hit, ~clear)
    return (2 * hit.astype(jnp.int32)
            + band.astype(jnp.int32)).astype(jnp.int8)


def escalation_class_step(carry, cls, idx, *, sustain_n: int, cool_n: int,
                          max_level: int = 3):
    """One escalation transition from a sample *class* (see
    ``escalation_classify``).  ``CLS_PAD`` is the identity transition.
    ``escalation_step`` delegates here, so the amplitude-facing and the
    class-facing machines cannot drift."""
    level, above, below, detect = carry
    hit = cls == CLS_HIT
    clear = cls == CLS_CLEAR
    on = cls != CLS_PAD
    above = jnp.where(hit, above + 1, jnp.where(on, 0, above))
    below = jnp.where(clear, below + 1, jnp.where(on, 0, below))
    esc = hit & (above >= sustain_n) & (level < max_level)
    detect = jnp.where(esc & (detect < 0), idx, detect)
    level = jnp.where(esc, level + 1, level)
    above = jnp.where(esc, 0, above)
    deesc = clear & (below >= cool_n) & (level > 0)
    level = jnp.where(deesc, level - 1, level)
    below = jnp.where(deesc, 0, below)
    return (level, above, below, detect), level


@functools.partial(jax.jit, static_argnames=("sustain_n", "cool_n",
                                             "max_level", "block"))
def escalation_scan(cls, idx0, carry, *, sustain_n: int, cool_n: int,
                    max_level: int = 3, block: int = 512):
    """Run the escalation machine over a class stream in O(n/block)
    sequential steps — bit-identical to folding ``escalation_class_step``
    sample by sample (property-tested in tests/test_control.py).

    The machine's per-sample recurrence is the monitor's real serial
    bottleneck (a trace-length ``lax.scan`` costs ~100x the Goertzel
    kernel at 1e6 samples).  But between class *changes* the transition
    has a closed form: within a homogeneous run the escalation
    candidates sit at ``j1 = max(1, period - counter)`` and every
    ``period`` samples after, of which ``room`` (head-room to
    ``max_level``, or down to 0) are taken.  The scan therefore walks
    fixed ``block``-sample blocks: an all-one-class block applies the
    closed form as a vector expression; a mixed block (a class boundary
    — rare at telemetry rates) falls back to an unrolled inner scan.
    The trailing partial block is padded with ``CLS_PAD`` (identity);
    a homogeneous block with a trailing pad run still takes the closed
    form over its live prefix, so short online chunks (the detector's
    per-tick calls) stay on the fast path.

    ``cls``: int8 classes from ``escalation_classify``; ``idx0``: global
    sample index of ``cls[0]`` (int32) — ``detect`` latches global
    indices, so chunked calls stay bit-identical to one offline call.
    Returns ``(carry', levels [len(cls)])``.
    """
    n = cls.shape[0]
    nb = max(-(-n // block), 1)
    pad = nb * block - n
    if pad:
        cls = jnp.concatenate(
            [cls, jnp.full((pad,), CLS_PAD, cls.dtype)])
    blocks = cls.reshape(nb, block)
    starts = (jnp.asarray(idx0, jnp.int32)
              + block * jnp.arange(nb, dtype=jnp.int32))
    j = jnp.arange(1, block + 1, dtype=jnp.int32)

    def run_form(room, counter, period, m):
        # homogeneous-run closed form over the block's m live samples
        # (trailing pads are the identity): candidate k sits at sample
        # j1 + (k-1)*period (1-indexed); `room` of them are taken, the
        # counter keeps counting past the last taken candidate
        j1 = jnp.maximum(1, period - counter)
        cnt = jnp.where((j >= j1) & (j <= m), 1 + (j - j1) // period, 0)
        e = jnp.minimum(room, jnp.max(cnt))
        taken = jnp.minimum(cnt, room)
        new_counter = jnp.where(e > 0, m - (j1 + (e - 1) * period),
                                counter + m)
        return j1, e, taken, new_counter

    def fast(carry, cb, g, m):
        level, above, below, detect = carry
        c0 = cb[0]
        j1h, eh, takh, ah = run_form(max_level - level, above, sustain_n, m)
        _, ec, takc, bc = run_form(level, below, cool_n, m)
        is_hit = c0 == CLS_HIT
        is_clear = c0 == CLS_CLEAR
        levels = jnp.where(is_hit, level + takh,
                           jnp.where(is_clear, level - takc, level))
        level2 = jnp.where(is_hit, level + eh,
                           jnp.where(is_clear, level - ec, level))
        above2 = jnp.where(is_hit, ah, 0)
        below2 = jnp.where(is_clear, bc, 0)
        detect2 = jnp.where(is_hit & (eh > 0) & (detect < 0),
                            g + j1h - 1, detect)
        return (level2, above2, below2, detect2), levels

    def slow(carry, cb, g, m):
        del m
        idx = g + jnp.arange(block, dtype=jnp.int32)
        return jax.lax.scan(
            lambda c, xi: escalation_class_step(
                c, xi[0], xi[1], sustain_n=sustain_n, cool_n=cool_n,
                max_level=max_level),
            carry, (cb, idx), unroll=min(block, 16))

    def body(carry, inp):
        cb, g = inp
        j0 = jnp.arange(block, dtype=jnp.int32)
        is_pad = cb == CLS_PAD
        m = jnp.sum((~is_pad).astype(jnp.int32))   # live prefix length ...
        trailing = jnp.all(is_pad == (j0 >= m))    # ... if pads all trail
        homog = (trailing & (m > 0)
                 & jnp.all(jnp.where(j0 < m, cb == cb[0], True)))
        return jax.lax.cond(homog, fast, slow, carry, cb, g, m)

    carry, levels = jax.lax.scan(body, carry, (blocks, starts))
    return carry, levels.reshape(-1)[:n]


@dataclasses.dataclass(frozen=True)
class TelemetrySource:
    period_s: float = 0.001     # sampling period (1 ms fast counters)
    latency_s: float = 0.002    # read-out latency
    noise_w: float = 0.0
    quantization_w: float = 1.0
    averaged: bool = False      # True = boxcar average over period

    def measure(self, w: np.ndarray, dt: float, seed: int = 0) -> np.ndarray:
        """Sampled+delayed view of true power w (same length, ZOH)."""
        n = len(w)
        k = max(int(round(self.period_s / dt)), 1)
        lag = int(round(self.latency_s / dt))
        if self.averaged and k > 1:
            kernel = np.ones(k) / k
            base = np.convolve(w, kernel, mode="full")[:n]
        else:
            base = w
        idx = (np.arange(n) // k) * k          # zero-order hold at samples
        m = base[np.clip(idx - lag, 0, n - 1)]
        if self.noise_w > 0:
            rng = np.random.default_rng(seed)
            m = m + rng.normal(0.0, self.noise_w, size=n)
        if self.quantization_w > 0:
            m = np.round(m / self.quantization_w) * self.quantization_w
        return m

    def measure_jax(self, w: jnp.ndarray, dt: float,
                    key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Pure traced mirror of ``measure`` for the jit/vmap engine.

        Sampling indices are static (period/latency/dt are config);
        noise, when enabled, draws from ``key`` instead of a numpy rng.
        NOTE: without an explicit ``key`` the noise vector is a fixed
        PRNGKey(0) draw — identical across calls and batch rows; thread a
        per-scenario key when sweeping noisy-telemetry configs.
        """
        n = w.shape[-1]
        k = max(int(round(self.period_s / dt)), 1)
        lag = int(round(self.latency_s / dt))
        if self.averaged and k > 1:
            kernel = jnp.ones(k, jnp.float32) / k
            base = jnp.convolve(w, kernel, mode="full",
                                precision=jax.lax.Precision.HIGHEST)[:n]
        else:
            base = w
        idx = np.clip((np.arange(n) // k) * k - lag, 0, n - 1)
        m = base[idx]
        if self.noise_w > 0:
            key = jax.random.PRNGKey(0) if key is None else key
            m = m + self.noise_w * jax.random.normal(key, (n,), jnp.float32)
        if self.quantization_w > 0:
            m = jnp.round(m / self.quantization_w) * self.quantization_w
        return m

"""Pure oracle(s) for the Goertzel bin-power kernels.

``bin_power_ref`` — per-window DFT-bin amplitude by direct correlation
(the mathematical definition the Goertzel recurrence implements).
``sliding_bin_power_ref`` — every-sample sliding window, float64 numpy:
the gold oracle the Pallas sliding kernel is tested against.
``sliding_bin_power_jnp`` — traced jnp mirror (jit/vmap-safe).

Numerics note (the PR-3 bugfix): both sliding estimators remove the
trace mean before accumulating.  Raw MW-scale traces carry a DC offset
(~5e8 W) three to four orders of magnitude above the oscillation
amplitudes the backstop guards against (~1e5 W); feeding that DC into
f32 cumulative sums buries the signal in rounding noise (the 9 Hz bin's
quiet-trace floor reaches ~1e4 W on a 30-minute trace) and makes every
partial warm-up window read ~2*DC, so no threshold can separate a real
oscillation from a quiet trace.  Removing the mean keeps every partial
sum at oscillation scale; the bins of interest (>= 0.1 Hz) measure the
AC content, which is unchanged.  The numpy ref additionally accumulates
in float64, making it exact at any trace length.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def goertzel_ref(windows, coef) -> jnp.ndarray:
    """Exact pure-jnp mirror of the kernel recurrence.

    windows: [W, win]; coef: [K] = 2*cos(2*pi*f*dt) -> amplitudes [W, K].
    (At integer cycles-per-window this equals ``bin_power_ref``; at
    fractional bins the two estimators differ by design — tests check both.)
    """
    import jax
    windows = jnp.asarray(windows, jnp.float32)
    coef = jnp.asarray(coef, jnp.float32)
    W, win = windows.shape
    K = coef.shape[0]

    def step(carry, xt):  # xt: [W]
        s1, s2 = carry
        s0 = xt[:, None] + coef[None, :] * s1 - s2
        return (s0, s1), None

    (s1, s2), _ = jax.lax.scan(
        step, (jnp.zeros((W, K), jnp.float32), jnp.zeros((W, K), jnp.float32)),
        windows.T)
    power = s1 * s1 + s2 * s2 - coef[None, :] * s1 * s2
    return (2.0 / win) * jnp.sqrt(jnp.maximum(power, 0.0))


def bin_power_ref(windows, dt: float, freqs) -> jnp.ndarray:
    """windows: [W, win]; freqs: [K] Hz -> amplitudes [W, K]."""
    windows = jnp.asarray(windows, jnp.float32)
    win = windows.shape[1]
    t = jnp.arange(win)[:, None] * (2 * jnp.pi * dt) * jnp.asarray(freqs)[None, :]
    re = jnp.einsum("wt,tk->wk", windows, jnp.cos(t))
    im = jnp.einsum("wt,tk->wk", windows, jnp.sin(t))
    return (2.0 / win) * jnp.sqrt(re * re + im * im)


def sliding_bin_power_jnp(x: jnp.ndarray, dt: float, freqs,
                          win: int) -> jnp.ndarray:
    """Traced mirror of ``sliding_bin_power_ref``: every-sample sliding
    window bin amplitudes [n, K] via complex cumulative sums of the
    mean-removed trace, jit/vmap-safe (``freqs`` and ``win`` are static).

    The product path is the Pallas kernel (``ops.sliding_bin_power``);
    this oracle stays the analysis-side reference and the backstop's
    ``use_pallas=False`` fallback.
    """
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[-1]
    # DC removal (see module docstring) at the f32 operating point of
    # ``ops.trace_mean``: centred on the first sample, so a constant
    # trace leaves exactly zero
    xc = x - (x[0] + jnp.mean(x - x[0]))
    # phases stay in-graph: a global-phase table is [n, K] (vs the Pallas
    # kernel's [win, K] host-precomputed tables) — materializing it as a
    # constant would bake tens of MB into the executable per trace length.
    # Post mean-removal the ~1e-3 rad f32 phase error at 10-minute traces
    # only scales the AC signal, not the DC offset.
    f = jnp.asarray(freqs, jnp.float32)
    t = jnp.arange(n, dtype=jnp.float32) * dt
    ph = jnp.exp(-2j * jnp.pi * t[:, None] * f[None, :])      # [n, K]
    cs = jnp.cumsum(xc[:, None] * ph, axis=0)
    w = jnp.concatenate([cs[:win], cs[win:] - cs[:-win]]) if n > win else cs
    denom = jnp.minimum(jnp.arange(n, dtype=jnp.float32) + 1.0, float(win))
    return 2.0 * jnp.abs(w) / denom[:, None]


def sliding_bin_power_ref(x: np.ndarray, dt: float, freqs: np.ndarray,
                          win: int) -> np.ndarray:
    """Every-sample sliding-window bin amplitudes [n, K] (numpy float64 —
    the gold oracle: mean-removed AND exact accumulation)."""
    x = np.asarray(x, np.float64)
    xc = x - x.mean()
    n = len(xc)
    k = len(freqs)
    out = np.zeros((n, k))
    t = np.arange(n) * dt
    for j, f in enumerate(freqs):
        ph = np.exp(-2j * np.pi * f * t)
        cs = np.cumsum(xc * ph)
        w = cs.copy()
        w[win:] = cs[win:] - cs[:-win]
        denom = np.minimum(np.arange(n) + 1, win)
        out[:, j] = 2.0 * np.abs(w) / denom
    return out

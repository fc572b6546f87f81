"""Rack-level energy storage (paper Sec. IV-C), as a lax.scan SoC model.

The BESS tracks a slowly-moving grid target (EMA of load) by discharging
into compute peaks and recharging in comm valleys — Fig. 7. Limits modeled:
capacity (J), charge/discharge power (W), round-trip efficiency, and the
charge/discharge mode-switch latency (the paper's requirement 4: 'switch
modes quickly'). Energy is conserved up to efficiency losses (property
tested).

Every parameter is a pytree leaf, so a capacity/power grid vmaps through
``apply_jax`` in one compiled call (see core/engine.py).

``smooth_tau`` (structure-static meta field) selects the gradient-design
relaxation: 0 is the exact hard SoC model below; > 0 replaces the
``jnp.sign`` charge/discharge mode switch and the latency-hold step gate
with tanh/sigmoid blends at temperature tau (the SoC tapers and power
clips are piecewise linear and already carry subgradients, so they stay).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.smoothing.base import (energy_overhead_jax, np_apply,
                                       register_mitigation)
from repro.core.smoothing.relax import sigmoid_gate, soft_sign


@dataclasses.dataclass(frozen=True)
class RackBattery:
    capacity_j: float                    # usable energy per rack-equivalent
    max_discharge_w: float
    max_charge_w: float
    efficiency: float = 0.95             # one-way (sqrt of round-trip)
    target_tau_s: float = 30.0           # EMA horizon for the grid target
    initial_soc: float = 0.5
    switch_latency_s: float = 0.0        # mode-switch dead time
    # 0 = exact hard semantics; > 0 = gradient-design relaxation (static
    # so hard and smooth configs never stack into one vmapped grid)
    smooth_tau: float = 0.0

    def _latency_samples(self, dt: float) -> jnp.ndarray:
        """Mode-switch dead time in whole samples, computed ONCE per trace
        (hoisted out of the scan body).  ``jnp.round`` makes this a
        static-like quantity: it is a pytree leaf (grids over latency still
        vmap), but its gradient is zero almost everywhere, so it is pinned
        with ``stop_gradient`` and excluded from gradient design — treat it
        like hardware, not a design variable."""
        return jax.lax.stop_gradient(jnp.round(self.switch_latency_s / dt))

    def apply_jax(self, w: jnp.ndarray, dt: float) -> Tuple[jnp.ndarray, Dict]:
        if self.smooth_tau:
            return self._apply_smooth(w, dt)
        alpha = dt / jnp.maximum(self.target_tau_s, dt)
        lat_n = self._latency_samples(dt)
        # guard: capacity 0 must degrade to a passthrough (soc stays 0,
        # tapers close both ports), not 0/0-NaN the soc fraction — the
        # gradient designer's box projection can land on exactly 0
        cap_j = jnp.maximum(self.capacity_j, 1e-9)

        def step(carry, p):
            soc, tgt, mode, hold = carry
            tgt = tgt + alpha * (p - tgt)
            want = p - tgt                      # >0: discharge, <0: charge
            new_mode = jnp.sign(want)
            switching = (new_mode != mode) & (new_mode != 0) & (mode != 0)
            hold = jnp.where(switching, lat_n, jnp.maximum(hold - 1.0, 0.0))
            blocked = hold > 0
            # power limits, with anti-windup taper near the SoC bounds so a
            # saturating battery releases the load gradually (no grid steps)
            soc_frac = soc / cap_j
            taper_lo = jnp.clip(soc_frac / 0.10, 0.0, 1.0)
            taper_hi = jnp.clip((1.0 - soc_frac) / 0.10, 0.0, 1.0)
            dis = jnp.clip(want, 0.0, self.max_discharge_w * taper_lo)
            dis = jnp.minimum(dis, soc * self.efficiency / dt)
            chg = jnp.clip(-want, 0.0, self.max_charge_w * taper_hi)
            chg = jnp.minimum(chg, (cap_j - soc) / self.efficiency / dt)
            dis = jnp.where(blocked, 0.0, dis)
            chg = jnp.where(blocked, 0.0, chg)
            grid = p - dis + chg
            soc = soc - dis * dt / self.efficiency + chg * dt * self.efficiency
            soc = jnp.clip(soc, 0.0, cap_j)
            return (soc, tgt, new_mode, hold), (grid, soc)

        w = jnp.asarray(w, jnp.float32)
        # grid target starts at the trace mean (the scheduled steady-state
        # draw a real operator bids into the day-ahead market) — starting at
        # w[0] makes the battery burn capacity chasing the initial transient.
        # The scan runs on the load less that mean, which changes nothing
        # but the rounding: a float32 target near 100 MW moves in 8 W steps,
        # and over tens of thousands of samples its drift reaches the
        # battery's charge and, through the SoC tapers, the output.
        mean = jnp.mean(w)
        init = (jnp.asarray(self.initial_soc * cap_j, jnp.float32),
                jnp.asarray(0.0, jnp.float32), jnp.asarray(0.0, jnp.float32),
                jnp.asarray(0.0, jnp.float32))
        _, (grid, soc) = jax.lax.scan(step, init, w - mean, unroll=8)
        grid = grid + mean
        aux = {
            "soc_trace": soc,
            "soc_min_frac": soc.min() / cap_j,
            "soc_max_frac": soc.max() / cap_j,
            "energy_overhead": energy_overhead_jax(w, grid),
            "peak_reduction_w": w.max() - grid.max(),
        }
        return grid, aux

    def _apply_smooth(self, w: jnp.ndarray, dt: float
                      ) -> Tuple[jnp.ndarray, Dict]:
        """Relaxed SoC model at temperature ``smooth_tau``: mode is a tanh
        of the power mismatch, the latency hold engages in proportion to
        the mode flip, and the blocked gate is a sigmoid of the remaining
        hold — everything else is the hard model unchanged."""
        tau = self.smooth_tau
        alpha = dt / jnp.maximum(self.target_tau_s, dt)
        lat_n = self._latency_samples(dt)
        cap_j = jnp.maximum(self.capacity_j, 1e-9)  # see apply_jax guard
        p_scale = 0.5 * (self.max_discharge_w + self.max_charge_w)
        # taper widths floored at ~2 power-limit samples of energy: the
        # hard 0.10*cap width makes the SoC recursion's reverse-mode
        # factor ~ max_W*dt / (0.10*cap*eff) — unbounded as cap -> 0, and
        # a scan-length product of that overflows f32 and NaNs the design
        # lane.  The floor keeps d(soc')/d(soc) >= 0.5 (contractive) at
        # any capacity; for realistically-sized batteries 0.10*cap
        # dominates and the forward matches the hard taper.
        w_lo = jnp.maximum(0.10 * cap_j,
                           2.0 * self.max_discharge_w * dt / self.efficiency)
        w_hi = jnp.maximum(0.10 * cap_j,
                           2.0 * self.max_charge_w * dt * self.efficiency)

        def step(carry, p):
            soc, tgt, mode, hold = carry
            tgt = tgt + alpha * (p - tgt)
            want = p - tgt
            new_mode = soft_sign(want, tau, p_scale)
            # opposing signs -> flip strength in (0, 1]
            switching = jnp.clip(-(new_mode * mode), 0.0, 1.0)
            hold = (switching * lat_n
                    + (1.0 - switching) * jnp.maximum(hold - 1.0, 0.0))
            open_f = sigmoid_gate(0.5 - hold, tau, lat_n + 1.0)
            taper_lo = jnp.clip(soc / w_lo, 0.0, 1.0)
            taper_hi = jnp.clip((cap_j - soc) / w_hi, 0.0, 1.0)
            dis = jnp.clip(want, 0.0, self.max_discharge_w * taper_lo)
            dis = jnp.minimum(dis, soc * self.efficiency / dt)
            chg = jnp.clip(-want, 0.0, self.max_charge_w * taper_hi)
            chg = jnp.minimum(chg, (cap_j - soc) / self.efficiency / dt)
            dis = open_f * dis
            chg = open_f * chg
            grid = p - dis + chg
            soc = soc - dis * dt / self.efficiency + chg * dt * self.efficiency
            soc = jnp.clip(soc, 0.0, cap_j)
            return (soc, tgt, new_mode, hold), (grid, soc)

        w = jnp.asarray(w, jnp.float32)
        init = (jnp.asarray(self.initial_soc * cap_j, jnp.float32),
                jnp.mean(w), jnp.asarray(0.0, jnp.float32),
                jnp.asarray(0.0, jnp.float32))
        _, (grid, soc) = jax.lax.scan(step, init, w, unroll=8)
        aux = {
            "soc_trace": soc,
            "soc_min_frac": soc.min() / cap_j,
            "soc_max_frac": soc.max() / cap_j,
            "energy_overhead": energy_overhead_jax(w, grid),
            "peak_reduction_w": w.max() - grid.max(),
        }
        return grid, aux

    def apply(self, w: np.ndarray, dt: float) -> Tuple[np.ndarray, Dict]:
        return np_apply(self, w, dt)


register_mitigation(
    RackBattery,
    data_fields=("capacity_j", "max_discharge_w", "max_charge_w",
                 "efficiency", "target_tau_s", "initial_soc",
                 "switch_latency_s"),
    meta_fields=("smooth_tau",))


def size_battery_for(job_w_swing: float, period_s: float, n_racks: int,
                     margin: float = 2.0) -> RackBattery:
    """Capacity to absorb half a swing cycle per rack, with margin."""
    per_rack_swing = job_w_swing / n_racks
    cap = margin * per_rack_swing * (period_s / 2)
    return RackBattery(capacity_j=cap * n_racks,
                       max_discharge_w=job_w_swing,
                       max_charge_w=job_w_swing)

"""Several training jobs behind one grid connection.

A ``Campus`` is a Study workload like an ``IterationTimeline``: the
Study's fleet axis gives its total chips, and each tenant (a job with its
own timeline) runs its share of them.  Every tenant is in steady state at
its own phase, so the campus's swing is what the tenants' waveforms add
up to at the point of coupling, not any one job's.

Host NumPy, like ``waveform.phase_levels``: what is made here fixes
shapes.  A campus row enters the engine as per-tenant inputs with a
leading tenant axis: ``levels`` [K, n], chips [K] and jitter shifts
[K, S].  Everything random is drawn from the row's seed:

* tenant ``k``'s phase, uniform over its cycle in samples, from
  ``default_rng([seed, k])``;
* tenant ``k``'s jitter seed: the row's seed for tenant 0 (so a
  one-tenant campus draws what a single job of that seed draws), a seed
  of its own from ``default_rng([seed, k, 1])`` for the others.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from repro.core.hardware import DEFAULT_HW, Hardware
from repro.core.phases import IterationTimeline
from repro.core.waveform import WaveformConfig, jitter_shifts, phase_levels


@dataclasses.dataclass(frozen=True)
class Tenant:
    """One job of a campus: its timeline and its share of the chips."""
    name: str
    timeline: IterationTimeline
    share: float


@dataclasses.dataclass(frozen=True)
class Campus:
    """Tenants sharing one grid connection, over a window of
    ``window_s`` seconds.  Shares are of the campus's chips and sum to 1;
    a tenant runs ``round(n_chips * share)`` of them."""
    tenants: Tuple[Tenant, ...]
    window_s: float

    def __post_init__(self):
        object.__setattr__(self, "tenants", tuple(self.tenants))
        names = [t.name for t in self.tenants]
        if not names or len(set(names)) != len(names):
            raise ValueError(f"a campus needs tenants of distinct names, "
                             f"got {names}")
        if min(t.share for t in self.tenants) <= 0 or abs(
                sum(t.share for t in self.tenants) - 1.0) > 1e-9:
            raise ValueError("tenant shares must be positive and sum to 1")
        if self.window_s <= 0:
            raise ValueError(f"window_s must be positive, got {self.window_s}")

    @property
    def period_s(self) -> float:
        """The slowest tenant's iteration: the longest cycle of any job
        on the campus."""
        return max(t.timeline.period_s for t in self.tenants)

    def n_samples(self, cfg: WaveformConfig) -> int:
        return int(round(self.window_s / cfg.dt))

    def tenant_chips(self, n_chips: int) -> np.ndarray:
        """Each tenant's whole chips [K]."""
        return np.asarray([round(n_chips * t.share) for t in self.tenants],
                          np.float64)

    def tenant_seeds(self, seed: int) -> Tuple[int, ...]:
        """Each tenant's jitter seed [K]."""
        return (int(seed),) + tuple(
            int(np.random.default_rng([seed, k, 1]).integers(2 ** 63))
            for k in range(1, len(self.tenants)))

    def cycles(self, cfg: WaveformConfig,
               hw: Hardware = DEFAULT_HW) -> Tuple[np.ndarray, ...]:
        """Each tenant's steady-state cycle of chip power levels: one
        iteration, or ``ckpt_every`` iterations with their checkpoint."""
        one = dataclasses.replace(cfg, steps=max(cfg.ckpt_every, 1))
        return tuple(phase_levels(t.timeline, one, hw) for t in self.tenants)

    def levels(self, seed: int, cfg: WaveformConfig,
               hw: Hardware = DEFAULT_HW) -> np.ndarray:
        """Per-tenant chip power levels over the window [K, n]: each
        tenant's cycle tiled from its phase."""
        t = np.arange(self.n_samples(cfg))
        return np.stack([c[(t + _phase(seed, k, len(c))) % len(c)]
                         for k, c in enumerate(self.cycles(cfg, hw))])

    def shifts(self, seed: int, cfg: WaveformConfig,
               sample_chips: int = 64) -> np.ndarray:
        """Per-tenant jitter shifts [K, S]."""
        return np.stack([jitter_shifts(cfg, s, sample_chips)
                         for s in self.tenant_seeds(seed)])


def _phase(seed: int, tenant: int, cycle_len: int) -> int:
    """Tenant ``tenant``'s phase at the window's start, in samples into
    its cycle of ``cycle_len``."""
    return int(np.random.default_rng([seed, tenant]).integers(cycle_len))


# A Study workload is a single job (an ``IterationTimeline``) or a
# ``Campus``; the engine and the Study ask these three what they need of
# either.

def n_tenants(workload) -> int:
    """A workload's tenants: a campus's, or 1 for a single job."""
    return len(workload.tenants) if isinstance(workload, Campus) else 1


def n_samples(workload, cfg: WaveformConfig,
              hw: Hardware = DEFAULT_HW) -> int:
    """The sample count of a workload's rows."""
    if isinstance(workload, Campus):
        return workload.n_samples(cfg)
    return len(phase_levels(workload, cfg, hw))


def row_inputs(workload, seed: int, n_chips, cfg: WaveformConfig,
               hw: Hardware, sample_chips: int, levels=None):
    """One row's engine inputs: per-tenant levels, chips and jitter
    shifts.  A campus of K > 1 tenants leads each with its tenant axis
    ([K, n], [K], [K, S]); a single job, like a campus of one tenant, has
    none ([n], a scalar, [S]).  ``levels`` replaces the levels made here
    (a job's ``phase_levels``, a campus's ``levels``)."""
    if not isinstance(workload, Campus):
        lv = phase_levels(workload, cfg, hw) if levels is None else levels
        return lv, n_chips, jitter_shifts(cfg, seed, sample_chips)
    lv = workload.levels(seed, cfg, hw) if levels is None else levels
    chips = workload.tenant_chips(n_chips)
    shifts = workload.shifts(seed, cfg, sample_chips)
    if len(chips) == 1:
        return lv[0], chips[0], shifts[0]
    return lv, chips, shifts

"""Sweep cells on a scenario mesh: ``drivers/sweep.py``'s Studies, with
each chunk's rows sharded over the traffic's ``mesh_chips`` chips
(``ScenarioShardPlan``) and the per-row results merged back on the host.

Everything else (the grid, the window, the rows checked) is the sweep
driver's; only where the rows run differs.
"""
from __future__ import annotations

from typing import Dict

from drivers import sweep


class Session(sweep.Session):
    """One run of a sweep cell on a scenario mesh."""

    def __init__(self, config: Dict, cell: Dict, seed: int):
        import jax
        from repro.parallel.sharding import ScenarioShardPlan

        super().__init__(config, cell, seed)
        chips = cell["traffic"]["mesh_chips"]
        self._plan = ScenarioShardPlan.make(jax.devices()[:chips])

    def study(self, block: int):
        study = super().study(block)
        study.plan = self._plan
        return study

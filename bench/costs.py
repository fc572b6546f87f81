"""Operations and bytes the algorithms need, counted from shapes.

These are the least work of the computation, whatever implements it:
a kernel that does more (recomputation, a log-step scan, padding) reads
below 100 % of its roofline, never above.  Peaks come from
``peaks.json`` by the device's ``device_kind``; a device that is not
there is an error.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence, Tuple

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict:
    with open(_PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def monitor_least_work(row_samples: Sequence[int],
                       bins: int) -> Tuple[float, float]:
    """(operations, bytes) of a sliding single-bin DFT monitor over rows
    of ``row_samples`` real samples each (the padding an implementation
    adds is not work the algorithm needs) and ``bins`` bins, reporting
    per sample the worst bin's amplitude and its escalation class.

    Per sample: one subtraction for the sample entering less the one
    leaving the window (shared by all bins); per bin, the recursive
    update X <- (X + delta) e^{i w} (one add, one complex multiply: 4
    multiplies and 2 adds) and the squared magnitude (2 multiplies, 1
    add); the maximum over bins (bins - 1 comparisons); one square root
    and one scale of the worst.  Bytes: the sample read (4) and the
    worst amplitude (4) and class (1) written.  The window length does
    not enter: the recursion needs no more work for a longer window.
    """
    samples = float(sum(row_samples))
    ops_per_sample = 1 + bins * (1 + 6 + 3) + (bins - 1) + 2
    return samples * ops_per_sample, samples * (4 + 4 + 1)


def roofline(ops: float, nbytes: float, seconds: float,
             peak: Dict) -> Tuple[float, str]:
    """(share of the roofline in %, the bound that binds): the least time
    the chip could take over the time measured."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_ops else "flops"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound

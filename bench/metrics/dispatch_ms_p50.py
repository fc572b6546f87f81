"""Median ``latency_s`` of the ControlLog's dispatch records in the
window: the program's own count of how long a rung took to build."""
import numpy as np


def read(ctx):
    lat = ctx.stats.get("dispatch_latencies_s")
    return None if not lat else 1e3 * float(np.median(lat))

"""Serve scheduler: 90th percentile of the time a request waited between
its arrival and its admission to a slot, over the window's requests that
arrived and were admitted before the profiler started (so no reading
holds the profiler's start or the stall while it writes its trace).
Read from the engine's own per-request records (``ServeMetrics``, host
clock).  Should move ``ttft_mean_ms``."""

import numpy as np


def read(ctx):
    w = ctx.get("queue_wait_s")
    if not w:
        return None
    return float(np.percentile(np.asarray(w, np.float64), 90)) * 1e3

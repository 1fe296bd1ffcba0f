"""served_p50_ms: median latency of every request due in the window, from
the time it was due to the time the client saw its answer (host clock).
A request that failed counts at the longest wait of the run."""
import numpy as np


def read(ctx):
    lat = ctx.readings.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 50) * 1e3)

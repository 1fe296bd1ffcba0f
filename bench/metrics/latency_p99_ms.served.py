"""latency_p99_ms.served: the 99th percentile of the same latencies as
``served_p50_ms`` (host clock).  The serving path's long host<->device
copies (``transfer_max_ms.served``) set it; with no bound on it."""
import numpy as np


def read(ctx):
    lat = ctx.readings.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 99) * 1e3)

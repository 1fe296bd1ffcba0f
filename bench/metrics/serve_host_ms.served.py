"""serve_host_ms.served: mean per micro-batch of the server's coalesce,
stage and scatter spans (``RequestTrace``, server clock), counted once per
batch and not per request, over the traces the server kept (ms)."""
import numpy as np


def read(ctx):
    traces = ctx.readings.get("traces")
    if not traces:
        return None
    per_batch = {}
    for tr in traces:
        if tr.dispatched_at is None:
            continue
        per_batch[tr.dispatched_at] = sum(
            sp.end - sp.start for sp in tr.spans
            if sp.name in ("coalesce", "stage", "scatter"))
    if not per_batch:
        return None
    return float(np.mean(list(per_batch.values())) * 1e3)

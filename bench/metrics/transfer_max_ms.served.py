"""transfer_max_ms.served: the longest single host<->device copy on the
host in the traced window (JAX's ``np.asarray(jax.Array)`` and
``DevicePutWithSharding`` events): the server fetches each micro-batch's
results and puts each staged batch this way, holding its thread (ms)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.transfers:
        return None
    lo, hi = ctx.span
    inside = [e - s for s, e, _ in ctx.trace.transfers if s >= lo and e <= hi]
    return max(inside) / 1e6 if inside else None

"""stages_ms.batch: device time per search outside the Mosaic custom calls
(merge, rescore, gather, pads), from the trace (ms)."""
import xplane


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or "searches" not in ctx.readings:
        return None
    lo, hi = ctx.span
    ns = xplane.busy_ns(ctx.trace, lo, hi, custom=False)
    return ns / 1e6 / ctx.readings["searches"]

"""device_idle.served: 1 - busy/window over the traced window, busy being
the union of the intervals in which a device op ran (%)."""
import xplane


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return xplane.idle_percent(ctx.trace, *ctx.span)

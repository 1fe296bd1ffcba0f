"""search_roofline.batch: the least time one search's work needs on the
cell's chips (``costs.least_time`` of the configuration's shapes over the
published peaks of that many chips) over the device time one search took:
all device busy time of the traced window, every op, kernel or not,
averaged over the chips, over the searches in it (%)."""
import costs
import xplane


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or "searches" not in ctx.readings:
        return None
    lo, hi = ctx.span
    per_search = xplane.busy_ns(ctx.trace, lo, hi) / 1e9 / ctx.readings["searches"]
    least, bound = costs.least_time(
        costs.search_cost(ctx.cfg, ctx.cfg["queries"]), ctx.peaks,
        ctx.cell["chips"])
    ctx.log(f"search_roofline.batch: least time {least!r} s ({bound} "
            f"bound), device time per search {per_search!r} s")
    return 100.0 * least / per_search

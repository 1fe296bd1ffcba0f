"""allgather_ms.batch: device time per search in the all-gathers that carry
each chip's winners to the others (ops named ``all-gather``: the start and
done halves of an async one, or a fused one), the union of their intervals
in the traced window, averaged over the chips, over the searches in it
(ms); nothing where no such op ran.  Logs the op names it matched."""
import xplane


def collective(name: str) -> bool:
    return "all-gather" in name or "all_gather" in name


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or "searches" not in ctx.readings:
        return None
    lo, hi = ctx.span
    names = sorted({op[2] for op in ctx.trace.ops
                    if collective(op[2]) and op[1] > lo and op[0] < hi})
    if not names:
        return None
    ctx.log(f"allgather_ms.batch: matched ops {names}")
    ns = xplane.op_ns(ctx.trace, lo, hi, collective)
    return ns / 1e6 / ctx.readings["searches"]

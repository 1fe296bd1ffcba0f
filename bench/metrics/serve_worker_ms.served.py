"""serve_worker_ms.served: the server worker's own host work per
micro-batch: its ``serve.coalesce``, ``serve.stage``,
``serve.dispatch[<bucket>]``, ``serve.account`` and ``serve.scatter``
spans, summed over the traced window, over the micro-batches dispatched in
it (ms).  Waits (idle, hold), the waits on the device (``serve.ready``,
``serve.release``) and the copies to the host (``serve.fetch``) are not
counted.

Logs how much of the window's device-idle time lies inside a worker span,
and the ten longest device-idle gaps, each labelled by the worker's
innermost span at the gap's middle."""
import program_spans

OWN = ("serve.coalesce", "serve.stage", "serve.account", "serve.scatter")


def read(ctx):
    spans = program_spans.worker(program_spans.of_run(ctx) or [])
    batches = ctx.readings.get("batches")
    if not spans or not batches:
        return None
    lo, hi = ctx.span
    idle = program_spans.idle(ctx.trace, lo, hi)
    idle_ns = sum(e - s for s, e in idle)
    inside = program_spans.overlap_ns(idle, spans)
    share = 100.0 * inside / idle_ns if idle_ns else 100.0
    ctx.log(f"serve_worker_ms.served: {share!r}% of the window's device-idle "
            f"time lies inside a worker span ({inside / 1e9!r} s of "
            f"{idle_ns / 1e9!r} s)")
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    ctx.log("serve_worker_ms.served: longest device-idle gaps: " + ", ".join(
        f"{program_spans.label_at(spans, (s + e) // 2)} {(e - s) / 1e9!r} s"
        for s, e in longest))
    own = [sp for sp in spans
           if sp[2] in OWN or sp[2].startswith("serve.dispatch[")]
    return program_spans.total_ns(own, lo, hi) / 1e6 / batches

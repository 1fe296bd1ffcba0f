"""serve_held_ms.served: time per micro-batch in which the server's worker
held the coalescing window with a dispatched batch still unfetched
(``serve.hold.inflight``) while no device op ran, that is with the previous
result finished on the device and waiting for the worker.  Summed over the
traced window, over the micro-batches dispatched in it (ms)."""
import program_spans


def read(ctx):
    spans = program_spans.worker(program_spans.of_run(ctx) or [])
    batches = ctx.readings.get("batches")
    if not spans or not batches:
        return None
    held = [sp for sp in spans if sp[2] == "serve.hold.inflight"]
    idle = program_spans.idle(ctx.trace, *ctx.span)
    return program_spans.overlap_ns(held, idle) / 1e6 / batches

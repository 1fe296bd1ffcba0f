"""serve_fetch_ms.served: time per micro-batch the server's worker spent
copying results to the host (``serve.fetch``: the two ``np.asarray`` copies
of a batch's values and indices), summed over the traced window, over the
micro-batches dispatched in it (ms).  Logs the longest fetch."""
import program_spans
import xplane


def read(ctx):
    spans = program_spans.worker(program_spans.of_run(ctx) or [])
    batches = ctx.readings.get("batches")
    if not spans or not batches:
        return None
    lo, hi = ctx.span
    fetches = xplane.clip([sp[:2] for sp in spans if sp[2] == "serve.fetch"],
                          lo, hi)
    if fetches:
        s, e = max(fetches, key=lambda iv: iv[1] - iv[0])
        ctx.log(f"serve_fetch_ms.served: {len(fetches)} fetches, longest "
                f"{(e - s) / 1e6!r} ms at {(s - lo) / 1e9!r} s into the "
                f"window")
    return sum(e - s for s, e in fetches) / 1e6 / batches

"""search_dispatch_ms.batch: host time per search in ``Index.search``
(``index.search``: validation, cast, pads, cache lookup and the dispatch,
not the wait for the device), summed over the traced window, over the
searches in it (ms)."""
import program_spans


def read(ctx):
    spans = program_spans.of_run(ctx)
    searches = ctx.readings.get("searches")
    if not spans or not searches:
        return None
    calls = [sp for sp in spans if sp[2] == "index.search"]
    if not calls:
        return None
    return program_spans.total_ns(calls, *ctx.span) / 1e6 / searches

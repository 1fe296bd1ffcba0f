"""shard_scan_pallas.batch: share of the run's sharded dispatches (set-up
included) whose shards each ran the fused Pallas kernel over their own
block of rows, not the XLA scan.  From the program's ``SHARD_SCAN_COUNTS``
(``repro_shard_scan_dispatches_total``) over ``DISPATCH_COUNTS`` (%);
nothing where the program has no such counter, and nothing off the chip
(a trace that holds no device ops), where the shards' scan follows the
platform and says nothing of the device path."""
from repro.search import backends


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    counts = getattr(backends, "SHARD_SCAN_COUNTS", None)
    sharded = backends.DISPATCH_COUNTS.get("sharded", 0)
    if counts is None or not sharded:
        return None
    return 100.0 * counts.get("pallas", 0) / sharded

"""fused_select_deferred.served: share of the run's Pallas dispatches (set-up
included) whose fused scan→select kernel took the deferred select: every
bin winner of a query block kept in VMEM, the top-k selected once per
block.  From the program's ``FUSED_SELECT_COUNTS``
(``repro_fused_select_dispatches_total``) over ``DISPATCH_COUNTS`` (%);
nothing where the program has no such counter."""
from repro.search import backends


def read(ctx):
    counts = getattr(backends, "FUSED_SELECT_COUNTS", None)
    pallas = backends.DISPATCH_COUNTS.get("pallas", 0)
    if counts is None or not pallas:
        return None
    return 100.0 * counts.get("deferred", 0) / pallas

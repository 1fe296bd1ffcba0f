"""scan_bf16_operands.batch: share of the run's Pallas dispatches (set-up
included) whose scan dot was given bf16 operands: bf16 rows as stored
against queries rounded to bf16, one MXU pass.  From the program's
``SCAN_OPERAND_COUNTS`` (``repro_scan_operand_dispatches_total``) over
``DISPATCH_COUNTS`` (%); nothing where the program has no such counter."""
from repro.search import backends


def read(ctx):
    counts = getattr(backends, "SCAN_OPERAND_COUNTS", None)
    pallas = backends.DISPATCH_COUNTS.get("pallas", 0)
    if counts is None or not pallas:
        return None
    return 100.0 * counts.get("bf16", 0) / pallas

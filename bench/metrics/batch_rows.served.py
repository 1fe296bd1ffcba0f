"""batch_rows.served: mean query rows per dispatch over the window, from
``SearchServer.stats()`` (dispatched rows over batches, padding excluded)."""


def read(ctx):
    batches = ctx.readings.get("batches")
    if not batches:
        return None
    return ctx.readings["dispatched_rows"] / batches

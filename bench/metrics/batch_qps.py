"""batch_qps: queries whose results reached the host in the window, over
the window (host clock).  The window closes when the last search's
results have reached the host, so it holds whole searches only."""


def read(ctx):
    r = ctx.readings
    if "queries" not in r:
        return None
    return r["queries"] / r["window_s"]

"""Device idle share (%) over the traced window of an ingest cell:
100 · (1 − busy / window), busy being the union of device operation
intervals, averaged over the chips (`chipbench.trace`)."""


def read(ctx):
    if ctx.trace is None or ctx.cell.traffic["kind"] != "ingest":
        return None
    if ctx.trace.n_devices == 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)

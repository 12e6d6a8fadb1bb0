"""Share of the traced window with no operation on the device:
1 - union of operation intervals / window, in %."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or ctx.trace.n_ops == 0:
        return None
    return 100.0 * ctx.trace.idle_share

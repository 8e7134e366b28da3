"""``idle_share.<cells>``: % of the traced window in which no op ran on the
device, mean over the chips."""


def read(ctx, variant=None):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy()
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx.trace.window_s)

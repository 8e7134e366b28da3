"""``eval_s``: window seconds over the whole forward evaluations in it."""


def read(ctx, variant=None):
    return ctx.window_s / ctx.units

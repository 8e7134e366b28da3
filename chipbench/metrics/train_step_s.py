"""``train_step_s``: window seconds over the whole training steps in it."""


def read(ctx, variant=None):
    return ctx.window_s / ctx.units

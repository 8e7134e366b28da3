"""``setup_s``: seconds from the start of the process to the first timed
unit: imports, data, compilation or cache loads, check steps, warm-up."""


def read(ctx, variant=None):
    return ctx.setup_s

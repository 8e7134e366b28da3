"""``horner_ms.<cells>``: device milliseconds per unit of the Horner
signature kernel, on the chip with the most.

The program names every Horner ``pallas_call`` ``signature.<role>.<launch
site>`` (``repro.kernels.KERNEL_NAMES``), and a kernel's custom call, so its
trace event, carries that name.
"""

from chipbench.metrics.kernel_ms import custom_call

PREFIX = "signature."


def horner(event) -> bool:
    return custom_call(event) and event.name.startswith(PREFIX)


def read(ctx, variant=None):
    if ctx.trace is None:
        return None
    most = max(ctx.trace.seconds(horner))
    return 1e3 * most / ctx.trace.units if most > 0 else None

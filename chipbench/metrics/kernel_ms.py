"""``kernel_ms.<role>.<cells>``: device milliseconds per unit of the Goursat
PDE kernels of one role, on the chip with the most.

The program names every Pallas kernel ``<layer>.<role>.<launch site>``
(``sigkernel_pde.fwd_ckpt._solve_flat``), and a kernel's custom call, so
its trace event, carries that name.  The role is the first part of the
metric's variant: ``kernel_ms.fwd_ckpt.train`` reads the custom calls
named ``sigkernel_pde.fwd_ckpt.*``, whichever wrapper launched them.  A
sharded Gram waits for its slowest chip, so the chip with the most counts.

The benchmark keeps the name prefixes itself: it reads the program only as
the system under test.  Custom calls under neither prefix are logged: a
kernel launched without a name shows there, beside XLA's own custom calls
(``AllocateBuffer``, ``ConcatBitcast``), which the reduced events cannot
tell apart from kernels (their ``custom_call_target`` is not kept).
"""

import re

PDE = "sigkernel_pde."
NAMED = (PDE, "signature.")


def custom_call(event) -> bool:
    return event.opcode == "custom-call"


def unnamed(trace) -> list:
    """Base names of the custom calls named by neither prefix."""
    return sorted({re.sub(r"\.\d+$", "", e.name)
                   for ops in trace.devices.values() for e in ops
                   if custom_call(e) and not e.name.startswith(NAMED)})


def read(ctx, variant=None):
    if ctx.trace is None or not variant:
        return None
    missing = unnamed(ctx.trace)
    if missing:
        ctx.log(f"[kernel_ms] custom calls named by neither "
                f"{' nor '.join(NAMED)}: {', '.join(missing)}")
    prefix = PDE + variant.split(".")[0] + "."
    most = max(ctx.trace.seconds(
        lambda e: custom_call(e) and e.name.startswith(prefix)))
    return 1e3 * most / ctx.trace.units if most > 0 else None

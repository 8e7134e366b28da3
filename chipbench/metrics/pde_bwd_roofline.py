"""``pde_bwd_roofline.<cells>``: share of the exact backward's roofline, %.

Numerator: the least time for the adjoint sweep the units needed
(``counts.pde_backward``; the forward recomputation inside the backward
kernel is not counted).  Denominator: the summed device time of the
backward kernels' events.
"""

from chipbench.metrics.pde_fwd_roofline import roofline

#: the exact-backward kernel, named after its wrapper ``_grad_flat``
KERNELS = ("_grad_flat",)


def read(ctx, variant=None):
    return roofline(ctx, "pde_bwd", KERNELS)

"""``engine_ms.<cells>``: device milliseconds per unit of every op outside
the PDE kernels and the collectives (transforms, increments and Δ, the
symmetric mirror, the reduction, the update), mean over the chips."""

from chipbench.metrics import collective_ms, pde_bwd_roofline, \
    pde_fwd_roofline

PDE = pde_fwd_roofline.KERNELS + pde_bwd_roofline.KERNELS


def outside(event) -> bool:
    return not (pde_fwd_roofline.matches(event, PDE)
                or collective_ms.matches(event))


def read(ctx, variant=None):
    if ctx.trace is None:
        return None
    per_device = ctx.trace.seconds(outside)
    return 1e3 * sum(per_device) / len(per_device) / ctx.trace.units

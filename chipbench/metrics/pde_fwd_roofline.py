"""``pde_fwd_roofline.<cells>``: share of the PDE forward's roofline, in %.

Numerator: the least time the chip could take for the forward work the
units needed (``counts.pde_forward``), the larger of flops over the VPU
f32 peak and bytes over HBM bandwidth, over all chips used.  Denominator:
the summed device time, over those chips, of the forward kernels' events.
The forward kernels that a backward pass reruns from checkpoints are
forward events too, so recomputation lowers the share.
"""

from chipbench import counts

#: The Goursat forward kernels as a TPU trace names them today: each is a
#: custom call named after the jitted wrapper in
#: ``repro.kernels.sigkernel_pde.ops`` (``jvp_jit__solve_fused_impl__.3``,
#: ``transpose_jvp_jit__solve_flat___.2``, ...).  ``_solve_flat`` is the
#: forward that saves checkpoints, which the backward pass reruns.
KERNELS = ("_solve_fused_impl", "_gram_fused_impl", "_solve_flat")


def matches(event, kernels=KERNELS) -> bool:
    return event.opcode == "custom-call" and any(k in event.name
                                                 for k in kernels)


def roofline(ctx, layer: str, kernels) -> float | None:
    work = ctx.work.get(layer)
    if ctx.trace is None or work is None:
        return None
    spent = sum(ctx.trace.seconds(lambda e: matches(e, kernels)))
    if spent <= 0:
        return None
    p = ctx.peaks
    least, bound = counts.roofline_seconds(
        work, p["vpu_f32_flops_per_s"] * len(ctx.devices),
        p["hbm_bytes_per_s"] * len(ctx.devices))
    ctx.log(f"[roofline] {layer}: {bound}-bound, least {least:.6e} s per "
            f"unit, kernels {spent / ctx.trace.units:.6e} s per unit "
            "summed over chips")
    return 100.0 * least * ctx.trace.units * len(ctx.devices) / spent


def read(ctx, variant=None):
    return roofline(ctx, "pde_fwd", KERNELS)

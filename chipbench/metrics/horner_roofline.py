"""``horner_roofline.<cells>``: share of the Horner signature kernel's
roofline, in %.

Numerator: the least time for the Horner work the units needed
(``counts_sig.horner``: pySigLib Alg. 2's operations at the VPU f32 peak,
or the increments read and the signatures written over HBM bandwidth,
whichever is larger), over all chips used.  Denominator: the summed device
time of the kernels named ``signature.``.
"""

from chipbench.metrics.horner_ms import PREFIX
from chipbench.metrics.pde_fwd_roofline import roofline


def read(ctx, variant=None):
    return roofline(ctx, "horner", (PREFIX,))

"""``sig_bwd_ms.<cells>``: device milliseconds per unit of the signature's
time-reversed exact backward, on the chip with the most.

The backward is plain XLA ops under the program span
``repro.signature.bwd`` (a ``jax.named_scope``).  The trace names ops by
HLO instruction only, so the unit reads from its compiled step which
instructions carry the scope in their ``op_name``
(``chipbench.hlo_scopes``) and hands the names on as
``work["op_names"]``; a unit that does not gives no reading.
"""

SCOPE = "repro.signature.bwd"


def read(ctx, variant=None):
    names = ctx.work.get("op_names", {}).get(SCOPE)
    if ctx.trace is None or not names:
        return None
    most = max(ctx.trace.seconds(lambda e: e.name in names))
    return 1e3 * most / ctx.trace.units if most > 0 else None

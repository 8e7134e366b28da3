"""Pallas TPU kernels for the paper's compute hot-spots.

- sigkernel_pde/: Goursat-PDE wavefront solver (fwd, exact bwd, fused-delta)
- signature/:     Horner truncated-signature kernel

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle).
"""

import jax

#: The ``name=`` of every ``pallas_call``, keyed by what it computes.  A
#: kernel's name is the instruction name of its custom call in the compiled
#: program, and so its event name in a device trace.  The form is
#: ``<layer>.<role>.<launch site>``: the role says what the kernel does
#: (``fwd``, ``fwd_ckpt`` for a forward that saves checkpoint rows for the
#: exact backward, ``bwd``), the launch site is the jitted wrapper in
#: ``ops.py`` that builds it.  Profiles group kernels by the first two parts;
#: docs/solver_guide.md ("Reading a profile") lists the names and the engine
#: scopes around them.
KERNEL_NAMES = {
    "fwd": "sigkernel_pde.fwd._solve_flat",
    "fwd_ckpt": "sigkernel_pde.fwd_ckpt._solve_flat",
    "fwd_fused": "sigkernel_pde.fwd._solve_fused_impl",
    "gram_fused": "sigkernel_pde.fwd._gram_fused_impl",
    "bwd": "sigkernel_pde.bwd._grad_flat",
    "horner": "signature.horner._horner_flat",
}


def interpret_mode() -> bool:
    """Mosaic compiles the kernels for a TPU; on any other backend they run
    in the Pallas interpreter."""
    return jax.default_backend() != "tpu"

"""Pallas TPU kernels for the paper's compute hot-spots.

- sigkernel_pde/: Goursat-PDE wavefront solver (fwd, exact bwd, fused-delta)
- signature/:     Horner truncated-signature kernel

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle).
"""

import jax


def interpret_mode() -> bool:
    """Mosaic compiles the kernels for a TPU; on any other backend they run
    in the Pallas interpreter."""
    return jax.default_backend() != "tpu"

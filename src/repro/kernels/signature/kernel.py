"""Pallas TPU kernel: Horner's algorithm for truncated signatures (pySigLib §2.3).

Realises the paper's memory-discipline choices natively in VMEM:

(1) the whole truncated signature (A_1..A_N) lives as ONE flattened
    contiguous scratch buffer of shape (sig_dim, BT) — levels back-to-back on
    the sublane axis, a batch tile of BT paths on the lane axis;
(2) levels are updated in REVERSE order (A_N → A_1) in place, so each
    path-step needs no second signature buffer;
(3) the Horner accumulator B_k is a single register/VMEM value reused by all
    levels (its tensor-product-by-z is a broadcast multiply + contiguous
    reshape — no strided writes);
(4) the final ``B_k ⊗ z + A_k`` accumulates directly into the signature
    buffer.

The tensor product with a level-1 increment in (level, batch) layout is

    C[(a·d + j), b] = A[a, b] · z[j, b]
      == (A[:, None, :] * z[None, :, :]).reshape(-1, BT)

i.e. a VPU broadcast multiply followed by a free (contiguous) reshape — this
is the TPU-native replacement for the paper's reverse-order in-place scalar
loop (DESIGN.md §2).

Grid = (batch_tiles, L_blocks); the signature scratch persists across the
sequential L-block sweep, so arbitrarily long paths stream through a fixed
VMEM working set.  Zero increments are exact no-ops (exp(0) = 1), so ops.py
pads both batch and length freely.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.tensoralg import level_offsets, level_sizes, sig_dim
from repro.kernels import KERNEL_NAMES


def vmem_scratch(shape, dtype=jnp.float32):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def horner_kernel(z_ref, out_ref, a_ref, *, d: int, depth: int, LB: int,
                  BT: int, n_lb: int, offs: List[int], sizes: List[int]):
    """One (batch_tile, L_block) grid step: LB Horner path-steps in VMEM."""
    lb = pl.program_id(1)

    @pl.when(lb == 0)
    def _reset():
        a_ref[...] = jnp.zeros_like(a_ref)

    def outer_z(a, z):
        """Tensor product with a level-1 increment: contiguous in this layout."""
        return (a[:, None, :] * z[None, :, :]).reshape(-1, BT)

    def step(li, carry):
        z = z_ref[0, li]                                  # (d, BT)
        # --- Horner's scheme (paper Alg 2), levels updated in reverse ---
        for k in range(depth, 1, -1):
            B = z / float(k)
            for i in range(1, k - 1):
                Ai = a_ref[offs[i - 1]:offs[i - 1] + sizes[i - 1], :]
                B = outer_z(B + Ai, z / float(k - i))
            Akm1 = a_ref[offs[k - 2]:offs[k - 2] + sizes[k - 2], :]
            B = B + Akm1
            sl = slice(offs[k - 1], offs[k - 1] + sizes[k - 1])
            a_ref[sl, :] = a_ref[sl, :] + outer_z(B, z)
        a_ref[offs[0]:offs[0] + sizes[0], :] = \
            a_ref[offs[0]:offs[0] + sizes[0], :] + z
        return carry

    jax.lax.fori_loop(0, LB, step, 0)

    @pl.when(lb == n_lb - 1)
    def _emit():
        out_ref[0] = a_ref[...]


def build_horner(n_tiles: int, Lp: int, d: int, depth: int, *, BT: int,
                 LB: int, interpret: bool):
    """pallas_call for increments laid out as (n_tiles, Lp, d, BT), Lp % LB == 0."""
    if Lp % LB != 0:
        raise ValueError(
            f"Horner kernel needs the padded length Lp={Lp} to be a "
            f"multiple of the length block LB={LB} — pick a "
            f"LaunchConfig.sig_lb that divides the padded length (the "
            f"ops.py wrapper pads to the block automatically)")
    n_lb = Lp // LB
    sd = sig_dim(d, depth)
    kern = functools.partial(
        horner_kernel, d=d, depth=depth, LB=LB, BT=BT, n_lb=n_lb,
        offs=level_offsets(d, depth), sizes=level_sizes(d, depth))
    return pl.pallas_call(
        kern,
        grid=(n_tiles, n_lb),
        in_specs=[pl.BlockSpec((1, LB, d, BT), lambda t, lb: (t, lb, 0, 0))],
        out_specs=pl.BlockSpec((1, sd, BT), lambda t, lb: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, sd, BT), jnp.float32),
        scratch_shapes=[vmem_scratch((sd, BT))],
        interpret=interpret,
        name=KERNEL_NAMES["horner"],
    )

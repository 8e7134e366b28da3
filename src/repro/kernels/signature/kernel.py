"""Pallas TPU kernel: Horner's algorithm for truncated signatures (pySigLib §2.3).

Realises the paper's memory-discipline choices natively in VMEM:

(1) a program's part of the truncated signature (A_1..A_N) lives as ONE
    flattened contiguous accumulator of shape (rows, BT) — levels
    back-to-back on the sublane axis, a batch tile of BT paths on the lane
    axis;
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

Deep signatures do not fit VMEM whole (d=5, N=7 is 97,655 rows, 50 MB at
128 lanes), so the words are split by their first ``prefix`` letters.  A
word's row in A_k ⊗ z is its prefix's row of A_k times one letter of z, so
the rows of A_k that start with a prefix u depend only on the rows of the
lower levels that start with u (or, for a level no longer than u, on u's own
prefix of that length).  Each program holds the rows of its prefix — d^(k-p)
of level k above the prefix length p, one row below it — and repeats the
small lower-level chain the others repeat too.  The arithmetic of every row
is that of the whole kernel, so the result does not depend on ``prefix``.

Grid = (batch_tiles, d, ..., d, L_blocks) with one axis of d per prefix
letter; a program's accumulator is its output block, which stays in VMEM
across the sequential L-block sweep, so arbitrarily long paths stream
through a fixed VMEM working set.  Zero increments are exact no-ops
(exp(0) = 1), so ops.py pads both batch and length freely.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import KERNEL_NAMES

SUBLANES, LANES = 8, 128


def prefix_rows(d: int, depth: int, prefix: int) -> List[int]:
    """Rows of levels 1..depth that a program with a ``prefix``-letter
    prefix holds: d^(k - prefix) of level k, one row of a level no longer
    than the prefix."""
    return [d ** max(k - prefix, 0) for k in range(1, depth + 1)]


def _tiles(rows: int) -> int:
    """Rows of f32 (8, 128) tiles that ``rows`` rows occupy."""
    return -(-rows // SUBLANES) * SUBLANES


def vmem_bytes(d: int, depth: int, prefix: int, LB: int, BT: int) -> int:
    """VMEM of one program as Mosaic lays it out in (8, 128) f32 tiles: the
    double-buffered accumulator and increment blocks, and the top level's
    temporaries (the (rows, d, BT) product before its reshape pads d to
    eight sublanes)."""
    rows = prefix_rows(d, depth, prefix)
    lanes = -(-BT // LANES) * LANES
    temps = (SUBLANES * rows[-2] + 3 * _tiles(rows[-1]) if depth > 1
             else _tiles(rows[0]))
    return 4 * lanes * (2 * _tiles(sum(rows)) + 2 * LB * _tiles(d) + temps)


def horner_kernel(z_ref, out_ref, *, d: int, depth: int, prefix: int,
                  LB: int, BT: int, offs: List[int], rows: List[int]):
    """One (batch_tile, prefix, L_block) grid step: LB Horner path-steps on
    the rows of one prefix, in VMEM."""
    lb = pl.program_id(prefix + 1)
    letters = [pl.program_id(j) for j in range(1, prefix + 1)]
    at = (0,) * (prefix + 1)

    @pl.when(lb == 0)
    def _reset():
        out_ref[...] = jnp.zeros_like(out_ref)

    def level(k):
        return at + (slice(offs[k - 1], offs[k - 1] + rows[k - 1]),
                     slice(None))

    def outer_z(a, z):
        """Tensor product with a level-1 increment: contiguous in this layout."""
        return (a[:, None, :] * z[None, :, :]).reshape(-1, BT)

    def step(li, carry):
        z = z_ref[0, li]                                  # (d, BT)
        # zp[j]: the row of z at the prefix's j-th letter, (1, BT)
        zp = []
        if prefix:
            row = jax.lax.broadcasted_iota(jnp.int32, (d, BT), 0)
            zp = [jnp.sum(jnp.where(row == a, z, 0.0), axis=0,
                          keepdims=True) for a in letters]

        def times(b, m, scale=None):
            """B ⊗ z/scale, the rows of level m that the program holds."""
            if m <= prefix:
                w = zp[m - 1]
                return b * (w if scale is None else w / scale)
            return outer_z(b, z if scale is None else z / scale)

        # --- Horner's scheme (paper Alg 2), levels updated in reverse ---
        for k in range(depth, 1, -1):
            B = (zp[0] if prefix else z) / float(k)
            for i in range(1, k - 1):
                B = times(B + out_ref[level(i)], i + 1, float(k - i))
            B = B + out_ref[level(k - 1)]
            out_ref[level(k)] = out_ref[level(k)] + times(B, k)
        out_ref[level(1)] = out_ref[level(1)] + (zp[0] if prefix else z)
        return carry

    jax.lax.fori_loop(0, LB, step, 0)


def build_horner(n_tiles: int, Lp: int, d: int, depth: int, *, BT: int,
                 LB: int, interpret: bool, prefix: int = 0):
    """pallas_call for increments laid out as (n_tiles, Lp, d, BT), Lp % LB == 0.

    Returns (n_tiles, d, ..., d, rows, BT): for each batch tile and each
    ``prefix``-letter prefix, the ``prefix_rows`` of every level that start
    with it (``ops.py`` puts the levels back together).
    """
    if Lp % LB != 0:
        raise ValueError(
            f"Horner kernel needs the padded length Lp={Lp} to be a "
            f"multiple of the length block LB={LB} — pick a "
            f"LaunchConfig.sig_lb that divides the padded length (the "
            f"ops.py wrapper pads to the block automatically)")
    if not 0 <= prefix < depth:
        raise ValueError(f"prefix must lie in [0, depth={depth}), "
                         f"got {prefix}")
    n_lb = Lp // LB
    rows = prefix_rows(d, depth, prefix)
    R = sum(rows)
    offs = [sum(rows[:k]) for k in range(depth)]
    kern = functools.partial(
        horner_kernel, d=d, depth=depth, prefix=prefix, LB=LB, BT=BT,
        offs=offs, rows=rows)
    est = vmem_bytes(d, depth, prefix, LB, BT)
    return pl.pallas_call(
        kern,
        grid=(n_tiles,) + (d,) * prefix + (n_lb,),
        in_specs=[pl.BlockSpec((1, LB, d, BT),
                               lambda t, *u: (t, u[-1], 0, 0))],
        out_specs=pl.BlockSpec((1,) * (prefix + 1) + (R, BT),
                               lambda t, *u: (t, *u[:-1], 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_tiles,) + (d,) * prefix + (R, BT), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 * 2 ** 20, max(32 * 2 ** 20, 2 * est))),
        interpret=interpret,
        name=KERNEL_NAMES["horner"],
    )


def assemble(out: jax.Array, d: int, depth: int, prefix: int) -> jax.Array:
    """(n_tiles, d, ..., d, rows, BT) per-prefix rows -> (n_tiles, sig_dim,
    BT) flat levels.  A level no longer than the prefix is held once by
    every program that shares its word: the programs whose later letters
    are all 0 give it."""
    if not prefix:
        return out
    n_tiles, BT = out.shape[0], out.shape[-1]
    rows = prefix_rows(d, depth, prefix)
    out = out.reshape(n_tiles, d ** prefix, sum(rows), BT)
    levels, off = [], 0
    for k, n in enumerate(rows, 1):
        if k >= prefix:
            levels.append(out[:, :, off:off + n].reshape(n_tiles, d ** k, BT))
        else:
            levels.append(out[:, ::d ** (prefix - k), off])
        off += n
    return jnp.concatenate(levels, axis=1)

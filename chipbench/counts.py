"""Work of each layer, counted from the shapes alone.

The counts are those of the algorithm, not of an implementation, so they
are the same whichever backend (``pallas``, ``pallas_fused``, ``antidiag``
or a later one) does the work, and a kernel that is fused or replaced
cannot make them stale.

PDE forward, per refined cell (order-1 scheme, arXiv 2509.10613 eq. (1))::

    k[i+1, j+1] = (k[i+1, j] + k[i, j+1])·A(p) − k[i, j]·B(p)
    A(p) = 1 + p·(1/2 + p/12)      4 flops: p/12, +1/2, ·p, +1
    B(p) = 1 − p·(p/12)            2 flops: ·p on p/12, 1 −
    update                         4 flops: +, ·A, k·B, −
                                  --
                                  10 flops

PDE exact backward, per refined cell (the adjoint sweep of the same
recurrence, with g = ∂F/∂k and out-of-grid g = 0)::

    g[a, b] = g[a, b+1]·A(p[a−1, b]) + g[a+1, b]·A(p[a, b−1])
              − g[a+1, b+1]·B(p[a, b])
    dΔ[i, j] += g[i+1, j+1]·((k[i+1, j] + k[i, j+1])·A'(p) − k[i, j]·B'(p))
    A'(p) = 1/2 + p/6,  B'(p) = −p/6

    A, B of the cell (each read by two adjoint updates)   6 flops
    A', B' of the cell                                    3 flops
    g update: 3 products, 2 sums                          5 flops
    dΔ: +, ·A', k·B', −, ·g, accumulate                   6 flops
                                                         --
                                                         20 flops

The forward values k that the adjoint reads are recomputed from
checkpoints by the backward kernels; that recomputation is not counted.
Bytes are the increment streams read plus the results written (and, for
the backward, the cotangents read and the increment gradients written):
never a Δ that one backend happens to materialise.
"""

from __future__ import annotations

import dataclasses

F32 = 4
PDE_FWD_FLOPS_PER_CELL = 10
PDE_BWD_FLOPS_PER_CELL = 20


@dataclasses.dataclass(frozen=True)
class Work:
    """Floating-point operations and HBM bytes of one unit of one layer."""

    flops: float
    bytes: float


def refined_cells(steps_x: int, steps_y: int, lam1: int, lam2: int) -> int:
    """Cells of one pair's refined grid: (steps_x·2^λ1)·(steps_y·2^λ2)."""
    return (steps_x << lam1) * (steps_y << lam2)


def stream_bytes(batch: int, steps: int, channels: int) -> int:
    """Bytes of a batch of f32 increment streams."""
    return batch * steps * channels * F32


def pde_forward(pairs: int, cells: int, streams_read: int) -> Work:
    """Forward solve of ``pairs`` pairs of ``cells`` refined cells each."""
    return Work(flops=float(pairs * cells * PDE_FWD_FLOPS_PER_CELL),
                bytes=float(streams_read + pairs * F32))


def pde_backward(pairs: int, cells: int, streams_read: int,
                 grads_written: int) -> Work:
    """Adjoint sweep of ``pairs`` pairs, recomputation not counted."""
    return Work(flops=float(pairs * cells * PDE_BWD_FLOPS_PER_CELL),
                bytes=float(streams_read + pairs * F32 + grads_written))


def mmd2_unbiased_pairs(bx: int, by: int) -> tuple:
    """Pairs the unbiased MMD² needs: (forward, backward w.r.t. X only).

    The estimator reads the off-diagonal halves of K_xx and K_yy and all of
    K_xy; the gradient with respect to X reaches K_xx and K_xy alone.
    """
    kxx, kyy, kxy = bx * (bx - 1) // 2, by * (by - 1) // 2, bx * by
    return kxx + kyy + kxy, kxx + kxy


def symmetric_gram_pairs(n: int) -> int:
    """Pairs of a symmetric n×n Gram: the upper triangle with its diagonal."""
    return n * (n + 1) // 2


def roofline_seconds(work: Work, flops_per_s: float,
                     bytes_per_s: float) -> tuple:
    """Least time the chip could take for ``work``, and what bounds it."""
    t_flops, t_bytes = work.flops / flops_per_s, work.bytes / bytes_per_s
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")

"""Plain reference for the signature-kernel cells: jax.numpy, float32.

The signature kernel of two paths under the linear static kernel solves the
Goursat PDE ``∂²k/∂s∂t = ⟨ẋ(s), ẏ(t)⟩·k`` with ``k = 1`` on both axes.  On
the refined grid of ``(Lx−1)·2^λ1 × (Ly−1)·2^λ2`` cells, with ``p`` the
inner product of the two increments of the unrefined cell divided by
``2^(λ1+λ2)``, the order-1 scheme (arXiv 2509.10613, eq. (1)) reads::

    k[i+1, j+1] = (k[i+1, j] + k[i, j+1])·A(p) − k[i, j]·B(p)
    A(p) = 1 + p/2 + p²/12,   B(p) = 1 − p²/12

and the kernel is the far corner ``k[nx, ny]``.  Gradients are plain
autodiff of this recurrence.  The only matmuls, the increment inner
products, run at ``Precision.HIGHEST``.

This module imports nothing of the program under test, and takes from it
no weights, tables or intermediate results.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.data import HIGHEST


def coeff_a(p):
    return 1.0 + p * (0.5 + p / 12.0)


def coeff_b(p):
    return 1.0 - p * (p / 12.0)


def increments(paths: jax.Array, time_aug: bool) -> jax.Array:
    """(B, L, d) paths -> (B, L−1, d') increments, with a uniform time
    channel over [0, 1] appended when ``time_aug``."""
    z = paths[:, 1:] - paths[:, :-1]
    if time_aug:
        dt = jnp.full(z.shape[:-1] + (1,), 1.0 / z.shape[1], z.dtype)
        z = jnp.concatenate([z, dt], axis=-1)
    return z


def goursat(delta: jax.Array, lam1: int, lam2: int) -> jax.Array:
    """k[nx, ny] for a batch of increment inner products (P, Lx, Ly) -> (P,).

    Sweeps anti-diagonals: diagonal ``s`` holds the cells ``(i, s − i)`` at
    lane ``i``.  Lanes outside the grid hold 1 and are never read by a cell
    inside it.
    """
    P, Lx, Ly = delta.shape
    nx, ny = Lx << lam1, Ly << lam2
    m = jnp.repeat(jnp.repeat(delta, 1 << lam1, axis=1), 1 << lam2, axis=2)
    m = m * 2.0 ** -(lam1 + lam2)                              # (P, nx, ny)
    lane = jnp.arange(nx + 1)
    s = jnp.arange(2, nx + ny + 1)[:, None]                    # (S, 1)
    j = s - lane                                               # (S, nx + 1)
    inside = (lane >= 1) & (j >= 1) & (j <= ny)
    # cell (i, j) is updated with p of unrefined-refined cell (i − 1, j − 1)
    p = m[:, jnp.clip(lane - 1, 0, nx - 1), jnp.clip(j - 1, 0, ny - 1)]
    p = jnp.where(inside, p, 0.0).transpose(1, 0, 2)           # (S, P, nx+1)
    ones = jnp.ones((P, 1), delta.dtype)

    def step(carry, xs):
        d1, d2 = carry                       # diagonals s − 1 and s − 2
        p_s, inside_s = xs
        left = d1                                            # k[i, j − 1]
        up = jnp.concatenate([ones, d1[:, :-1]], axis=1)     # k[i − 1, j]
        upleft = jnp.concatenate([ones, d2[:, :-1]], axis=1)  # k[i−1, j−1]
        cur = (left + up) * coeff_a(p_s) - upleft * coeff_b(p_s)
        return (jnp.where(inside_s, cur, 1.0), d1), None

    start = jnp.ones((P, nx + 1), delta.dtype)
    (last, _), _ = jax.lax.scan(step, (start, start), (p, inside))
    return last[:, nx]


def gram(sx: jax.Array, sy: jax.Array, lam1: int, lam2: int,
         rows: int) -> jax.Array:
    """Gram (Bx, By) of increment streams, ``rows`` rows at a time.

    Each block is rematerialised in the backward pass, so only one block's
    sweep is held at a time.
    """
    Bx = sx.shape[0]
    if Bx % rows:
        raise ValueError(f"rows={rows} does not divide Bx={Bx}")

    @jax.checkpoint
    def block(sxb):
        delta = jnp.einsum("aid,bjd->abij", sxb, sy, precision=HIGHEST)
        k = goursat(delta.reshape((-1,) + delta.shape[2:]), lam1, lam2)
        return k.reshape(rows, -1)

    blocks = sx.reshape((Bx // rows, rows) + sx.shape[1:])
    return jax.lax.map(block, blocks).reshape(Bx, -1)


def pair_kernels(sa: jax.Array, sb: jax.Array, lam1: int,
                 lam2: int) -> jax.Array:
    """k(a_p, b_p) for paired increment streams (P, L, d) -> (P,)."""
    delta = jnp.einsum("pid,pjd->pij", sa, sb, precision=HIGHEST)
    return goursat(delta, lam1, lam2)


def mmd2_unbiased(X: jax.Array, Y: jax.Array, *, time_aug: bool, lam1: int,
                  lam2: int, rows: int) -> tuple:
    """Unbiased squared MMD of two path batches under the signature kernel,
    and the sum of the magnitudes of its three Gram means, which sets the
    scale of its rounding."""
    sx, sy = increments(X, time_aug), increments(Y, time_aug)
    bx, by = X.shape[0], Y.shape[0]
    kxx = gram(sx, sx, lam1, lam2, rows)
    kyy = gram(sy, sy, lam1, lam2, rows)
    kxy = gram(sx, sy, lam1, lam2, rows)
    sxx = (kxx.sum() - jnp.trace(kxx)) / (bx * (bx - 1))
    syy = (kyy.sum() - jnp.trace(kyy)) / (by * (by - 1))
    sxy = kxy.mean()
    return sxx + syy - 2.0 * sxy, abs(sxx) + abs(syy) + 2.0 * abs(sxy)

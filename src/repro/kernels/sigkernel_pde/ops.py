"""Jit'd public wrappers for the sig-kernel PDE Pallas kernels.

Responsibilities:
* dtype discipline (compute in f32; bf16/f16 inputs are upcast),
* batch flattening,
* zero-padding Lx to the strip granularity (Δ = 0 rows/cols leave the Goursat
  solution invariant because A(0) = B(0) = 1, so padding is exact — and the
  padded problem's *exact* adjoint restricted to the real Δ block is the real
  problem's exact adjoint),
* zero-padding the batch of the fused kernels to whole packs of
  ``kernel.fused_pack`` pairs (zero increments give k = 1 pairs, sliced
  off), and that of the exact backward to whole packs of
  ``grad_kernel.bwd_pack`` pairs (zero Δ and zero cotangent give a zero
  adjoint, sliced off),
* strip-height (T) selection,
* interpret-mode selection (TPU: compiled; elsewhere interpret=True).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import dispatch
from .. import interpret_mode
from .kernel import build_fwd, build_fwd_fused, build_gram_fused, fused_pack
from .grad_kernel import build_bwd, bwd_pack

_MAX_T = 128


def choose_T(lam1: int, max_t: int = _MAX_T) -> int:
    """Strip height: the cap (one strip row per lane), at least 2**lam1.

    The VMEM working set is a few (W, 128)-padded buffers whatever T ≤ 128
    is, so a lower strip saves nothing; ``kernel.vmem_limit`` sizes it.
    """
    return max(max_t, 1 << lam1)


def _pad_batched(delta: jax.Array, R: int):
    B, Lx, Ly = delta.shape
    pad = (-Lx) % R
    if pad:
        delta = jnp.pad(delta, ((0, 0), (0, pad), (0, 0)))
    return delta, Lx + pad


def _max_t(launch) -> int:
    """Strip-height cap from a LaunchConfig (``None`` -> module default)."""
    return getattr(launch, "pde_strip", None) or _MAX_T


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _solve_flat(delta: jax.Array, lam1: int, lam2: int, with_cps: bool,
                launch=None, scheme: str = "order1",
                interior_dtype: str = "float32"):
    B, Lx, Ly = delta.shape
    T = choose_T(lam1, _max_t(launch))
    delta, Lxp = _pad_batched(delta, T >> lam1)
    call = build_fwd(B, Lxp, Ly, T=T, lam1=lam1, lam2=lam2,
                     save_cps=with_cps, interpret=interpret_mode(), scheme=scheme,
                     interior_dtype=interior_dtype)
    out = call(delta)
    return out


def solve(delta: jax.Array, lam1: int = 0, lam2: int = 0, launch=None,
          scheme: str = "order1",
          interior_dtype: str = "float32") -> jax.Array:
    """Final kernel values for Δ (..., Lx, Ly) -> (...,)."""
    batch_shape = delta.shape[:-2]
    flat = delta.reshape((-1,) + delta.shape[-2:]).astype(jnp.float32)
    k = _solve_flat(flat, lam1, lam2, False, launch, scheme, interior_dtype)
    return k.reshape(batch_shape)


def solve_with_grid(delta: jax.Array, lam1: int = 0, lam2: int = 0,
                    launch=None, scheme: str = "order1",
                    interior_dtype: str = "float32"):
    """Forward + residuals for the exact backward (checkpoint rows, not the
    full grid).  Returns (k, cps)."""
    batch_shape = delta.shape[:-2]
    flat = delta.reshape((-1,) + delta.shape[-2:]).astype(jnp.float32)
    k, cps = _solve_flat(flat, lam1, lam2, True, launch, scheme,
                         interior_dtype)
    return k.reshape(batch_shape), cps


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _grad_flat(delta, cps, gbar, lam1, lam2, launch=None,
               scheme: str = "order1", interior_dtype: str = "float32"):
    B, Lx, Ly = delta.shape
    T = choose_T(lam1, _max_t(launch))
    delta, Lxp = _pad_batched(delta, T >> lam1)
    P = bwd_pack(Ly << lam2, T, B)
    Bp = -(-B // P) * P
    dispatch.record_packed_slots(Bp, B, slot="bwd_pack")
    delta, cps, gbar = (_pad_rows(a, 0, Bp) for a in (delta, cps, gbar))
    call = build_bwd(Bp, Lxp, Ly, T=T, lam1=lam1, lam2=lam2,
                     interpret=interpret_mode(), scheme=scheme,
                     interior_dtype=interior_dtype, pack=P)
    dd = call(delta, delta, cps, gbar)
    return dd[:B, :Lx, :]


def solve_grad(delta: jax.Array, cps: jax.Array, gbar: jax.Array,
               lam1: int = 0, lam2: int = 0, launch=None,
               scheme: str = "order1",
               interior_dtype: str = "float32") -> jax.Array:
    """Exact ∂F/∂Δ (paper Alg 4) from saved checkpoint rows.

    ``launch`` must match the forward's — the checkpoint-row cadence is the
    strip height, so backward strips must line up with the saved rows (and
    the scheme/interior_dtype must match: the backward recomputes strip
    interiors with the SAME stencil and rounding the forward used).
    """
    batch_shape = delta.shape[:-2]
    flat = delta.reshape((-1,) + delta.shape[-2:]).astype(jnp.float32)
    g = gbar.reshape((-1,)).astype(jnp.float32)
    dd = _grad_flat(flat, cps, g, lam1, lam2, launch, scheme, interior_dtype)
    return dd.reshape(batch_shape + dd.shape[-2:]).astype(delta.dtype)


# ---------------------------------------------------------------------------
# fused-Δ variants (beyond-paper: Δ never exists in HBM — see kernel.py)
#
# Both are differentiable: the forward never materialises Δ, and the
# custom_vjp backward falls back to the checkpointed exact scheme (Alg 4) —
# Δ is rebuilt for the reverse sweep only, and the backward kernel itself
# recomputes strip interiors from the forward's checkpoint rows.
# ---------------------------------------------------------------------------

def _pad_rows(a: jax.Array, axis: int, n: int) -> jax.Array:
    """Zero-pad ``a`` along ``axis`` to length ``n``: zero increments give
    zero Δ rows or pairs, exact no-ops (k = 1) that the caller drops."""
    pad = n - a.shape[axis]
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _packed(n: int, Ly: int, lam2: int, T: int) -> int:
    """``n`` pairs rounded up to whole packs of the fused kernels."""
    P = fused_pack(Ly << lam2, T, n)
    return -(-n // P) * P


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _solve_fused_impl(dx: jax.Array, dy: jax.Array, lam1: int,
                      lam2: int, launch=None, scheme: str = "order1",
                      interior_dtype: str = "float32") -> jax.Array:
    B, Lx, d = dx.shape
    Ly = dy.shape[1]
    T = choose_T(lam1, _max_t(launch))
    Lxp = -(-Lx // (T >> lam1)) * (T >> lam1)
    Bp = _packed(B, Ly, lam2, T)
    dispatch.record_packed_slots(Bp, B)
    dx = _pad_rows(_pad_rows(dx, 1, Lxp), 0, Bp)
    dy = _pad_rows(dy, 0, Bp)
    call = build_fwd_fused(Bp, Lxp, Ly, d, T=T, lam1=lam1, lam2=lam2,
                           interpret=interpret_mode(), scheme=scheme,
                           interior_dtype=interior_dtype)
    return call(dx.astype(jnp.float32), dy.astype(jnp.float32))[:B]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def solve_fused(dx: jax.Array, dy: jax.Array, lam1: int = 0,
                lam2: int = 0, launch=None, scheme: str = "order1",
                interior_dtype: str = "float32") -> jax.Array:
    """k̂ final values from increments directly. dx: (B, Lx, d), dy: (B, Ly, d)."""
    return _solve_fused_impl(dx, dy, lam1, lam2, launch, scheme,
                             interior_dtype)


def _solve_fused_fwd(dx, dy, lam1, lam2, launch, scheme="order1",
                     interior_dtype="float32"):
    return (_solve_fused_impl(dx, dy, lam1, lam2, launch, scheme,
                              interior_dtype), (dx, dy))


def _delta_pullback(dd, dx, dy):
    """Pull ∂F/∂Δ back through Δ = dx · dyᵀ onto the increments."""
    with jax.named_scope("repro.pde.pullback"):
        ddx = jnp.einsum("...ij,...jd->...id", dd, dy.astype(dd.dtype))
        ddy = jnp.einsum("...ij,...id->...jd", dd, dx.astype(dd.dtype))
        return ddx.astype(dx.dtype), ddy.astype(dy.dtype)


def _solve_fused_bwd(lam1, lam2, launch, scheme, interior_dtype, res, gbar):
    dx, dy = res
    with jax.named_scope("repro.pde.pullback"):
        delta = jnp.einsum("bid,bjd->bij", dx.astype(jnp.float32),
                           dy.astype(jnp.float32))
    _, cps = solve_with_grid(delta, lam1, lam2, launch, scheme,
                             interior_dtype)
    dd = solve_grad(delta, cps, gbar, lam1, lam2, launch, scheme,
                    interior_dtype)
    return _delta_pullback(dd, dx, dy)


solve_fused.defvjp(_solve_fused_fwd, _solve_fused_bwd)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _gram_fused_impl(dX: jax.Array, dY: jax.Array, lam1: int,
                     lam2: int, launch=None, scheme: str = "order1",
                     interior_dtype: str = "float32") -> jax.Array:
    Bx, Lx, d = dX.shape
    By, Ly = dY.shape[0], dY.shape[1]
    T = choose_T(lam1, _max_t(launch))
    Lxp = -(-Lx // (T >> lam1)) * (T >> lam1)
    Byp = _packed(By, Ly, lam2, T)
    dispatch.record_packed_slots(Bx * Byp, Bx * By)
    dX = _pad_rows(dX, 1, Lxp)
    dY = _pad_rows(dY, 0, Byp)
    call = build_gram_fused(Bx, Byp, Lxp, Ly, d, T=T, lam1=lam1,
                            lam2=lam2, interpret=interpret_mode(), scheme=scheme,
                            interior_dtype=interior_dtype)
    return call(dX.astype(jnp.float32), dY.astype(jnp.float32))[:, :By]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def gram_fused(dX: jax.Array, dY: jax.Array, lam1: int = 0,
               lam2: int = 0, launch=None, scheme: str = "order1",
               interior_dtype: str = "float32") -> jax.Array:
    """Full Gram from increments. dX: (Bx, Lx, d), dY: (By, Ly, d) -> (Bx, By)."""
    return _gram_fused_impl(dX, dY, lam1, lam2, launch, scheme,
                            interior_dtype)


def _gram_fused_fwd(dX, dY, lam1, lam2, launch, scheme="order1",
                    interior_dtype="float32"):
    return (_gram_fused_impl(dX, dY, lam1, lam2, launch, scheme,
                             interior_dtype), (dX, dY))


def _gram_fused_bwd(lam1, lam2, launch, scheme, interior_dtype, res, gbar):
    # The reverse sweep materialises the Bx·By pairwise Δ block — bound it by
    # row-blocking the Gram (repro.core.gram), which confines this to one
    # block at a time.
    dX, dY = res
    with jax.named_scope("repro.pde.pullback"):
        delta = jnp.einsum("aid,bjd->abij", dX.astype(jnp.float32),
                           dY.astype(jnp.float32))
    _, cps = solve_with_grid(delta, lam1, lam2, launch, scheme,
                             interior_dtype)
    dd = solve_grad(delta, cps, gbar, lam1, lam2, launch, scheme,
                    interior_dtype)
    with jax.named_scope("repro.pde.pullback"):
        ddX = jnp.einsum("abij,bjd->aid", dd, dY.astype(dd.dtype))
        ddY = jnp.einsum("abij,aid->bjd", dd, dX.astype(dd.dtype))
        return ddX.astype(dX.dtype), ddY.astype(dY.dtype)


gram_fused.defvjp(_gram_fused_fwd, _gram_fused_bwd)

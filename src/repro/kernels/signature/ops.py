"""Jit'd public wrapper for the Horner signature Pallas kernel.

Handles batch/length padding (zero increments are exact no-ops), the
(batch, L, d) -> (tiles, L, d, BT) layout transform, the split of deep
signatures by leading letters under the VMEM budget, and exact backprop:
the backward pass is the time-reversed signature deconstruction of
pySigLib §2.4 (O(1) memory in path length), reusing the validated pure-JAX
implementation in ``repro.core.signature``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.tensoralg import sig_dim
from .. import interpret_mode
from .kernel import LANES, assemble, build_horner, vmem_bytes

_VMEM_BUDGET = 16 * 1024 * 1024
_MAX_BT = 128
_LB = 256


def default_use_pallas() -> bool:
    """Backend-based default for ``use_pallas=None``: the compiled kernel on
    TPU; elsewhere it would run in interpret mode, so the pure-JAX
    implementation is preferred."""
    return not interpret_mode()


def choose_prefix(d: int, depth: int, LB: int, BT: int) -> int:
    """Fewest leading letters by which the Horner kernel splits the words so
    that one program's working set fits the VMEM budget.  A tile takes
    (8, 128) VMEM tiles whatever its width, so the batch tile stays whole
    and the levels are split instead."""
    prefix = 0
    while (prefix < depth - 1
           and vmem_bytes(d, depth, prefix, LB, BT) > _VMEM_BUDGET):
        prefix += 1
    return prefix


@functools.partial(jax.jit, static_argnums=(1, 2))
def _horner_flat(z: jax.Array, depth: int, launch=None) -> jax.Array:
    from repro.core import dispatch
    from repro.core.config import resolve_launch
    launch = resolve_launch(launch)
    B, Lm1, d = z.shape
    LB = min(launch.sig_lb or _LB, max(Lm1, 1))
    BT = min(launch.sig_bt or _MAX_BT, _MAX_BT)
    prefix = choose_prefix(d, depth, LB, BT)
    Bp = -(-B // BT) * BT
    Lp = -(-Lm1 // LB) * LB
    zp = jnp.pad(z.astype(jnp.float32), ((0, Bp - B), (0, Lp - Lm1), (0, 0)))
    n_tiles = Bp // BT
    dispatch.record_horner_lanes(n_tiles * -(-BT // LANES) * LANES, B)
    zt = zp.reshape(n_tiles, BT, Lp, d).transpose(0, 2, 3, 1)  # (t, L, d, BT)
    out = build_horner(n_tiles, Lp, d, depth, BT=BT, LB=LB, prefix=prefix,
                       interpret=interpret_mode())(zt)
    out = assemble(out, d, depth, prefix)                       # (t, sd, BT)
    sd = sig_dim(d, depth)
    return out.transpose(0, 2, 1).reshape(Bp, sd)[:B]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def signature_from_increments(z: jax.Array, depth: int,
                              launch=None) -> jax.Array:
    """Truncated signature of increment streams z (..., L-1, d) via Pallas.

    ``launch`` is an optional :class:`repro.core.config.LaunchConfig` whose
    ``sig_bt`` / ``sig_lb`` knobs set the batch-tile and length-block shapes
    (``None`` fields keep the module defaults).  The tile geometry never
    changes the per-path arithmetic — results are bitwise-identical across
    launch configs.
    """
    batch_shape = z.shape[:-2]
    flat = z.reshape((-1,) + z.shape[-2:])
    sig = _horner_flat(flat, depth, launch)
    return sig.reshape(batch_shape + sig.shape[-1:]).astype(z.dtype)


def _fwd(z, depth, launch):
    sig = signature_from_increments(z, depth, launch)
    return sig, (z, sig)


def _bwd(depth, launch, res, g):
    # The exact §2.4 time-reversed backward is pure JAX — tile-shape free,
    # so every LaunchConfig shares the one validated implementation.
    from repro.core.signature import _signature_core_bwd
    z, sig = res
    return _signature_core_bwd(depth, (z, sig.astype(jnp.float32)),
                               g.astype(jnp.float32))


signature_from_increments.defvjp(_fwd, _bwd)


def logsignature_from_increments(z: jax.Array, depth: int,
                                 mode: str = "lyndon",
                                 launch=None) -> jax.Array:
    """Fused increments -> log-signature via the Pallas Horner kernel.

    The Horner recursion (the O(L) hot loop) runs through the same
    ``pallas_call`` as :func:`signature_from_increments` — no forked kernel —
    and the log + Lyndon projection are applied as a cheap epilogue: a fixed
    polynomial in the signature levels followed by a static gather
    (``mode="lyndon"``) or gather+matmul (``mode="brackets"``).  Gradients
    reuse the exact time-reversed deconstruction backward of the signature
    kernel wrapper via autodiff composition.
    """
    from repro.core.logsignature import MODES, _log, _project
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    d = z.shape[-1]
    sig = signature_from_increments(z, depth, launch)
    return _project(_log(sig, d, depth), d, depth, mode)

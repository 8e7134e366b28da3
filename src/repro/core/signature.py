"""Truncated path signatures (pySigLib §2) in pure JAX.

Implements both algorithms from the paper:

* Algorithm 1 — the *direct* update (à la ``iisignature``), used as an
  independently-written cross-check oracle.
* Algorithm 2 — *Horner's scheme* (à la ``signatory``), the production path.

Both follow the paper's memory discipline conceptually (flat contiguous level
layout, reverse-order level updates); the literal in-place buffer reuse is
realised in the Pallas kernels (``repro.kernels.signature``), while here the
same arithmetic is expressed functionally for XLA.

Backpropagation (§2.4) uses the time-reversed-path deconstruction of
Reizenstein [42, §4.9]: the backward pass never stores intermediate signatures;
it *reconstructs* S(x_{1:ℓ}) from S(x_{1:ℓ+1}) by Chen-multiplying with
exp(-z_ℓ) (the signature of the reversed segment), so backward memory is O(1)
in path length.  Implemented as a ``jax.custom_vjp``.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp

from . import tensoralg as ta
from .dispatch import UNSET


# ---------------------------------------------------------------------------
# increments (with optional on-the-fly transforms, §4)
# ---------------------------------------------------------------------------

def path_increments(path: jax.Array) -> jax.Array:
    """z_ℓ = x_{ℓ+1} - x_ℓ along the second-to-last axis."""
    return path[..., 1:, :] - path[..., :-1, :]


def _effective_increments(path: jax.Array, pipeline,
                          lengths=None) -> jax.Array:
    """Increment stream with a §4 :class:`TransformPipeline` applied on-the-fly.

    Never materialises the transformed path; only its increments, which is all
    the signature algorithms consume.  Delegates to
    :func:`repro.core.transforms.pipeline_increments`.  With ``lengths=``
    (ragged batches) padded increments are zeroed in place — exact no-ops
    for the Horner recursion — so the valid prefix stays first.
    """
    from . import transforms as tf
    return tf.pipeline_increments(path, pipeline, lengths, align="start")


def transformed_dim(d: int, time_aug: bool, lead_lag: bool) -> int:
    """Channel dimension after on-the-fly transforms.

    Prefer :meth:`repro.TransformPipeline.transformed_dim`; this helper is
    kept for the bool-flag call sites.
    """
    if lead_lag:
        d = 2 * d
    if time_aug:
        d = d + 1
    return d


# ---------------------------------------------------------------------------
# Algorithm 1 — direct
# ---------------------------------------------------------------------------

def _direct_step(levels: List[jax.Array], z: jax.Array, depth: int) -> List[jax.Array]:
    """A_k <- Σ_{i=0}^{k} A_i ⊗ z^{⊗(k-i)}/(k-i)!  (reverse level order)."""
    ez = ta.tensor_exp_levels(z, depth)
    new = list(levels)
    for k in range(depth, 0, -1):           # reverse order: reads only A_i, i<k
        acc = levels[k - 1] + ez[k - 1]     # i=k term (A_k) + i=0 term (z^{⊗k}/k!)
        for i in range(1, k):
            acc = acc + ta.outer(levels[i - 1], ez[k - i - 1])
        new[k - 1] = acc
    return new


# ---------------------------------------------------------------------------
# Algorithm 2 — Horner
# ---------------------------------------------------------------------------

def _horner_step(levels: List[jax.Array], z: jax.Array, depth: int) -> List[jax.Array]:
    """One path-step of Horner's scheme (Alg 2):

        A_k = (B_k + A_{k-1}) ⊗ z + A_k,
        B_k = ((...((z/k + A_1) ⊗ z/(k-1) + A_2) ⊗ z/(k-2) + ...) ⊗ z/2)
    """
    new = list(levels)
    for k in range(depth, 1, -1):
        b = z / k
        for i in range(1, k - 1):
            b = ta.outer(b + levels[i - 1], z / (k - i))
        b = b + levels[k - 2]               # + A_{k-1}
        new[k - 1] = ta.outer(b, z) + levels[k - 1]
    new[0] = levels[0] + z
    return new


# ---------------------------------------------------------------------------
# full signatures
# ---------------------------------------------------------------------------

def _signature_scan(z: jax.Array, d: int, depth: int, step_fn) -> jax.Array:
    """Scan a per-step update over the increment stream z (..., L-1, d)."""
    from .dispatch import record_scan_steps
    record_scan_steps(z.shape[-2])
    batch_shape = z.shape[:-2]
    init = [jnp.zeros((*batch_shape, s), dtype=z.dtype) for s in ta.level_sizes(d, depth)]
    zs = jnp.moveaxis(z, -2, 0)             # (L-1, ..., d) for scan

    def body(carry, zt):
        return step_fn(carry, zt, depth), None

    levels, _ = jax.lax.scan(body, init, zs)
    return ta.join_levels(levels)


def signature_direct(path: jax.Array, depth: int, *, transforms=None,
                     time_aug=UNSET, lead_lag=UNSET) -> jax.Array:
    """Truncated signature via Algorithm 1 (direct).  Cross-check oracle."""
    from .config import resolve_transforms
    cfg = resolve_transforms(transforms, time_aug, lead_lag)
    z = _effective_increments(path, cfg)
    return _signature_scan(z, z.shape[-1], depth, _direct_step)


def _signature_horner_from_increments(z: jax.Array, depth: int) -> jax.Array:
    return _signature_scan(z, z.shape[-1], depth, _horner_step)


# -- custom VJP: time-reversed deconstruction backward (§2.4) ---------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _signature_core(z: jax.Array, depth: int) -> jax.Array:
    return _signature_horner_from_increments(z, depth)


def _signature_core_fwd(z, depth):
    sig = _signature_horner_from_increments(z, depth)
    return sig, (z, sig)


def _signature_core_bwd(depth, res, g):
    z, sig = res
    d = z.shape[-1]

    def step(s_prev_flat, zt):
        """Local forward step as a flat->flat function for per-step VJP."""
        return ta.chen(s_prev_flat, ta.tensor_exp(zt, depth), d, depth)

    def body(carry, zt):
        s_after, g_after = carry
        # deconstruct: S_before = S_after ⊗ exp(-z)   (time-reversed segment)
        s_before = ta.chen(s_after, ta.tensor_exp(-zt, depth), d, depth)
        _, vjp = jax.vjp(step, s_before, zt)
        g_before, g_z = vjp(g_after)
        return (s_before, g_before), g_z

    with jax.named_scope("repro.signature.bwd"):
        zs = jnp.moveaxis(z, -2, 0)
        (_, _), g_z = jax.lax.scan(body, (sig, g), zs, reverse=True)
        return (jnp.moveaxis(g_z, 0, -2),)


_signature_core.defvjp(_signature_core_fwd, _signature_core_bwd)


def signature(path: jax.Array, depth: int, *, transforms=None,
              backend: str = "auto", stream: bool = False, lengths=None,
              launch=None,
              time_aug=UNSET, lead_lag=UNSET, use_pallas=None) -> jax.Array:
    """Truncated signature of a batch of piecewise-linear paths.

    Args:
      path: (..., L, d) discrete stream; linearly interpolated.
      depth: truncation level N.
      transforms: a :class:`repro.TransformPipeline` — §4 transforms
        (basepoint / lead-lag / time-aug over [t0, t1]), applied on-the-fly
        to increments.  Default: no transforms.
      backend: ``"reference"`` (pure-JAX Horner scan), ``"pallas"`` (the TPU
        kernel; interpret mode — slow — elsewhere), or ``"auto"`` (default):
        the registry in :mod:`repro.core.dispatch` picks "pallas" on TPU and
        "reference" on CPU/GPU.  With ``stream=True`` only ``"auto"`` /
        ``"reference"`` are valid (the streamed scan is pure JAX);
        explicitly requesting ``"pallas"`` raises instead of silently
        degrading.
      stream: if True return signatures of all prefixes (..., L-1, sig_dim).
      lengths: optional (...,) int array of per-path true point counts for
        ragged (variable-length) batches.  Each path is treated as if
        truncated to its own length — padding content is ignored, and the
        ``time_aug`` grid ends at ``t1`` at the *true* last point.  The
        length axis is padded up to a power-of-two bucket
        (:func:`repro.core.transforms.pad_ragged`) so nearby max-lengths
        share one jit trace.  With ``stream=True``, prefix entries at or
        past a path's true end repeat its final signature.
      launch: an optional :class:`repro.LaunchConfig`; its ``sig_bt`` /
        ``sig_lb`` knobs set the Pallas kernel's batch-tile and
        length-block shapes (``None`` fields fall back to the autotuned
        winner for this shape bucket, then to the library defaults).
        Tile geometry never changes the arithmetic — results are
        bitwise-identical across launch configs.  Ignored by the pure-JAX
        reference backend and the streamed scan.
      time_aug / lead_lag: deprecated bool aliases for ``transforms=``
        (DeprecationWarning once per call-site; bitwise-identical results).
      use_pallas: deprecated alias — ``True`` -> ``backend="pallas"``,
        ``False`` -> ``backend="reference"`` (with a DeprecationWarning);
        ``None`` keeps the historical meaning of auto.

    Returns:
      (..., sig_dim(d', depth)) flat signature (levels 1..depth), where d' is
      the transformed channel count (``transforms.transformed_dim(d)``).
    """
    from . import dispatch
    from . import transforms as tf
    from .config import resolve_transforms
    cfg = resolve_transforms(transforms, time_aug, lead_lag)
    if lengths is not None:
        path, lengths = tf.pad_ragged(path, lengths)
    z = _effective_increments(path, cfg, lengths)
    backend = dispatch.canonicalize(backend, op="signature",
                                    use_pallas=use_pallas)
    if stream:
        if backend != "auto" and backend != "reference":
            raise ValueError(
                f"signature(stream=True) has no {backend!r} implementation "
                "— the streamed prefix scan is pure JAX; pass "
                "backend='auto' or backend='reference'")
        return _signature_stream_from_increments(z, depth)
    key_shape = (z.shape[-2], z.shape[-1], depth)
    backend = dispatch.resolve(
        backend, op="signature", shape=key_shape,
        dtype=z.dtype, ragged=lengths is not None)
    if backend == "pallas":
        from repro.kernels.signature import ops as sig_ops
        launch = dispatch.resolve_launch(launch, op="signature",
                                         shape=key_shape, dtype=z.dtype,
                                         ragged=lengths is not None)
        return sig_ops.signature_from_increments(z, depth, launch)
    return _signature_core(z, depth)


def _signature_stream_from_increments(z: jax.Array, depth: int) -> jax.Array:
    """All prefix signatures: (..., L-1, sig_dim). Differentiable via scan."""
    from .dispatch import record_scan_steps
    record_scan_steps(z.shape[-2])
    d = z.shape[-1]
    batch_shape = z.shape[:-2]
    init = [jnp.zeros((*batch_shape, s), dtype=z.dtype) for s in ta.level_sizes(d, depth)]
    zs = jnp.moveaxis(z, -2, 0)

    def body(carry, zt):
        new = _horner_step(carry, zt, depth)
        return new, ta.join_levels(new)

    _, flats = jax.lax.scan(body, init, zs)
    return jnp.moveaxis(flats, 0, -2)


def signature_combine(sig_a: jax.Array, sig_b: jax.Array, d: int, depth: int) -> jax.Array:
    """Chen-combine signatures of consecutive path segments."""
    return ta.chen(sig_a, sig_b, d, depth)

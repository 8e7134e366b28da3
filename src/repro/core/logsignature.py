"""Log-signatures of paths (the compressed path feature of Signatory).

The log-signature logS(x) = log(S(x)) is the truncated-tensor-algebra log of
the signature.  It carries the same information as S(x) up to the chosen
depth but lives in the free Lie algebra, whose dimension (the number of
Lyndon words, Witt's formula) is much smaller than the full tensor algebra —
e.g. d=5, N=5: 829 vs 3905 coordinates.

Pipeline:  increments --Horner--> S(x) --tensor_log--> flat Lie element
--Lyndon projection--> compressed coordinates.  The Horner recursion is the
*same* hot path as ``repro.core.signature`` (and routes through the same
Pallas kernel when ``use_pallas``); log + projection are a cheap epilogue.

Backpropagation reuses the time-reversed deconstruction backward of
``core.signature`` (§2.4, O(1) memory in path length): the custom VJP pulls
the cotangent back through ``tensor_log`` analytically via ``jax.vjp`` and
hands the signature cotangent to ``_signature_core_bwd``.

Modes (see ``repro.core.lyndon``):

* ``"lyndon"``   — Lyndon-word coefficients (default; a static gather).
* ``"brackets"`` — coefficients in the Lyndon bracket basis (triangular solve,
  precomputed).
* ``"expand"``   — the full flat tensor layout of log(S(x)) (sig_dim wide).
"""

from __future__ import annotations

import functools
import jax

from . import dispatch as dispatch_mod
from . import lyndon
from . import tensoralg as ta
from .signature import (_effective_increments, _signature_core_bwd,
                        _signature_horner_from_increments,
                        _signature_stream_from_increments)

MODES = ("lyndon", "brackets", "expand")


def logsignature_dim(d: int, depth: int, mode: str = "lyndon") -> int:
    """Output width of :func:`logsignature` for a (transformed) channel count d."""
    if mode == "expand":
        return ta.sig_dim(d, depth)
    return lyndon.logsig_dim(d, depth)


def _log(sig: jax.Array, d: int, depth: int) -> jax.Array:
    """The truncated log of flat signatures, under its profile scope."""
    with jax.named_scope("repro.logsig.log"):
        return ta.tensor_log(sig, d, depth)


def _project(flat_log: jax.Array, d: int, depth: int, mode: str) -> jax.Array:
    if mode == "expand":
        return flat_log
    with jax.named_scope("repro.logsig.project"):
        return lyndon.compress(flat_log, d, depth, mode)


# ---------------------------------------------------------------------------
# core: increments -> flat log-signature, with the reused exact backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _logsignature_core(z: jax.Array, depth: int) -> jax.Array:
    """Flat (mode="expand") log-signature of an increment stream z (..., L-1, d)."""
    d = z.shape[-1]
    return _log(_signature_horner_from_increments(z, depth), d, depth)


def _logsig_core_fwd(z, depth):
    sig = _signature_horner_from_increments(z, depth)
    d = z.shape[-1]
    return _log(sig, d, depth), (z, sig)


def _logsig_core_bwd(depth, res, g):
    z, sig = res
    d = z.shape[-1]
    # pull the cotangent back through the (pointwise-polynomial) log ...
    _, log_vjp = jax.vjp(lambda s: _log(s, d, depth), sig)
    (g_sig,) = log_vjp(g)
    # ... then reuse the O(1)-memory time-reversed deconstruction of §2.4.
    return _signature_core_bwd(depth, (z, sig), g_sig)


_logsignature_core.defvjp(_logsig_core_fwd, _logsig_core_bwd)


def logsignature_from_increments(z: jax.Array, depth: int,
                                 mode: str = "lyndon") -> jax.Array:
    """Log-signature of increment streams z (..., L-1, d), pure-JAX path."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    d = z.shape[-1]
    return _project(_logsignature_core(z, depth), d, depth, mode)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def logsignature(path: jax.Array, depth: int, *, mode: str = "lyndon",
                 transforms=None, backend: str = "auto",
                 stream: bool = False, lengths=None, launch=None,
                 time_aug=dispatch_mod.UNSET,
                 lead_lag=dispatch_mod.UNSET, use_pallas=None) -> jax.Array:
    """Truncated log-signature of a batch of piecewise-linear paths.

    Args:
      path: (..., L, d) discrete stream; linearly interpolated.
      depth: truncation level N.
      mode: "lyndon" (default) | "brackets" | "expand" — see module docstring.
      transforms: a :class:`repro.TransformPipeline` — §4 transforms
        (basepoint / lead-lag / time-aug over [t0, t1]), applied on-the-fly
        to increments.  Default: no transforms.
      backend: ``"reference"`` (pure-JAX Horner scan) | ``"pallas"`` (the TPU
        kernel) | ``"auto"`` (default; the registry in
        :mod:`repro.core.dispatch` picks "pallas" on TPU, "reference"
        elsewhere).  The Lyndon projection is a final gather either way.
        With ``stream=True`` explicitly requesting ``"pallas"`` raises (the
        streamed scan is pure JAX); ``"auto"`` degrades silently.
      stream: if True return log-signatures of all prefixes
        (..., L-1, logsig_dim).
      lengths: optional (...,) int array of per-path true point counts for
        ragged batches — same semantics as :func:`repro.core.signature`
        (padding masked, per-path time grid, power-of-two length buckets;
        streamed prefixes repeat the final value past the true end).
      launch: an optional :class:`repro.LaunchConfig` — same semantics as
        :func:`repro.core.signature.signature` (``sig_bt`` / ``sig_lb``
        tile the Pallas Horner kernel; bitwise-identical results across
        launch configs; ignored off the pallas backend).
      time_aug / lead_lag: deprecated bool aliases for ``transforms=``
        (DeprecationWarning once per call-site; bitwise-identical results).
      use_pallas: deprecated alias — explicit bools warn and map to
        ``backend="pallas"`` / ``"reference"``; ``None`` keeps the
        historical meaning of auto.

    Returns:
      (..., logsignature_dim(d', depth, mode)) where d' is the transformed
      channel count (``transforms.transformed_dim(d)``).
    """
    from . import dispatch
    from . import transforms as tf
    from .config import resolve_transforms
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    cfg = resolve_transforms(transforms, time_aug, lead_lag)
    if lengths is not None:
        path, lengths = tf.pad_ragged(path, lengths)
    z = _effective_increments(path, cfg, lengths)
    d = z.shape[-1]
    backend = dispatch.canonicalize(backend, op="logsignature",
                                    use_pallas=use_pallas)
    if stream:
        if backend not in ("auto", "reference"):
            raise ValueError(
                f"logsignature(stream=True) has no {backend!r} "
                "implementation — the streamed prefix scan is pure JAX; "
                "pass backend='auto' or backend='reference'")
        sig_stream = _signature_stream_from_increments(z, depth)
        return _project(_log(sig_stream, d, depth), d, depth, mode)
    key_shape = (z.shape[-2], z.shape[-1], depth)
    backend = dispatch.resolve(
        backend, op="logsignature", shape=key_shape, dtype=z.dtype,
        ragged=lengths is not None)
    if backend == "pallas":
        from repro.kernels.signature import ops as sig_ops
        launch = dispatch.resolve_launch(launch, op="logsignature",
                                         shape=key_shape, dtype=z.dtype,
                                         ragged=lengths is not None)
        return sig_ops.logsignature_from_increments(z, depth, mode, launch)
    return logsignature_from_increments(z, depth, mode)


def logsignature_combine(lsa: jax.Array, lsb: jax.Array, d: int, depth: int,
                         mode: str = "lyndon") -> jax.Array:
    """Log-signature of a concatenation from the pieces' log-signatures.

    Chen's identity holds for signatures, so combine via exp -> ⊗ -> log:
    logS(x * y) = log(exp(logS(x)) ⊗ exp(logS(y))).  ``d`` is the
    (transformed) channel count the inputs were computed with.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode != "expand":
        lsa = lyndon.expand(lsa, d, depth, mode)
        lsb = lyndon.expand(lsb, d, depth, mode)
    sa = ta.tensor_exp_full(lsa, d, depth)
    sb = ta.tensor_exp_full(lsb, d, depth)
    combined = ta.tensor_log(ta.chen(sa, sb, d, depth), d, depth)
    return _project(combined, d, depth, mode)

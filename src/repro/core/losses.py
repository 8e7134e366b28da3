"""Signature-kernel training losses.

The workload pySigLib exists to accelerate: sig-kernel scores for training
generative models on time series (paper §1; refs [16, 21, 24]).  All losses
are differentiable through the exact one-pass backward of
``repro.core.sigkernel`` and route their Gram matrices through the unified
engine in ``repro.core.gram`` — the symmetric ``Kxx``/``Kyy`` terms solve
only the upper triangle (≈2× fewer PDE solves), and ``backend=`` selects the
solver via the registry in ``repro.core.dispatch``.

With ``streaming=`` on (auto-enabled whenever ``row_block=`` is set) the
losses never materialise their Gram matrices at all: every term routes
through :func:`repro.core.gram.sigkernel_gram_reduce`, which accumulates
per-row-block partial sums under ``jax.checkpoint`` in both the forward and
the VJP, and the shape guard
:func:`repro.core.gram.assert_streaming_reduction` abstractly traces the
reduction once per shape to prove no (B, B) intermediate exists.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import resolve_kernel_configs
from .dispatch import UNSET
from .gram import sigkernel_gram, sigkernel_gram_reduce


def _use_streaming(streaming: Optional[bool], row_block: Optional[int],
                   approx: bool = False) -> bool:
    """``streaming=None`` means auto: stream iff the caller bounded memory
    with ``row_block=`` (the only reason to pay the reduction's extra
    trace) — or an approximation is active (``features=`` /
    ``error_budget=``), whose whole point is O(B·rank) memory: the
    feature-space reduction never forms a B×B Gram, so streaming is the
    natural default.  Explicit True/False always wins."""
    if streaming is None:
        return row_block is not None or approx
    return bool(streaming)


def mmd2(X: jax.Array, Y: jax.Array, *, transforms=None, grid=None,
         static_kernel=None, unbiased: bool = True, backend: str = "auto",
         row_block: Optional[int] = None, streaming: Optional[bool] = None,
         lengths=None, lengths_y=None, features=None, error_budget=None,
         lam1=UNSET, lam2=UNSET, time_aug=UNSET, lead_lag=UNSET,
         use_pallas=UNSET) -> jax.Array:
    """Squared MMD between two path distributions under the signature kernel.

    X: (Bx, L, d) samples from P;  Y: (By, L', d) samples from Q.

    ``transforms=`` (:class:`repro.TransformPipeline`), ``grid=``
    (:class:`repro.GridConfig`) and ``static_kernel=`` (:class:`repro.Linear`
    / :class:`repro.RBF`) configure the kernel; the legacy
    ``lam1/lam2/time_aug/lead_lag/use_pallas`` kwargs are deprecated
    aliases (DeprecationWarning once per call-site).

    ``lengths``/``lengths_y`` — optional (Bx,)/(By,) int arrays of per-path
    true point counts — make both batches ragged: each Gram term masks its
    padding exactly (see :func:`repro.core.gram.sigkernel_gram`), so the two
    sides may be padded to *different* L and still compare correctly.

    ``streaming`` — ``True`` accumulates all three Gram terms as per-block
    partial sums (forward and gradient) via
    :func:`repro.core.gram.sigkernel_gram_reduce`, so the full (B, B) Grams
    never exist; peak memory is set by ``row_block`` instead of the batch.
    ``None`` (default) auto-enables streaming when ``row_block=`` is set;
    ``False`` forces the dense Grams.  Values and gradients match the dense
    path to summation-order tolerance, and an intermediate-shape assertion
    (abstract trace, no FLOPs, once per shape) guards against the streaming
    path silently densifying.

    ``features=`` (a :class:`repro.FeatureConfig`) or ``error_budget=``
    activate the approximate feature-map backends exactly as in
    :func:`repro.core.gram.sigkernel_gram`; all three Gram terms then
    reduce in feature space — O(B·rank) memory end-to-end, streaming by
    default (see docs/api/public.md § Approximate kernels).

    The unbiased estimator divides by ``b·(b−1)`` and therefore needs at
    least two samples on each side — a single-sample batch raises instead of
    silently returning NaN; use ``unbiased=False`` for ``b = 1``.
    """
    bx, by = X.shape[0], Y.shape[0]
    if unbiased and min(bx, by) < 2:
        raise ValueError(
            f"unbiased MMD needs >= 2 samples per side (got Bx={bx}, "
            f"By={by}); the 1/(b·(b-1)) normaliser is NaN at b=1 — "
            "pass unbiased=False")
    cfg, g, kernel = resolve_kernel_configs(
        transforms, grid, static_kernel, time_aug=time_aug,
        lead_lag=lead_lag, lam1=lam1, lam2=lam2)
    approx = features is not None or error_budget is not None
    kw = dict(transforms=cfg, grid=g, static_kernel=kernel,
              backend=backend, row_block=row_block, use_pallas=use_pallas,
              features=features, error_budget=error_budget)
    if _use_streaming(streaming, row_block, approx):
        rkw = dict(kw, check_streaming=True)
        sxx_sum = sigkernel_gram_reduce(X, lengths=lengths,
                                        include_diag=not unbiased, **rkw)
        syy_sum = sigkernel_gram_reduce(Y, lengths=lengths_y,
                                        include_diag=not unbiased, **rkw)
        sxy_sum = sigkernel_gram_reduce(X, Y, lengths=lengths,
                                        lengths_y=lengths_y, **rkw)
        with jax.named_scope("repro.gram.reduce"):
            if unbiased:
                sxx = sxx_sum / (bx * (bx - 1))
                syy = syy_sum / (by * (by - 1))
            else:
                sxx = sxx_sum / (bx * bx)
                syy = syy_sum / (by * by)
            return sxx + syy - 2.0 * sxy_sum / (bx * by)
    Kxx = sigkernel_gram(X, lengths=lengths, **kw)   # upper triangle only
    Kyy = sigkernel_gram(Y, lengths=lengths_y, **kw)
    Kxy = sigkernel_gram(X, Y, lengths=lengths, lengths_y=lengths_y, **kw)
    with jax.named_scope("repro.gram.reduce"):
        if unbiased:
            sxx = (Kxx.sum() - jnp.trace(Kxx)) / (bx * (bx - 1))
            syy = (Kyy.sum() - jnp.trace(Kyy)) / (by * (by - 1))
        else:
            sxx = Kxx.mean()
            syy = Kyy.mean()
        return sxx + syy - 2.0 * Kxy.mean()


def scoring_rule(X: jax.Array, y: jax.Array, *, transforms=None, grid=None,
                 static_kernel=None, backend: str = "auto",
                 row_block: Optional[int] = None,
                 streaming: Optional[bool] = None,
                 lengths=None, length_y=None,
                 features=None, error_budget=None,
                 lam1=UNSET, lam2=UNSET, time_aug=UNSET, lead_lag=UNSET,
                 use_pallas=UNSET) -> jax.Array:
    """Sig-kernel score  E[k(X,X')]/2 − E[k(X,y)]  for one observation y (L, d).

    A strictly proper scoring rule for path-valued prediction [24].
    ``E[k(X,X')]`` averages over distinct pairs (divides by ``b·(b−1)``), so
    the ensemble needs at least two members.  Configured like :func:`mmd2`;
    ``lengths`` (B,) makes the ensemble ragged, ``length_y`` (a scalar int)
    gives the observation's true point count.  ``streaming=`` streams both
    terms as per-block partial sums exactly as in :func:`mmd2` (auto-on when
    ``row_block=`` is set) — the (B, B) ensemble Gram never exists.
    ``features=`` / ``error_budget=`` activate the approximate feature-map
    backends (streaming by default), as in :func:`mmd2`.
    """
    b = X.shape[0]
    if b < 2:
        raise ValueError(
            f"scoring_rule needs an ensemble of >= 2 paths (got B={b}); "
            "the 1/(b·(b-1)) normaliser is NaN at b=1")
    cfg, g, kernel = resolve_kernel_configs(
        transforms, grid, static_kernel, time_aug=time_aug,
        lead_lag=lead_lag, lam1=lam1, lam2=lam2)
    approx = features is not None or error_budget is not None
    kw = dict(transforms=cfg, grid=g, static_kernel=kernel,
              backend=backend, row_block=row_block, use_pallas=use_pallas,
              features=features, error_budget=error_budget)
    ly = None if length_y is None else jnp.reshape(length_y, (1,))
    if _use_streaming(streaming, row_block, approx):
        rkw = dict(kw, check_streaming=True)
        exx_sum = sigkernel_gram_reduce(X, lengths=lengths,
                                        include_diag=False, **rkw)
        exy_sum = sigkernel_gram_reduce(X, y[None], lengths=lengths,
                                        lengths_y=ly, **rkw)
        return 0.5 * exx_sum / (b * (b - 1)) - exy_sum / b
    Kxx = sigkernel_gram(X, lengths=lengths, **kw)
    exx = (Kxx.sum() - jnp.trace(Kxx)) / (b * (b - 1))
    Kxy = sigkernel_gram(X, y[None], lengths=lengths, lengths_y=ly, **kw)
    return 0.5 * exx - Kxy.mean()


def sig_aux_loss(hidden: jax.Array, target: jax.Array, *, proj: jax.Array,
                 transforms=None, grid=None, static_kernel=None,
                 backend: str = "auto", row_block: Optional[int] = None,
                 streaming: Optional[bool] = None,
                 lengths=None, lengths_target=None,
                 features=None, error_budget=None,
                 lam1=UNSET, lam2=UNSET, time_aug=UNSET, lead_lag=UNSET,
                 use_pallas=UNSET) -> jax.Array:
    """Auxiliary sig-kernel loss between a model's hidden trajectory and a
    target path distribution (the glue attaching the paper's technique to any
    sequence architecture — DESIGN.md §5).

    hidden: (B, L, H) hidden states; proj: (H, d) fixed/learned projection into
    a low-dim path space; target: (B, L, d) reference paths.  ``lengths`` /
    ``lengths_target`` (each (B,)) make the corresponding side ragged — e.g.
    packed batches of variable-length sequences.  The legacy
    ``time_aug=``/``lead_lag=`` bools are accepted as the same deprecated
    aliases its siblings :func:`mmd2`/:func:`scoring_rule` take (one
    DeprecationWarning per call-site, identical results).  ``streaming=``,
    ``features=`` and ``error_budget=`` pass through to :func:`mmd2` — an
    active approximation makes the auxiliary loss O(B·rank), which is what
    lets it ride along every training step of a large model.
    """
    cfg, g, kernel = resolve_kernel_configs(
        transforms, grid, static_kernel, time_aug=time_aug,
        lead_lag=lead_lag, lam1=lam1, lam2=lam2)
    path = hidden @ proj                      # (B, L, d)
    # normalise scale so the PDE stays well-conditioned for wide layers
    path = path / jnp.sqrt(jnp.asarray(proj.shape[0], path.dtype))
    return mmd2(path, target, transforms=cfg, grid=g, static_kernel=kernel,
                unbiased=False, backend=backend, row_block=row_block,
                streaming=streaming, lengths=lengths,
                lengths_y=lengths_target, features=features,
                error_budget=error_budget, use_pallas=use_pallas)

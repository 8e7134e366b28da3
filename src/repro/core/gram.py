"""The Gram engine: one entry point for every sig-kernel Gram variant.

``sigkernel_gram(X, Y=None, ...)`` unifies what used to be three separate
code paths (dense einsum, row-blocked ``lax.map``, fused-Δ Pallas) behind the
backend registry in :mod:`repro.core.dispatch`:

* **dense** — all ``Bx·By`` Δ matrices materialised at once (small batches);
* **blocked** — ``row_block`` Gram rows live at a time; ``Bx`` is
  zero-padded to the block granularity (zero increments ⇒ k = 1 rows that
  are dropped, so padding is exact — same trick the PDE kernels use for
  strips);
* **fused** (``backend="pallas_fused"``) — Δ is built in VMEM from the
  increments and never exists in HBM, now differentiable end-to-end via the
  checkpointed exact backward;
* **symmetric fast path** — when ``Y`` is omitted only the
  ``Bx·(Bx+1)/2`` upper-triangle pairs are solved (≈2× fewer PDE solves for
  the ``Kxx``/``Kyy`` terms of every loss) and the result is mirrored.

Beyond the single-device engine this module provides the *distributed* and
*streaming* layers (docs/api/public.md § Distributed & streaming Grams):

* :func:`sigkernel_gram_sharded` — the same Gram tiled over a real device
  mesh via ``shard_map``: rows block-cyclic over the ``data`` axis, columns
  block-cyclic over ``model``; the symmetric fast path deals the global
  upper-triangle *pairs* round-robin over every device so the triangular
  tile grid stays load-balanced.
* :func:`sigkernel_gram_reduce` — streaming scalar reductions
  (``ΣK`` with or without the diagonal) that accumulate per-row-block
  partial sums under ``jax.checkpoint``, so neither the forward nor the
  VJP ever materialises the full (Bx, By) Gram.  ``mmd2`` and
  ``scoring_rule`` route through it when ``streaming=`` is on.
* :func:`assert_streaming_reduction` — an ``eval_shape``-style abstract
  trace (no FLOPs) over a reduction's jaxpr that raises
  :class:`StreamingViolation` if any intermediate materialises a
  ``(Bx, By, ...)`` array — the guard against silently densifying.

Row blocks and the Gram tiling are annotated with the logical mesh axes of
:mod:`repro.parallel.api` (rows → ``"batch"``, columns → ``"model"``), so
under a mesh + ``logical_rules`` context a pod-scale Gram is one call; with
no mesh the annotations are no-ops and the same code runs on a laptop.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import dispatch
from . import features as ft
from . import transforms as tf
from .config import (_maybe_scale as _scale, delta_from_gram,
                     resolve_kernel_configs)
from .dispatch import UNSET
from .sigkernel import _sigkernel_from_delta
from repro.parallel.api import shard
from repro.parallel.sharding import block_cyclic_perm, get_shard_map


def _prepare(paths: jax.Array, cfg, kernel, lengths=None) -> jax.Array:
    """Per-path stream the pair solvers consume: transformed *increments*
    for increment-lifting (linear) kernels, transformed *points* for
    everything else (the Δ-from-Gram path needs actual points).

    Either way zero-padding rows with zeros is exact: zero increments and
    all-zero point rows both give Δ = 0 ⇒ k = 1 rows, which are dropped.

    With ``lengths=`` (ragged batches) the streams come back *end-aligned*:
    each path's padding turns into exactly-zero leading Δ rows/columns for
    any pairing, which leaves the Goursat boundary of ones bitwise intact —
    so everything downstream of this function (pair gathers, row blocks,
    the fused kernels, the symmetric fast path, the sharded tiling) is
    ragged-oblivious.
    """
    with jax.named_scope("repro.transform"):
        if kernel.lifts_increments:
            return tf.pipeline_increments(paths, cfg, lengths, align="end")
        return tf.transform_path(paths, cfg, lengths, align="end")


def _pair_delta(sa: jax.Array, sb: jax.Array, kernel) -> jax.Array:
    """Δ for batches of prepared streams (leading dims broadcast)."""
    with jax.named_scope("repro.gram.pairs"):
        if kernel.lifts_increments:
            return kernel.delta_from_increments(sa, sb)
        return delta_from_gram(kernel.gram(sa, sb))


def _gather_pairs(sX: jax.Array, a_idx, b_idx):
    """The prepared streams of the pairs ``(a_idx[i], b_idx[i])``."""
    with jax.named_scope("repro.gram.pairs"):
        return sX[a_idx], sX[b_idx]


def _mirror(k: jax.Array, a_idx, b_idx, B: int) -> jax.Array:
    """The symmetric (B, B) Gram from its upper-triangle values ``k``."""
    with jax.named_scope("repro.gram.reduce"):
        K = jnp.zeros((B, B), k.dtype).at[a_idx, b_idx].set(k)
        return K + jnp.triu(K, k=1).T


def _solve_pairs(sa: jax.Array, sb: jax.Array, kernel, backend: str,
                 g, launch=None) -> jax.Array:
    """Solve one batch of prepared pairs (P, ·, d) × (P, ·, d) -> (P,).

    ``g`` is the resolved :class:`repro.GridConfig`: refinement levels AND
    the scheme / interior-dtype static knobs travel together so every pair
    solver (fused or Δ-materialising) runs the same discretisation.
    """
    if backend == "pallas_fused":
        from repro.kernels.sigkernel_pde import ops as pde_ops
        # fused kernels compute ⟨dx, dy⟩ in VMEM; fold a non-unit linear
        # scale into one side (scale·⟨dx, dy⟩ = ⟨scale·dx, dy⟩ exactly)
        return pde_ops.solve_fused(_scale(sa, kernel.scale), sb, g.lam1,
                                   g.lam2, launch, g.scheme,
                                   g.interior_dtype)
    return _sigkernel_from_delta(_pair_delta(sa, sb, kernel), g.lam1, g.lam2,
                                 backend, launch, g.scheme, g.interior_dtype)


def _gram_block(sxb: jax.Array, sY: jax.Array, kernel, backend: str,
                g, launch=None) -> jax.Array:
    """Gram block from prepared streams (r, ·, d) × (By, ·, d) -> (r, By)."""
    if backend == "pallas_fused":
        from repro.kernels.sigkernel_pde import ops as pde_ops
        return pde_ops.gram_fused(_scale(sxb, kernel.scale), sY, g.lam1,
                                  g.lam2, launch, g.scheme, g.interior_dtype)
    delta = _pair_delta(sxb[:, None], sY[None, :], kernel)
    return _sigkernel_from_delta(delta, g.lam1, g.lam2, backend, launch,
                                 g.scheme, g.interior_dtype)


def _gram_rows(sX: jax.Array, sY: jax.Array, kernel, backend: str,
               g, row_block: Optional[int], launch=None) -> jax.Array:
    """(Bx, ·, d) × (By, ·, d) -> (Bx, By), optionally ``row_block`` rows
    in flight at a time (``Bx`` zero-padded; padded rows dropped)."""
    Bx, By = sX.shape[0], sY.shape[0]
    if row_block is None:
        return _gram_block(sX, sY, kernel, backend, g, launch)
    pad = (-Bx) % row_block
    n_blocks = (Bx + pad) // row_block
    with jax.named_scope("repro.gram.pairs"):
        if pad:  # zero rows -> Δ = 0 -> k = 1 rows, dropped below: exact
            sX = jnp.pad(sX, ((0, pad), (0, 0), (0, 0)))
        sXb = sX.reshape(n_blocks, row_block, *sX.shape[1:])
    K = jax.lax.map(
        lambda sxb: _gram_block(sxb, sY, kernel, backend, g, launch),
        sXb)
    return K.reshape(n_blocks * row_block, By)[:Bx]


def _solve_pairs_chunked(sX: jax.Array, a_idx, b_idx, kernel, backend: str,
                         g, chunk: Optional[int], launch=None) -> jax.Array:
    """k values for an explicit pair list into one stream batch, at most
    ``chunk`` pairs of replicated increments live at once.

    Only the (chunk,)-sized index arrays are materialised up front; the
    pair gather itself happens inside the mapped body, one chunk at a
    time, so live replicated increments stay at 2·chunk·L·d floats.
    Padding pairs (0, 0) are solved and dropped (exact; accounted by the
    caller's pair-solve budget).
    """
    a_idx, b_idx = jnp.asarray(a_idx), jnp.asarray(b_idx)
    n = a_idx.shape[0]
    if chunk is None or chunk >= n:
        return _solve_pairs(*_gather_pairs(sX, a_idx, b_idx), kernel,
                            backend, g, launch)
    pad = (-n) % chunk
    with jax.named_scope("repro.gram.pairs"):
        a = jnp.concatenate([a_idx, jnp.zeros((pad,), a_idx.dtype)])
        b = jnp.concatenate([b_idx, jnp.zeros((pad,), b_idx.dtype)])
    k = jax.lax.map(
        lambda ab: _solve_pairs(*_gather_pairs(sX, *ab), kernel, backend, g,
                                launch),
        (a.reshape(-1, chunk), b.reshape(-1, chunk)))
    return k.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# shared front-end: validation, config resolution, ragged padding, dispatch
# ---------------------------------------------------------------------------

def _resolve_engine(X, Y, symmetric, lengths, lengths_y, transforms, grid,
                    static_kernel, lam1, lam2, time_aug, lead_lag,
                    use_pallas, solver, backend, launch=None,
                    features=None, error_budget=None):
    """The engine front-end every Gram entry point shares.

    Validates shapes/flags, resolves configs + legacy shims, pads ragged
    batches, and resolves ``backend`` through the dispatch registry and
    ``launch`` through :func:`repro.core.dispatch.resolve_launch`
    (explicit > autotuned > defaults).  Returns
    ``(X, Y, cfg, grid_cfg, kernel, backend, symmetric, launch, feats)``
    with ``X``/``Y`` already ragged-padded (masking is burnt into the
    prepared streams downstream, so ``lengths`` are consumed here).

    ``feats`` is the active :class:`repro.core.features.FeatureConfig` or
    None (= exact engine).  An approximation activates one of three ways:
    an explicit ``features=`` config; an explicit approximate *backend
    name* (``"rff"``/``"nystroem"``) together with ``features=`` or
    ``error_budget=`` (without either the dispatch layer refuses — the
    capability-flag contract); or ``backend="auto"`` + ``error_budget=``
    when the autotune cache holds a measured frontier point meeting the
    budget (:func:`repro.core.dispatch.resolve_approx`) — never
    otherwise.
    """
    if X.ndim != 3 or (Y is not None and Y.ndim != 3):
        raise ValueError(
            f"sigkernel_gram expects (B, L, d) paths, got X {X.shape}"
            + ("" if Y is None else f", Y {Y.shape}"))
    if symmetric is None:
        symmetric = Y is None
    if symmetric and not (Y is None or Y is X):
        raise ValueError("symmetric=True requires Y to be None or X itself")
    if not symmetric and Y is None:
        raise ValueError("symmetric=False requires Y (pass Y=X for the "
                         "full symmetric Gram without the fast path)")
    if lengths_y is not None and Y is None:
        raise ValueError("lengths_y= requires Y; for the symmetric Gram "
                         "pass lengths= (it applies to both sides)")

    cfg, g, kernel = resolve_kernel_configs(
        transforms, grid, static_kernel, time_aug=time_aug,
        lead_lag=lead_lag, lam1=lam1, lam2=lam2)
    if lengths is not None:
        X, lengths = tf.pad_ragged(X, lengths)
    if lengths_y is not None:
        Y, lengths_y = tf.pad_ragged(Y, lengths_y)
    ragged = lengths is not None or lengths_y is not None
    backend = dispatch.canonicalize(backend, op="gram",
                                    use_pallas=use_pallas, solver=solver)
    if backend == "pallas_fused" and not kernel.lifts_increments:
        raise ValueError(
            "backend='pallas_fused' builds Δ from increments in VMEM and "
            f"only supports the linear lift, got "
            f"static_kernel={type(kernel).__name__}; pass backend='auto'")
    Lx = cfg.transformed_steps(X.shape[1])
    Ly = Lx if Y is None else cfg.transformed_steps(Y.shape[1])
    By = X.shape[0] if Y is None else Y.shape[0]
    key_shape = (X.shape[0], By, Lx << g.lam1, Ly << g.lam2,
                 cfg.transformed_dim(X.shape[-1]))

    feats = ft.resolve_features(features)
    if feats is not None and backend not in ("auto", feats.method):
        raise ValueError(
            f"features=FeatureConfig(method={feats.method!r}) conflicts "
            f"with backend={backend!r}; pass backend='auto' or "
            f"backend={feats.method!r}")
    explicit_approx = (backend in dispatch.backends_for("gram")
                       and dispatch.get(backend).approximate)
    # the feature-map backends only implement the order-1 discretisation
    # (BackendSpec.schemes): a non-default scheme keeps "auto" off the
    # approx frontier entirely; naming one explicitly is refused, with the
    # scheme-capability error rather than the opt-in one (the caller DID
    # opt in — the scheme is what rules the backend out)
    if explicit_approx and (features is not None
                            or error_budget is not None):
        dispatch.check_scheme(backend, g.scheme, op="gram")
    if feats is None and explicit_approx and error_budget is not None \
            and g.scheme == "order1":
        # explicit approx backend + a budget: take the measured frontier
        # rank when the cache is warm, the library default otherwise
        found = dispatch.resolve_approx(
            "gram", key_shape, X.dtype, error_budget=error_budget,
            ragged=ragged)
        rank = found[1] if found is not None and found[0] == backend \
            else ft.FeatureConfig.rank
        feats = ft.FeatureConfig(method=backend, rank=rank)
    if feats is None and backend == "auto" and error_budget is not None \
            and g.scheme == "order1":
        found = dispatch.resolve_approx(
            "gram", key_shape, X.dtype, error_budget=error_budget,
            ragged=ragged)
        if found is not None:
            feats = ft.FeatureConfig(method=found[0], rank=found[1])

    if feats is None and backend == "auto" and error_budget is not None \
            and g.scheme == "order1" and g.interior_dtype == "float32":
        # scheme frontier: a measured (scheme, coarsen, interior_dtype)
        # point meeting the budget may run the EXACT engine cheaper — an
        # order-2 stencil on a coarser grid, or bf16 interiors.  Only
        # consulted from the defaults: an explicit scheme/dtype choice is
        # never overridden.
        g, X, Y, Lx, Ly, key_shape = _apply_scheme_point(
            dispatch.resolve_scheme("gram", key_shape, X.dtype,
                                    error_budget=error_budget,
                                    ragged=ragged),
            g, X, Y, cfg, ragged, By)

    if feats is not None:
        backend = dispatch.resolve(feats.method, op="gram",
                                   allow_approximate=True, scheme=g.scheme)
    else:
        backend = dispatch.resolve(
            backend, op="gram", grid_cells=(Lx << g.lam1) * (Ly << g.lam2),
            shape=key_shape,
            dtype=X.dtype, allow_fused=kernel.lifts_increments,
            ragged=ragged, scheme=g.scheme)
    launch = dispatch.resolve_launch(launch, op="gram", shape=key_shape,
                                     dtype=X.dtype, ragged=ragged)
    return (X, Y, lengths, lengths_y, cfg, g, kernel, backend, symmetric,
            launch, feats)


def _apply_scheme_point(found, g, X, Y, cfg, ragged, By):
    """Apply a scheme-frontier point ``(scheme, coarsen, interior_dtype)``.

    ``coarsen`` halves the PDE grid ``coarsen`` times: via the dyadic
    refinement levels when both are deep enough (exactly what the tuner
    measured), else by stride-subsampling the raw paths (dense batches
    only — ragged lengths would shift, so the point is skipped there).
    Recomputes the transformed lengths and cache-key shape when anything
    changed.
    """
    if found is None:
        return g, X, Y, *_key_dims(X, Y, cfg, g, By)
    scheme_p, coarsen, idt = found
    if coarsen:
        if g.lam1 >= coarsen and g.lam2 >= coarsen:
            g = dataclasses.replace(g, lam1=g.lam1 - coarsen,
                                    lam2=g.lam2 - coarsen)
        elif not ragged and X.shape[1] > (1 << coarsen):
            step = 1 << coarsen
            X = X[:, ::step]
            Y = Y if Y is None else Y[:, ::step]
        else:
            return g, X, Y, *_key_dims(X, Y, cfg, g, By)
    g = dataclasses.replace(g, scheme=scheme_p, interior_dtype=idt)
    return g, X, Y, *_key_dims(X, Y, cfg, g, By)


def _key_dims(X, Y, cfg, g, By):
    """(Lx, Ly, key_shape) for the current paths/config — the per-op
    autotune cache-key shape documented in repro.bench.autotune.cache_key."""
    Lx = cfg.transformed_steps(X.shape[1])
    Ly = Lx if Y is None else cfg.transformed_steps(Y.shape[1])
    key_shape = (X.shape[0], By, Lx << g.lam1, Ly << g.lam2,
                 cfg.transformed_dim(X.shape[-1]))
    return Lx, Ly, key_shape


# ---------------------------------------------------------------------------
# approximate feature maps — phi(X) whose inner products ≈ the exact Gram
# ---------------------------------------------------------------------------

def _nystroem_maps(sX, sY, feats, kernel, backend, g, launch):
    """Nyström features from prepared streams: phi = K(·, Z) · L_w^{-T}.

    Landmarks Z are pivoted-Cholesky-selected from a ``pool``-sized random
    subset of X (the pool Gram costs pool² exact solves — B-independent);
    the per-path cost is one row of ``rank`` exact solves.  The selection
    indices are detached (``stop_gradient``); every gathered value stays
    differentiable.
    """
    Bx = sX.shape[0]
    pool = feats.pool_size(Bx)
    rank = min(feats.rank, pool)
    pool_idx = jax.random.permutation(feats.resolved_key(), Bx)[:pool]
    sP = sX[pool_idx]
    dispatch.record_pair_solves(
        pool * pool + Bx * rank + (0 if sY is None else sY.shape[0] * rank))
    G_pool = _gram_block(sP, sP, kernel, backend, g, launch)
    piv, _ = ft.pivoted_cholesky(G_pool, rank)
    sZ = sP[piv]
    Lw = ft.nystroem_factor(G_pool[piv][:, piv], feats.jitter)
    phiX = ft.nystroem_phi(
        _gram_rows(sX, sZ, kernel, backend, g, None, launch), Lw)
    if sY is None:
        return phiX, None
    phiY = ft.nystroem_phi(
        _gram_rows(sY, sZ, kernel, backend, g, None, launch), Lw)
    return phiX, phiY


def _feature_maps(X, Y, feats, cfg, g, kernel, lengths, lengths_y, launch):
    """phi(X), phi(Y) under ONE shared feature-map draw (phi(Y) is None
    when ``Y`` is) — sharing the draw is what makes ⟨phi(X), phi(Y)⟩ a
    kernel approximation rather than noise."""
    if feats.method == "rff":
        phiX = ft.rff_features(X, feats, cfg, kernel, lengths)
        phiY = None if Y is None else \
            ft.rff_features(Y, feats, cfg, kernel, lengths_y)
        return phiX, phiY
    # nystroem: the pool/cross Grams use the exact engine's auto backend
    exact = dispatch.resolve("auto", op="gram",
                             allow_fused=kernel.lifts_increments,
                             scheme=g.scheme)
    sX = _prepare(X, cfg, kernel, lengths)
    sY = None if Y is None else _prepare(Y, cfg, kernel, lengths_y)
    return _nystroem_maps(sX, sY, feats, kernel, exact, g, launch)


def sigkernel_gram(X: jax.Array, Y: Optional[jax.Array] = None, *,
                   backend: str = "auto", row_block: Optional[int] = None,
                   symmetric: Optional[bool] = None,
                   lengths=None, lengths_y=None,
                   transforms=None, grid=None, static_kernel=None,
                   launch=None, features=None, error_budget=None,
                   lam1=UNSET, lam2=UNSET,
                   time_aug=UNSET, lead_lag=UNSET,
                   use_pallas=UNSET, solver=UNSET) -> jax.Array:
    """Signature-kernel Gram matrix ``K[a, b] = k(X_a, Y_b)``.

    Args:
      X: (Bx, L, d) batch of paths.
      Y: (By, L', d) batch, or ``None`` for the symmetric Gram ``k(X_a, X_b)``
        (solves only the upper triangle — ≈2× fewer PDE solves; large
        batches are auto-chunked so the pair gather never exceeds a fixed
        HBM budget).
      lengths / lengths_y: optional (Bx,) / (By,) int arrays of per-path
        true point counts for ragged batches.  ``K[a, b]`` is then exactly
        ``k(X_a[:lengths[a]], Y_b[:lengths_y[b]])``: padding is masked into
        end-aligned streams whose zero Δ rows/columns leave the Goursat
        boundary bitwise intact, on every backend including the fused-Δ
        Pallas kernels (see docs/solver_guide.md).  Length axes are padded
        to power-of-two buckets so nearby sizes share one jit trace;
        ``lengths_y`` requires ``Y``.
      backend: a name from :mod:`repro.core.dispatch` ("reference" |
        "antidiag" | "pallas" | "pallas_fused") or ``"auto"`` (platform- and
        shape-aware; "pallas_fused" on TPU).  ``"pallas_fused"`` requires
        the linear static kernel (Δ is built from increments in VMEM).
      row_block: if set, at most ``row_block`` Gram rows (or the equivalent
        number of symmetric pairs) are in flight at once; ``Bx`` is
        zero-padded to the block granularity, padded rows are dropped.
      symmetric: force/forbid the symmetric fast path.  Default: ``Y is
        None``.  ``symmetric=True`` requires ``Y`` to be ``None`` or ``X``.
      transforms: a :class:`repro.TransformPipeline` (§4 transforms,
        applied on-the-fly; basepoint included).
      grid: a :class:`repro.GridConfig` — dyadic refinement of the PDE grid.
      static_kernel: the static-kernel lift (:class:`repro.Linear` default,
        :class:`repro.RBF` for the Gaussian lift via the Δ-from-gram path).
      launch: an optional :class:`repro.LaunchConfig` of launch-parameter
        overrides (PDE strip height, Gram ``row_block`` default, antidiag
        band chunking).  ``None`` fields fall back to the autotuned winner
        for this shape bucket (if a tuned cache is warm) and then to the
        library defaults; an explicit ``row_block=`` argument beats
        ``launch.gram_row_block``.  Launch parameters never change the
        math — see docs/benchmarks.md § Launch-parameter tuning.
      features: a :class:`repro.FeatureConfig` activating an *approximate*
        feature-map backend (``"rff"`` / ``"nystroem"``): the result is
        ``phi(X) @ phi(Y).T ≈ K`` with no B×B PDE solve grid — O(B·rank)
        work, differentiable by plain autodiff through the feature maps,
        deterministic given the config's ``key`` leaf.  See
        docs/api/public.md § Approximate kernels.
      error_budget: a relative-error budget allowing ``backend="auto"`` to
        *legally* pick an approximation: used only when the autotune cache
        holds a measured accuracy-vs-speed frontier point for this shape
        bucket meeting the budget (the bench suite's ``approx_frontier``
        workload records them); otherwise the exact engine runs.  Without
        ``features=``/``error_budget=``, approximate backends are refused
        even when named explicitly.
      lam1 / lam2 / time_aug / lead_lag: deprecated aliases for ``grid=`` /
        ``transforms=`` (DeprecationWarning once per call-site).
      use_pallas / solver: deprecated aliases (DeprecationWarning) mapped to
        backend names — see docs/solver_guide.md.

    Returns:
      (Bx, By) Gram matrix (f32), differentiable end-to-end through the
      exact one-pass backward on every backend.

    See also :func:`sigkernel_gram_sharded` (the same Gram tiled over a
    device mesh) and :func:`sigkernel_gram_reduce` (streaming ``ΣK``
    without materialising K — what ``mmd2(streaming=True)`` uses).
    """
    (X, Y, lengths, lengths_y, cfg, g, kernel, backend, symmetric, launch,
     feats) = \
        _resolve_engine(X, Y, symmetric, lengths, lengths_y, transforms,
                        grid, static_kernel, lam1, lam2, time_aug, lead_lag,
                        use_pallas, solver, backend, launch,
                        features=features, error_budget=error_budget)
    if row_block is None:  # explicit arg beats the launch knob
        row_block = launch.gram_row_block

    if feats is not None:
        phiX, phiY = _feature_maps(X, Y, feats, cfg, g, kernel, lengths,
                                   lengths_y, launch)
        K = phiX @ (phiX if phiY is None else phiY).T
        return shard(K, "batch", "model")

    sX = _prepare(X, cfg, kernel, lengths)
    sX = shard(sX, "batch", None, None)
    Bx = sX.shape[0]

    if symmetric:
        return _symmetric_gram(sX, kernel, backend, row_block, g, launch)

    sY = _prepare(Y, cfg, kernel, lengths_y)
    sY = shard(sY, "model", None, None)
    By = sY.shape[0]

    if row_block is None:
        dispatch.record_pair_solves(Bx * By)
    else:
        n_blocks = (Bx + (-Bx) % row_block) // row_block
        dispatch.record_pair_solves(n_blocks * row_block * By)
    K = _gram_rows(sX, sY, kernel, backend, g, row_block, launch)
    return shard(K, "batch", "model")


# the pair-gather replicates increments (2·chunk·L·d floats live at once);
# above this budget an unset row_block is auto-chunked so the symmetric fast
# path never costs more HBM than the dense Gram it replaces
_SYM_GATHER_BUDGET = 64 * 1024 * 1024


def _auto_row_block(other: int, L: int, d: int) -> int:
    """Row block bounding one block's replicated-stream bytes by the
    gather budget: ``row_block`` rows against ``other`` columns."""
    return max(1, _SYM_GATHER_BUDGET // (8 * max(1, other) * L * d))


def _symmetric_gram(sX: jax.Array, kernel, backend: str,
                    row_block: Optional[int], g, launch=None) -> jax.Array:
    """Upper-triangle pair solve + mirror: Bx·(Bx+1)/2 (+ pad) PDE solves."""
    Bx = sX.shape[0]
    a_idx, b_idx = np.triu_indices(Bx)
    n_pairs = a_idx.size

    if row_block is None and 8 * n_pairs * sX.shape[1] * sX.shape[2] \
            > _SYM_GATHER_BUDGET:
        row_block = _auto_row_block(Bx, sX.shape[1], sX.shape[2])

    if row_block is None:
        dispatch.record_pair_solves(n_pairs)
        k = _solve_pairs(*_gather_pairs(sX, a_idx, b_idx), kernel, backend,
                         g, launch)
    else:
        # a block of `row_block` Gram rows ~ row_block·Bx pairs of live Δ
        chunk = max(1, int(row_block)) * Bx
        dispatch.record_pair_solves(n_pairs + (-n_pairs) % chunk)
        k = _solve_pairs_chunked(sX, a_idx, b_idx, kernel, backend, g,
                                 chunk, launch)
    return shard(_mirror(k, a_idx, b_idx, Bx), "batch", "model")


# ---------------------------------------------------------------------------
# streaming reductions — ΣK without materialising K (mmd2 / scoring_rule)
# ---------------------------------------------------------------------------

class StreamingViolation(RuntimeError):
    """A reduction that was requested to stream materialises the full Gram
    (or the full pairwise Δ stack) as an intermediate."""


def _walk_jaxpr_avals(jaxpr, visit) -> None:
    """Visit the aval of every intermediate in ``jaxpr``, recursing into
    sub-jaxprs (scan/map bodies, custom-vjp branches, pjit calls...)."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                visit(aval)
        stack = list(eqn.params.values())
        while stack:
            obj = stack.pop()
            if hasattr(obj, "eqns"):            # a Jaxpr
                _walk_jaxpr_avals(obj, visit)
            elif hasattr(obj, "jaxpr"):         # a ClosedJaxpr
                stack.append(obj.jaxpr)
            elif isinstance(obj, (list, tuple)):
                stack.extend(obj)


def assert_streaming_reduction(fn, *args, gram_shape,
                               what: str = "reduction") -> None:
    """Abstractly trace ``fn(*args)`` and raise :class:`StreamingViolation`
    if any intermediate materialises an array with leading dims
    ``gram_shape = (Bx, By)``.

    This is an ``eval_shape``-grade check: ``fn`` is traced with abstract
    values only (``args`` may be arrays or ``jax.ShapeDtypeStruct``), no
    FLOPs run, and every intermediate of the resulting jaxpr — including
    scan/map bodies and custom-VJP branches — is shape-checked.  Pass
    ``jax.value_and_grad(fn)`` to cover the VJP as well; ``mmd2`` /
    ``scoring_rule`` do exactly that when ``streaming=`` is on.

    The check keys on the *leading-dims* fingerprint of the dense engine:
    the full Gram is ``(Bx, By)`` and the dense pairwise Δ stack is
    ``(Bx, By, Lx, Ly)``, so both are caught by one prefix test.  Pick
    ``Bx != By`` and batch sizes distinct from L/d in tests to avoid
    shape-coincidence false positives (the internal guard behind
    ``mmd2(streaming=True)`` de-aliases them automatically by re-tracing
    with bumped batch sizes — genuine dense intermediates track the batch
    dims, coincidences like a ragged pad width equal to ``Bx`` do not).
    """
    offending = _dense_intermediates(fn, *args, gram_shape=gram_shape)
    if offending:
        bx, by = gram_shape
        raise StreamingViolation(
            f"streaming {what} materialises dense ({bx}, {by}) "
            f"intermediates: {sorted(set(offending))} — the full Gram "
            "(or pairwise Δ stack) must never exist; lower row_block or "
            "report a bug in repro.core.gram")


def _dense_intermediates(fn, *args, gram_shape) -> list:
    """Shapes of every intermediate of the abstract trace of ``fn(*args)``
    whose leading dims equal ``gram_shape``."""
    bx, by = gram_shape
    closed = jax.make_jaxpr(fn)(*args)
    offending = []

    def visit(aval):
        if len(aval.shape) >= 2 and aval.shape[0] == bx \
                and aval.shape[1] == by:
            offending.append(tuple(aval.shape))

    _walk_jaxpr_avals(closed.jaxpr, visit)
    return offending


#: (shape/config) keys whose streaming reduction already passed the guard
_stream_checked: set = set()


def _reduce_guard_key(args) -> Optional[tuple]:
    try:
        hash(args)
        return args
    except TypeError:
        return None  # unhashable config leaf (e.g. traced sigma): recheck


def sigkernel_gram_reduce(X: jax.Array, Y: Optional[jax.Array] = None, *,
                          include_diag: bool = True,
                          backend: str = "auto",
                          row_block: Optional[int] = None,
                          symmetric: Optional[bool] = None,
                          lengths=None, lengths_y=None,
                          transforms=None, grid=None, static_kernel=None,
                          launch=None, features=None, error_budget=None,
                          lam1=UNSET, lam2=UNSET,
                          time_aug=UNSET, lead_lag=UNSET,
                          use_pallas=UNSET, solver=UNSET,
                          check_streaming: bool = False) -> jax.Array:
    """Streaming ``Σ_{a,b} K[a, b]`` — the Gram-sum without the Gram.

    The workhorse of ``mmd2(streaming=True)`` / ``scoring_rule``:
    accumulates per-row-block (asymmetric) or per-pair-chunk (symmetric)
    partial sums under ``jax.checkpoint``, so at most one block of PDE
    solves is live at a time in the forward AND the backward — the VJP
    rematerialises each block instead of stacking residuals.  The full
    (Bx, By) Gram, and the (Bx, By, Lx, Ly) pairwise Δ stack, never exist.

    Args (beyond :func:`sigkernel_gram`'s):
      include_diag: symmetric reductions only — ``False`` drops the
        ``k(x_a, x_a)`` diagonal (the ``Σ − tr`` of the unbiased MMD) at
        zero extra solves (off-diagonal pairs enter with weight 2, the
        diagonal with weight 0).
      row_block: streaming granularity — at most ``row_block`` Gram rows
        (or ``row_block · Bx`` symmetric pairs) in flight.  Default: the
        largest block that fits the engine's pair-gather budget (for small
        problems that is one block, i.e. dense-equivalent).
      features / error_budget: activate an approximate feature-map backend
        exactly as in :func:`sigkernel_gram`.  The reduction then becomes
        pure feature algebra — ``ΣK = ⟨Σ_a phi(X)_a, Σ_b phi(Y)_b⟩`` and
        the diag-dropped symmetric sum ``‖Σ phi‖² − Σ_a ‖phi_a‖²`` — so
        peak memory is O(B·rank) with no row blocking needed, in the value
        and the grad (the streaming-shape guard covers this path too).
      check_streaming: run :func:`assert_streaming_reduction` on this
        reduction (value + grad) once per shape/config key before
        executing — the guard ``mmd2``/``scoring_rule`` enable whenever a
        streaming path is requested.  Skipped when one block covers the
        whole batch (streaming degenerates to dense by construction) —
        except on the feature path, which is checked whenever requested.

    Returns a scalar (f32), differentiable with the same exact one-pass
    backward as the Gram itself.
    """
    if not include_diag and not (symmetric or
                                 (symmetric is None and Y is None)):
        raise ValueError("include_diag=False requires the symmetric "
                         "reduction (Y=None)")
    # capture pre-padding abstract args for the guard: the re-entrant
    # closure below replays the padding itself
    guard_args = (X, Y, lengths, lengths_y)
    (X, Y, lengths, lengths_y, cfg, g, kernel, backend, symmetric, launch,
     feats) = \
        _resolve_engine(X, Y, symmetric, lengths, lengths_y, transforms,
                        grid, static_kernel, lam1, lam2, time_aug, lead_lag,
                        use_pallas, solver, backend, launch,
                        features=features, error_budget=error_budget)
    if row_block is None:  # explicit arg beats the launch knob
        row_block = launch.gram_row_block

    if feats is not None:
        if check_streaming:
            _guard_reduce(guard_args, include_diag=include_diag,
                          backend=backend,
                          row_block=1 if row_block is None else row_block,
                          symmetric=symmetric, transforms=cfg, grid=g,
                          static_kernel=kernel, launch=launch,
                          features=feats)
        phiX, phiY = _feature_maps(X, Y if not symmetric else None, feats,
                                   cfg, g, kernel, lengths, lengths_y,
                                   launch)
        if symmetric:
            s = phiX.sum(axis=0)
            total = s @ s
            if not include_diag:  # ΣK − tr(K), in feature space
                total = total - (phiX * phiX).sum()
            return total
        return phiX.sum(axis=0) @ phiY.sum(axis=0)

    sX = _prepare(X, cfg, kernel, lengths)
    Bx, L, d = sX.shape

    if symmetric:
        rb = row_block if row_block is not None else _auto_row_block(Bx, L, d)
        streams = rb * Bx < Bx * (Bx + 1) // 2
    else:
        By = Y.shape[0]
        rb = row_block if row_block is not None else _auto_row_block(By, L, d)
        streams = rb < Bx

    if check_streaming and streams:
        _guard_reduce(guard_args, include_diag=include_diag,
                      backend=backend, row_block=rb, symmetric=symmetric,
                      transforms=cfg, grid=g, static_kernel=kernel,
                      launch=launch)

    if symmetric:
        return _reduce_symmetric(sX, kernel, backend, rb, g, include_diag,
                                 launch)
    sY = _prepare(Y, cfg, kernel, lengths_y)
    return _reduce_rows(sX, sY, kernel, backend, rb, g, launch)


def _guard_reduce(guard_args, **kw) -> None:
    """Run the streaming-shape guard (value + grad) once per key.

    An abstract trace at the real batch sizes first, and — only if that
    finds a ``(Bx, By)``-shaped intermediate — confirmation traces with
    the batch dims AND ``row_block`` bumped (by one and by two).  A
    genuine dense Gram/Δ intermediate tracks the batch dims and is
    ``row_block``-independent, so it survives every bump.  Shape
    coincidences involve a size that does not track both bumped batch
    dims: static sizes (a ragged pad width equal to ``Bx``, a PDE grid
    dim equal to ``By``) cannot match the batch at two different bumps,
    and block-derived sizes (the symmetric pair chunk ``row_block · Bx``,
    the per-block row count) are pushed off the batch diagonal by the
    ``row_block`` bump — so both classes are cleared as false positives.
    """
    X, Y, lengths, lengths_y = guard_args
    names = [n for n, a in (("lengths", lengths), ("lengths_y", lengths_y))
             if a is not None]
    lens = [jnp.asarray(a) for a in (lengths, lengths_y) if a is not None]
    key = _reduce_guard_key((
        X.shape, str(X.dtype), None if Y is None else (Y.shape, str(Y.dtype)),
        tuple((a.shape, str(a.dtype)) for a in lens), tuple(names),
        tuple(sorted((k, repr(v)) for k, v in kw.items()))))
    if key is not None and key in _stream_checked:
        return
    n_arr = 1 if Y is None else 2
    diff = tuple(range(n_arr))

    def trace(bump):
        kwb = dict(kw, row_block=kw["row_block"] + bump)

        def red(*args):
            arrs, ls = args[:n_arr], args[n_arr:]
            return sigkernel_gram_reduce(*arrs, check_streaming=False,
                                         **dict(zip(names, ls)), **kwb)

        def s(a):
            return jax.ShapeDtypeStruct((a.shape[0] + bump,)
                                        + tuple(a.shape[1:]), a.dtype)
        args = [s(X)] + ([] if Y is None else [s(Y)]) + [s(a) for a in lens]
        bx = X.shape[0] + bump
        by = bx if Y is None else Y.shape[0] + bump
        return _dense_intermediates(
            jax.value_and_grad(red, argnums=diff), *args,
            gram_shape=(bx, by)), (bx, by)

    offending, (bx, by) = trace(0)
    if offending:
        if trace(1)[0] and trace(2)[0]:
            raise StreamingViolation(
                f"streaming Gram reduction materialises dense ({bx}, {by}) "
                f"intermediates: {sorted(set(offending))} — the full Gram "
                "(or pairwise Δ stack) must never exist; lower row_block "
                "or report a bug in repro.core.gram")
    if key is not None:
        _stream_checked.add(key)


def _reduce_symmetric(sX: jax.Array, kernel, backend: str, row_block: int,
                      g, include_diag: bool, launch=None) -> jax.Array:
    """Σ over the symmetric Gram via the upper triangle: off-diagonal
    pairs weighted 2, diagonal 1 (or 0), padding 0."""
    Bx = sX.shape[0]
    a_idx, b_idx = np.triu_indices(Bx)
    w = np.where(a_idx == b_idx, 1.0 if include_diag else 0.0, 2.0)
    n_pairs = a_idx.size
    chunk = max(1, int(row_block)) * Bx
    if chunk == Bx:
        # keep the per-chunk solver's (chunk, ...) intermediates off the
        # (Bx, Bx) fingerprint the streaming-shape guard scans for
        chunk = Bx + 1
    if chunk >= n_pairs:
        dispatch.record_pair_solves(n_pairs)
        k = _solve_pairs(*_gather_pairs(sX, a_idx, b_idx), kernel, backend,
                         g, launch)
        with jax.named_scope("repro.gram.reduce"):
            return (jnp.asarray(w, k.dtype) * k).sum()
    pad = (-n_pairs) % chunk
    dispatch.record_pair_solves(n_pairs + pad)
    a = np.concatenate([a_idx, np.zeros(pad, a_idx.dtype)])
    b = np.concatenate([b_idx, np.zeros(pad, b_idx.dtype)])
    wts = np.concatenate([w, np.zeros(pad, w.dtype)])
    a_c = jnp.asarray(a).reshape(-1, chunk)
    b_c = jnp.asarray(b).reshape(-1, chunk)
    w_c = jnp.asarray(wts, sX.dtype).reshape(-1, chunk)

    def block(abw):
        ai, bi, wi = abw
        k = _solve_pairs(*_gather_pairs(sX, ai, bi), kernel, backend, g,
                         launch)
        with jax.named_scope("repro.gram.reduce"):
            return (wi * k).sum()

    # checkpoint: lax.map would otherwise stack every block's Δ/grid
    # residuals — the backward rematerialises them one block at a time
    parts = jax.lax.map(jax.checkpoint(block), (a_c, b_c, w_c))
    with jax.named_scope("repro.gram.reduce"):
        return parts.sum()


def _reduce_rows(sX: jax.Array, sY: jax.Array, kernel, backend: str,
                 row_block: int, g, launch=None) -> jax.Array:
    """Σ over the (Bx, By) Gram, ``row_block`` rows at a time."""
    Bx, By = sX.shape[0], sY.shape[0]
    rb = max(1, int(row_block))
    if rb == 1 and By == 1:
        # (n_blocks, rb) = (Bx, 1) stacked blocks would alias the (Bx, 1)
        # Gram fingerprint the streaming-shape guard scans for
        rb = 2
    if rb >= Bx:
        dispatch.record_pair_solves(Bx * By)
        K = _gram_block(sX, sY, kernel, backend, g, launch)
        with jax.named_scope("repro.gram.reduce"):
            return K.sum()
    pad = (-Bx) % rb
    n_blocks = (Bx + pad) // rb
    dispatch.record_pair_solves(n_blocks * rb * By)
    with jax.named_scope("repro.gram.pairs"):
        if pad:
            sX = jnp.pad(sX, ((0, pad), (0, 0), (0, 0)))
        sXb = sX.reshape(n_blocks, rb, *sX.shape[1:])
    # padded rows give k = 1 (zero increments), NOT 0 — mask them out
    valid = (jnp.arange(n_blocks * rb).reshape(n_blocks, rb) < Bx)

    def block(args):
        sxb, v = args
        Kb = _gram_block(sxb, sY, kernel, backend, g, launch)
        with jax.named_scope("repro.gram.reduce"):
            return jnp.where(v[:, None], Kb, 0.0).sum()

    parts = jax.lax.map(jax.checkpoint(block), (sXb, valid))
    with jax.named_scope("repro.gram.reduce"):
        return parts.sum()


# ---------------------------------------------------------------------------
# sharded Gram — the (Bx, By) tile grid over a real device mesh
# ---------------------------------------------------------------------------

def sigkernel_gram_sharded(X: jax.Array, Y: Optional[jax.Array] = None, *,
                           mesh=None, row_axis: str = "data",
                           col_axis: str = "model", tile: int = 8,
                           backend: str = "auto",
                           row_block: Optional[int] = None,
                           symmetric: Optional[bool] = None,
                           lengths=None, lengths_y=None,
                           transforms=None, grid=None,
                           static_kernel=None, launch=None,
                           features=None, error_budget=None) -> jax.Array:
    """:func:`sigkernel_gram` tiled over a device mesh via ``shard_map``.

    The (Bx, By) Gram tile grid is 2-D **block-cyclic** sharded: row tiles
    of ``tile`` paths dealt round-robin over ``mesh[row_axis]``, column
    tiles over ``mesh[col_axis]``.  Each device solves its tiles' Goursat
    problems entirely locally from replicated prepared streams — no
    collectives cross the PDE solves; only the output concatenation (and
    whatever reduction the caller applies) is cross-device.

    The symmetric fast path is preserved *globally*: when ``Y`` is
    omitted, the ``Bx·(Bx+1)/2`` upper-triangle pairs are dealt
    round-robin over **all** ``mesh[row_axis]·mesh[col_axis]`` devices (the
    cyclic deal is what keeps the triangular tile grid load-balanced — a
    contiguous split would give the last device ~2× the solves of the
    first), solved locally, and mirrored once on the way out.  Total PDE
    solves stay at the triangle count (+ round-up padding), exactly as on
    one device.

    Args (beyond the single-device engine's):
      mesh: a :class:`jax.sharding.Mesh` with ``row_axis`` and ``col_axis``
        axes.  Default: :func:`repro.launch.mesh.make_gram_mesh` over every
        local device (a near-square ``(data, model)`` factorisation).
      tile: block-cyclic tile granularity (rows and columns).
      row_block: per-device sub-chunking — at most ``row_block`` local Gram
        rows (or ``row_block · Bx`` symmetric pairs) in flight per device.

    Ragged batches (``lengths=``) work unchanged: masking is burnt into the
    end-aligned prepared streams *before* the tiles are dealt, so the
    sharded tiling is ragged-oblivious.  Values match the single-device
    engine to reduction-order tolerance (bitwise for the pair solves
    themselves — only concatenation order differs).

    On a 1-device mesh this degenerates to the single-device engine.
    Prove it on a simulated mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see
    docs/api/public.md § Distributed & streaming Grams and
    ``examples/gram_matrix_distributed.py``).

    ``features=`` / ``error_budget=`` compose here too: with an
    approximation active there is no per-pair solve grid to tile, so the
    feature maps are computed once and the (Bx, By) result is the sharded
    matmul ``phi(X) @ phi(Y).T`` — rows annotated to the ``"batch"`` axis,
    columns to ``"model"``, partitioned by XLA under the active mesh.
    """
    (X, Y, lengths, lengths_y, cfg, g, kernel, backend, symmetric, launch,
     feats) = \
        _resolve_engine(X, Y, symmetric, lengths, lengths_y, transforms,
                        grid, static_kernel, UNSET, UNSET, UNSET, UNSET,
                        UNSET, UNSET, backend, launch,
                        features=features, error_budget=error_budget)
    if feats is not None:
        phiX, phiY = _feature_maps(X, Y, feats, cfg, g, kernel, lengths,
                                   lengths_y, launch)
        phiX = shard(phiX, "batch", None)
        K = phiX @ (phiX if phiY is None else shard(phiY, "model", None)).T
        return shard(K, "batch", "model")
    if row_block is None:  # explicit arg beats the launch knob
        row_block = launch.gram_row_block
    if mesh is None:
        from repro.launch.mesh import make_gram_mesh
        mesh = make_gram_mesh()
    for ax in (row_axis, col_axis):
        if ax not in mesh.shape:
            raise ValueError(
                f"mesh has no {ax!r} axis (axes: {tuple(mesh.shape)}); "
                "pass row_axis=/col_axis= matching your mesh")
    # check_vma=False: the Pallas calls in the local solves declare their
    # outputs without mesh-axis variance
    shard_map = get_shard_map()
    nd, nm = mesh.shape[row_axis], mesh.shape[col_axis]

    sX = _prepare(X, cfg, kernel, lengths)
    Bx = sX.shape[0]

    if symmetric:
        D = nd * nm
        a_idx, b_idx = np.triu_indices(Bx)
        n_pairs = a_idx.size
        pad = (-n_pairs) % D
        a_pad = np.concatenate([a_idx, np.zeros(pad, a_idx.dtype)])
        b_pad = np.concatenate([b_idx, np.zeros(pad, b_idx.dtype)])
        n_loc = (n_pairs + pad) // D
        # round-robin deal: device r solves global pairs r, r+D, r+2D, ...
        a_dev = jnp.asarray(a_pad.reshape(n_loc, D).T.copy())   # (D, n_loc)
        b_dev = jnp.asarray(b_pad.reshape(n_loc, D).T.copy())
        dispatch.record_pair_solves(n_pairs + pad)
        chunk = None if row_block is None else max(1, int(row_block)) * Bx

        def local(a_loc, b_loc, sx):
            k = _solve_pairs_chunked(sx, a_loc[0], b_loc[0], kernel,
                                     backend, g, chunk, launch)
            return k[None]

        with jax.named_scope("repro.gram.shard"):
            k_dev = shard_map(
                local, mesh=mesh,
                in_specs=(P((row_axis, col_axis)), P((row_axis, col_axis)),
                          P()),
                out_specs=P((row_axis, col_axis)), check_vma=False)(
                    a_dev, b_dev, sX)
            # undo the deal: global pair t·D + r sits at device r, slot t
            k = k_dev.reshape(D, n_loc).T.reshape(-1)[:n_pairs]
        return shard(_mirror(k, a_idx, b_idx, Bx), "batch", "model")

    sY = _prepare(Y, cfg, kernel, lengths_y)
    By = sY.shape[0]

    def _deal(s, n_shards):
        """Pad + block-cyclic permute dim 0; returns (dealt, inv_perm)."""
        B = s.shape[0]
        t = max(1, min(int(tile), -(-B // n_shards)))
        n_blocks = -(-B // t)
        n_blocks += (-n_blocks) % n_shards
        padded = n_blocks * t
        perm, inv = block_cyclic_perm(padded, n_shards, t)
        with jax.named_scope("repro.gram.pairs"):
            if padded > B:  # zero rows -> k = 1 tiles, sliced off at the end
                s = jnp.pad(s, ((0, padded - B),) + ((0, 0),) * (s.ndim - 1))
            return s[jnp.asarray(perm)], inv

    sXp, invR = _deal(sX, nd)
    sYp, invC = _deal(sY, nm)
    dispatch.record_pair_solves(sXp.shape[0] * sYp.shape[0])

    def local(sx, sy):
        return _gram_rows(sx, sy, kernel, backend, g, row_block, launch)

    with jax.named_scope("repro.gram.shard"):
        Kp = shard_map(local, mesh=mesh,
                       in_specs=(P(row_axis), P(col_axis)),
                       out_specs=P(row_axis, col_axis),
                       check_vma=False)(sXp, sYp)
        K = Kp[jnp.asarray(invR)][:, jnp.asarray(invC)][:Bx, :By]
    return shard(K, "batch", "model")

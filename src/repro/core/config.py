"""Composable kernel API v1 — pytree-registered config dataclasses.

The public surface of :mod:`repro` used to be a kwarg soup: ``time_aug=`` /
``lead_lag=`` bools, ``lam1``/``lam2`` ints and a deprecated ``use_pallas``
sprinkled over every entry point.  This module replaces them with three
small frozen dataclasses, all registered as JAX pytrees so they pass
cleanly through ``jax.jit`` / ``jax.vmap`` / ``jax.grad`` boundaries:

``TransformPipeline(time_aug, lead_lag, basepoint, t0, t1)``
    The §4 path transforms, applied on-the-fly to increments in the
    canonical order **basepoint → lead-lag → time-aug** (matching the
    materialised ``time_augment(lead_lag(basepoint(x)), t0, t1)``).
    ``t0``/``t1`` are *data* leaves (traceable floats); the booleans are
    static metadata because they change output shapes.

``GridConfig(lam1, lam2)``
    The independent dyadic refinement orders of the Goursat PDE grid.
    Both static (they enter shapes via bit-shifts).

``LaunchConfig(pde_strip, sig_bt, sig_lb, gram_row_block, band_chunk)``
    Kernel *launch parameters* — the tile/block/strip shapes that used to
    be module constants (``_MAX_T``, ``_MAX_BT``/``_LB``, the Gram
    ``row_block`` heuristic).  All static; all default to ``None`` ("use
    the library default", bitwise-identical to the pre-tuning constants).
    The autotune subsystem (:mod:`repro.bench.autotune`) sweeps a bounded
    space of these per shape-bucket and persists the winner.

``StaticKernel`` — ``Linear(scale)`` / ``RBF(sigma)``
    The static-kernel *lift* under the signature kernel (KSig-style).
    ``Linear`` keeps the paper's one-matmul Δ from increments; ``RBF``
    feeds the same Goursat solver through the Δ-from-Gram path
    (:func:`delta_from_gram`), so ``jax.grad`` still uses the exact
    one-pass §3.4 backward through ``_sigkernel_from_delta`` and plain
    autodiff (exact) through the RBF Gram itself.  ``scale``/``sigma``
    are data leaves — differentiable kernel hyper-parameters.

The legacy kwargs survive as shims: :func:`resolve_transforms` and
:func:`resolve_grid` map them onto config objects with a
``DeprecationWarning`` once per call-site (via
:func:`repro.core.dispatch._warn_deprecated`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .dispatch import UNSET, _warn_deprecated


def _pytree_dataclass(cls, data_fields, meta_fields):
    """Register a frozen dataclass as a pytree with static metadata."""
    jax.tree_util.register_dataclass(cls, data_fields=list(data_fields),
                                     meta_fields=list(meta_fields))
    return cls


# ---------------------------------------------------------------------------
# TransformPipeline — §4 path transforms as one value
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TransformPipeline:
    """On-the-fly §4 path transforms, in order basepoint → lead-lag → time-aug.

    Attributes:
      time_aug: append a uniform time channel over ``[t0, t1]``.
      lead_lag: interleave lead/lag copies (2d channels, 2L-1 points).
      basepoint: prepend the origin, making translations visible to S(x).
      t0 / t1: endpoints of the time-augmentation grid (data leaves —
        traceable under ``jit``; only used when ``time_aug=True``).
    """

    time_aug: bool = False
    lead_lag: bool = False
    basepoint: bool = False
    t0: float = 0.0
    t1: float = 1.0

    @property
    def identity(self) -> bool:
        """True when the pipeline changes nothing (fast-path check)."""
        return not (self.time_aug or self.lead_lag or self.basepoint)

    def transformed_dim(self, d: int) -> int:
        """Channel count after the pipeline (basepoint adds points, not
        channels)."""
        if self.lead_lag:
            d = 2 * d
        if self.time_aug:
            d = d + 1
        return d

    def transformed_steps(self, L: int) -> int:
        """Increment count after the pipeline for an L-point path."""
        n = L - 1
        if self.basepoint:
            n += 1
        if self.lead_lag:
            n *= 2
        return n


_pytree_dataclass(TransformPipeline, data_fields=("t0", "t1"),
                  meta_fields=("time_aug", "lead_lag", "basepoint"))


# ---------------------------------------------------------------------------
# GridConfig — dyadic refinement of the PDE grid
# ---------------------------------------------------------------------------

#: Goursat cell-update stencils the PDE backends implement
#: (kernels/sigkernel_pde/stencil.py holds the shared coefficient sets).
GRID_SCHEMES = ("order1", "order2")

#: interior-cell storage precisions. "bfloat16" rounds every interior cell
#: through bf16 after its update while the boundary of ones, carried
#: boundary rows and the readout stay f32 (the mixed-precision contract —
#: see docs/solver_guide.md, "Choosing a scheme order").
GRID_INTERIOR_DTYPES = ("float32", "bfloat16")


@dataclasses.dataclass(frozen=True)
class GridConfig:
    """Static Goursat-solver discretisation: grid refinement, stencil, dtype.

    ``lam1``/``lam2`` are independent dyadic refinement orders (λ1, λ2): a
    refined grid has ``(Lx << lam1) · (Ly << lam2)`` cells, so these enter
    shapes and must be Python ints.

    ``scheme`` selects the cell-update stencil: ``"order1"`` (the default —
    bitwise-identical to the historical solvers) or ``"order2"``, which adds
    an anti-diagonal curvature correction and typically reaches order-1
    accuracy on a ~2× coarser grid (docs/solver_guide.md).

    ``interior_dtype`` selects interior-cell storage precision:
    ``"float32"`` (default) or ``"bfloat16"`` (interior cells rounded
    through bf16 after each update; boundary and readout stay f32).

    All four fields are **static** metadata (compile-time choices baked
    into the kernels), hence pytree aux data.
    """

    lam1: int = 0
    lam2: int = 0
    scheme: str = "order1"
    interior_dtype: str = "float32"

    def __post_init__(self):
        for name in ("lam1", "lam2"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                raise ValueError(
                    f"GridConfig.{name} must be a non-negative Python int "
                    f"(it sets static grid shapes), got {v!r}")
        if self.scheme not in GRID_SCHEMES:
            raise ValueError(
                f"GridConfig.scheme must be one of {GRID_SCHEMES} (the "
                f"Goursat cell-update stencil, a static compile-time "
                f"choice), got {self.scheme!r}")
        if self.interior_dtype not in GRID_INTERIOR_DTYPES:
            raise ValueError(
                f"GridConfig.interior_dtype must be one of "
                f"{GRID_INTERIOR_DTYPES} (interior-cell storage precision; "
                f"boundary/readout always stay float32), "
                f"got {self.interior_dtype!r}")

    @property
    def cells_scale(self) -> int:
        return 1 << (self.lam1 + self.lam2)


_pytree_dataclass(GridConfig, data_fields=(),
                  meta_fields=("lam1", "lam2", "scheme", "interior_dtype"))


# ---------------------------------------------------------------------------
# LaunchConfig — kernel launch parameters (the autotune search space)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """Kernel launch parameters: the tile/block/strip shapes of the hot paths.

    Every field is **static** metadata (they set kernel block shapes and
    jit-trace structure) and every field defaults to ``None`` — "use the
    library default", which reproduces the pre-tuning constants bitwise.
    Non-default values come from three places, in precedence order: an
    explicit ``launch=`` kwarg on an entry point, the autotune cache
    (:mod:`repro.bench.autotune` sweeps a small bounded space per
    shape-bucket and persists the winner), and the defaults.

    Attributes:
      pde_strip: refined-row strip height per Goursat Pallas program
        (cap on ``T``; default 128 = ``kernels.sigkernel_pde.ops._MAX_T``).
        Must be a power of two; still shrunk to fit the VMEM budget and
        clamped to at least one unrefined row (``1 << lam1``).
      sig_bt: batch-tile (lane) cap of the signature Horner kernel
        (default 128 = ``kernels.signature.ops._MAX_BT``). Power of two.
        A signature too deep for the VMEM budget is split by leading
        letters, not by narrower tiles, which would save no VMEM.
      sig_lb: length-block of the signature Horner kernel's grid
        (default 256 = ``kernels.signature.ops._LB``). Power of two.
      gram_row_block: Gram-engine row blocking (``row_block=``) applied
        when the caller didn't pass one. Default ``None`` keeps today's
        behaviour (dense, or the symmetric path's gather-budget heuristic).
      band_chunk: antidiagonal-wavefront solver batching — at most this
        many Goursat pair problems are vectorised per sweep
        (``lax.map`` over chunks). Default: the whole flattened batch in
        one sweep. Caps the live band memory for huge pair batches.
    """

    pde_strip: Optional[int] = None
    sig_bt: Optional[int] = None
    sig_lb: Optional[int] = None
    gram_row_block: Optional[int] = None
    band_chunk: Optional[int] = None

    _POW2_FIELDS = ("pde_strip", "sig_bt", "sig_lb")

    def __post_init__(self):
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"LaunchConfig.{f.name} must be None or a positive "
                    f"Python int (it sets static kernel block shapes), "
                    f"got {v!r}")
            if f.name in self._POW2_FIELDS and v & (v - 1):
                raise ValueError(
                    f"LaunchConfig.{f.name} must be a power of two "
                    f"(kernel tiling constraint), got {v}")

    @property
    def is_default(self) -> bool:
        """True when every knob is at the library default."""
        return all(getattr(self, f.name) is None
                   for f in dataclasses.fields(self))

    def to_dict(self) -> dict:
        """JSON-friendly dict of the non-default knobs (autotune cache)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if getattr(self, f.name) is not None}

    @classmethod
    def from_dict(cls, d: dict) -> "LaunchConfig":
        """Rebuild from :meth:`to_dict` output.

        Unknown keys are dropped (fail-open: a cache written by a newer
        version must not break an older library); known keys with invalid
        values raise — callers treat that as a stale cache entry.
        """
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in dict(d).items() if k in known})


_pytree_dataclass(LaunchConfig, data_fields=(),
                  meta_fields=("pde_strip", "sig_bt", "sig_lb",
                               "gram_row_block", "band_chunk"))


def resolve_launch(launch: Optional[LaunchConfig]) -> LaunchConfig:
    """Default + type-check the ``launch=`` kwarg of the entry points."""
    if launch is None:
        return LaunchConfig()
    if not isinstance(launch, LaunchConfig):
        raise TypeError(
            f"launch= expects a LaunchConfig, got {type(launch).__name__} "
            f"(see docs/benchmarks.md, 'Launch-parameter tuning')")
    return launch


# ---------------------------------------------------------------------------
# static-kernel lifts (KSig-style)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StaticKernel:
    """Base class for static-kernel lifts κ under the signature kernel.

    Subclasses implement :meth:`gram` — pointwise κ between path *points*.
    ``lifts_increments = True`` (only :class:`Linear`) means Δ can be built
    directly from increment streams with one matmul (the paper's design
    choice (2), and what the fused Pallas kernels consume); every other
    kernel goes through the Δ-from-Gram path (:func:`delta_from_gram`).
    """

    #: Δ is a plain increment matmul — usable by the fused Pallas kernels
    lifts_increments = False

    def gram(self, x: jax.Array, y: jax.Array) -> jax.Array:
        """κ(x_i, y_j) pointwise: (..., Lx, d) × (..., Ly, d) -> (..., Lx, Ly).

        Leading batch dims broadcast, so ``gram(x[:, None], y[None])`` gives
        all pairwise point-Grams of two path batches.
        """
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Linear(StaticKernel):
    """κ(x, y) = scale · ⟨x, y⟩ — the paper's plain signature kernel."""

    scale: float = 1.0
    lifts_increments = True

    def gram(self, x, y):
        return _maybe_scale(jnp.einsum("...id,...jd->...ij", x, y),
                            self.scale)

    def delta_from_increments(self, dx: jax.Array, dy: jax.Array) -> jax.Array:
        """Δ[i,j] = scale · ⟨dx_i, dy_j⟩ — one batched matmul."""
        return _maybe_scale(jnp.einsum("...id,...jd->...ij", dx, dy),
                            self.scale)


@dataclasses.dataclass(frozen=True)
class RBF(StaticKernel):
    """κ(x, y) = exp(−‖x−y‖² / (2σ²)) — the Gaussian static-kernel lift."""

    sigma: float = 1.0

    def gram(self, x, y):
        sq = (jnp.sum(x * x, axis=-1)[..., :, None]
              + jnp.sum(y * y, axis=-1)[..., None, :]
              - 2.0 * jnp.einsum("...id,...jd->...ij", x, y))
        sq = jnp.maximum(sq, 0.0)        # clamp catastrophic cancellation
        return jnp.exp(-sq / (2.0 * jnp.asarray(self.sigma) ** 2))


for _cls, _data in ((StaticKernel, ()), (Linear, ("scale",)),
                    (RBF, ("sigma",))):
    _pytree_dataclass(_cls, data_fields=_data, meta_fields=())


def _maybe_scale(v: jax.Array, scale) -> jax.Array:
    """Multiply by ``scale`` unless it is concretely 1 (bitwise no-op)."""
    if isinstance(scale, (int, float)) and scale == 1.0:
        return v
    return v * scale


def delta_from_gram(G: jax.Array) -> jax.Array:
    """Δ from a pointwise static-kernel Gram: the double increment

        Δ[i,j] = G[i+1,j+1] − G[i+1,j] − G[i,j+1] + G[i,j]

    (..., Lx, Ly) -> (..., Lx-1, Ly-1).  For κ = ⟨·,·⟩ this reduces exactly
    to the increment matmul; for any other κ it is the discrete mixed
    second derivative the Goursat scheme integrates.
    """
    return (G[..., 1:, 1:] - G[..., 1:, :-1]
            - G[..., :-1, 1:] + G[..., :-1, :-1])


# ---------------------------------------------------------------------------
# legacy-kwarg resolution (deprecation shims shared by every entry point)
# ---------------------------------------------------------------------------

def _legacy_names(**kwargs) -> list:
    return [name for name, v in kwargs.items() if v is not UNSET]


def resolve_transforms(transforms: Optional[TransformPipeline],
                       time_aug=UNSET, lead_lag=UNSET,
                       _warn: bool = True) -> TransformPipeline:
    """Merge the legacy ``time_aug=``/``lead_lag=`` bools into a config.

    An explicit ``transforms=`` wins (contradictory legacy kwargs are
    ignored with a warning, matching :func:`dispatch.canonicalize`).
    Explicitly-passed legacy bools — even ``False`` — warn once per
    call-site and build the equivalent :class:`TransformPipeline`, which
    runs through the *same* code path (bitwise-identical results).
    """
    used = _legacy_names(time_aug=time_aug, lead_lag=lead_lag)
    if transforms is not None:
        if not isinstance(transforms, TransformPipeline):
            raise TypeError(
                f"transforms= expects a TransformPipeline, got "
                f"{type(transforms).__name__} (see docs/migration.md)")
        if used and _warn:
            _warn_deprecated(
                f"deprecated {'/'.join(n + '=' for n in used)} ignored "
                "because transforms= was passed explicitly "
                "(docs/migration.md)")
        return transforms
    if used:
        if _warn:
            _warn_deprecated(
                f"{'/'.join(n + '=' for n in used)} deprecated; pass "
                "transforms=repro.TransformPipeline(...) "
                "(docs/migration.md)")
        return TransformPipeline(
            time_aug=bool(time_aug) if time_aug is not UNSET else False,
            lead_lag=bool(lead_lag) if lead_lag is not UNSET else False)
    return TransformPipeline()


def resolve_grid(grid: Optional[GridConfig], lam1=UNSET, lam2=UNSET,
                 _warn: bool = True) -> GridConfig:
    """Merge the legacy ``lam1=``/``lam2=`` ints into a :class:`GridConfig`."""
    used = _legacy_names(lam1=lam1, lam2=lam2)
    if grid is not None:
        if not isinstance(grid, GridConfig):
            raise TypeError(
                f"grid= expects a GridConfig, got {type(grid).__name__} "
                f"(see docs/migration.md)")
        if used and _warn:
            _warn_deprecated(
                f"deprecated {'/'.join(n + '=' for n in used)} ignored "
                "because grid= was passed explicitly (docs/migration.md)")
        return grid
    if used:
        if _warn:
            _warn_deprecated(
                f"{'/'.join(n + '=' for n in used)} deprecated; pass "
                "grid=repro.GridConfig(lam1=..., lam2=...) "
                "(docs/migration.md)")
        return GridConfig(lam1=int(lam1) if lam1 is not UNSET else 0,
                          lam2=int(lam2) if lam2 is not UNSET else 0)
    return GridConfig()


def resolve_kernel_configs(transforms, grid, static_kernel, *,
                           time_aug=UNSET, lead_lag=UNSET,
                           lam1=UNSET, lam2=UNSET):
    """One-stop legacy resolution for the sig-kernel entry points.

    Emits at most **one** ``DeprecationWarning`` per call-site even when a
    call mixes transform and grid legacy kwargs (``sigkernel(x, y, lam1=1,
    time_aug=True)`` warns once, naming both).
    """
    used = _legacy_names(time_aug=time_aug, lead_lag=lead_lag,
                         lam1=lam1, lam2=lam2)
    if used:
        ignored = []
        if transforms is not None:
            ignored += _legacy_names(time_aug=time_aug, lead_lag=lead_lag)
        if grid is not None:
            ignored += _legacy_names(lam1=lam1, lam2=lam2)
        taken = [n for n in used if n not in ignored]
        parts = []
        if taken:
            parts.append(f"{'/'.join(n + '=' for n in taken)} deprecated; "
                         "pass transforms=repro.TransformPipeline(...) / "
                         "grid=repro.GridConfig(...)")
        if ignored:
            parts.append(f"deprecated {'/'.join(n + '=' for n in ignored)} "
                         "ignored because the config object was passed "
                         "explicitly")
        _warn_deprecated("; ".join(parts) + " (docs/migration.md)")
    return (resolve_transforms(transforms, time_aug, lead_lag, _warn=False),
            resolve_grid(grid, lam1, lam2, _warn=False),
            resolve_static_kernel(static_kernel))


def resolve_static_kernel(static_kernel: Optional[StaticKernel]
                          ) -> StaticKernel:
    """Default the lift to the paper's linear kernel; validate the type."""
    if static_kernel is None:
        return Linear()
    if not isinstance(static_kernel, StaticKernel):
        raise TypeError(
            f"static_kernel= expects a StaticKernel (repro.Linear / "
            f"repro.RBF), got {type(static_kernel).__name__}")
    return static_kernel

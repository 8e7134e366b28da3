"""Unified solver-backend registry and dispatch (the single switchboard).

Every compute-heavy entry point (``signature``, ``logsignature``,
``sigkernel``, the Gram engine in :mod:`repro.core.gram` and the losses on
top of it) selects its execution path through this registry instead of
ad-hoc ``use_pallas`` bools / ``solver=`` strings.  A backend is a *named*
implementation with capability flags; ``"auto"`` resolves per op from the
active JAX platform and the problem shape.

Registered backends:

``"reference"``
    Pure-JAX row-major scans (oracle-grade, serial).  Works everywhere,
    exact one-pass backward for the sig-kernel ops.
``"antidiag"``
    Vectorised anti-diagonal wavefront (SIMD on CPU/GPU).  Sig-kernel ops
    only; the exact backward recomputes the reference grid.
``"pallas"``
    Pallas TPU kernels (compiled on TPU, interpret mode elsewhere).
    Checkpointed exact backward for the PDE; Horner kernel for signatures.
``"pallas_fused"``
    Fused-Δ Pallas PDE kernels: Δ is built in VMEM from the increments and
    never exists in HBM.  Gram-capable; differentiable via the checkpointed
    exact backward (which re-materialises Δ for the reverse sweep only).
``"rff"`` / ``"nystroem"``
    Approximate feature-map Gram backends (:mod:`repro.core.features`):
    random Fourier signature features and Nyström landmark low-rank.
    Flagged ``approximate=True`` — never resolved for an exact request;
    ``"auto"`` may pick them only when the caller passes an
    ``error_budget=`` and the autotune cache holds a measured frontier
    entry meeting it (:func:`resolve_approx`).
``"auto"``
    Measured winner from the on-disk autotune cache when one exists for the
    (op, shape, dtype, platform) key (:mod:`repro.bench.autotune`);
    shape/platform heuristics when the cache is cold or autotuning is
    disabled (``REPRO_DISABLE_AUTOTUNE=1``).

The legacy ``use_pallas=``/``solver=`` kwargs survive as thin deprecation
shims: :func:`canonicalize` maps them onto backend names with a
``DeprecationWarning`` (once per call-site).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import threading
import warnings
from typing import Dict, FrozenSet, Optional, Tuple

import jax

#: ops a backend can serve
OPS = ("signature", "logsignature", "sigkernel", "gram")

#: sentinel distinguishing "kwarg not passed" from an explicit value
UNSET = object()

#: below this many refined PDE cells the serial reference scan wins on
#: CPU/GPU (the anti-diagonal skew/gather overhead dominates tiny grids)
_ANTIDIAG_MIN_CELLS = 4096


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Capability card for one named backend."""

    name: str
    ops: FrozenSet[str]
    #: backward is the paper's exact one-pass scheme (§2.4 / §3.4 Alg 4),
    #: not plain autodiff through the forward
    grad_exact: bool
    #: can produce a whole Gram matrix without materialising every pairwise
    #: Δ in HBM up front
    gram_capable: bool
    #: compiled only on TPU; elsewhere it runs in (slow) interpret mode
    needs_tpu: bool
    #: consumes path increments directly — Δ never exists in HBM
    fused: bool = False
    #: result is an *approximation* (feature-map inner products, not the
    #: exact PDE kernel) — refused unless the caller opted in with
    #: ``features=`` / ``error_budget=``; never an ``"auto"`` winner for
    #: an exact request
    approximate: bool = False
    #: Goursat cell-update stencils this backend implements
    #: (:data:`repro.core.config.GRID_SCHEMES`).  A backend that does not
    #: implement the requested ``GridConfig.scheme`` is *refused* with an
    #: error — never silently downgraded to another stencil.
    schemes: FrozenSet[str] = frozenset({"order1", "order2"})


_REGISTRY: Dict[str, BackendSpec] = {}


def register(spec: BackendSpec) -> BackendSpec:
    """Add (or replace) a backend in the registry."""
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> BackendSpec:
    """Look up a backend by name; raise with the known names otherwise."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)} "
            f"(plus 'auto')") from None


def backends_for(op: str) -> Tuple[str, ...]:
    """Names of all registered backends that serve ``op``."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; known: {OPS}")
    return tuple(sorted(n for n, s in _REGISTRY.items() if op in s.ops))


register(BackendSpec("reference", frozenset(OPS), grad_exact=True,
                     gram_capable=False, needs_tpu=False))
register(BackendSpec("antidiag", frozenset({"sigkernel", "gram"}),
                     grad_exact=True, gram_capable=False, needs_tpu=False))
register(BackendSpec("pallas", frozenset(OPS), grad_exact=True,
                     gram_capable=False, needs_tpu=True))
register(BackendSpec("pallas_fused", frozenset({"sigkernel", "gram"}),
                     grad_exact=True, gram_capable=True, needs_tpu=True,
                     fused=True))
# feature-map approximations: differentiable (plain JAX autodiff through
# the feature maps — not the paper's one-pass exact-Gram backward, hence
# grad_exact=False), Gram-capable by construction (phi is (B, F); no B×B
# intermediate ever forms), platform-agnostic
register(BackendSpec("rff", frozenset({"gram"}), grad_exact=False,
                     gram_capable=True, needs_tpu=False, approximate=True,
                     schemes=frozenset({"order1"})))
register(BackendSpec("nystroem", frozenset({"gram"}), grad_exact=False,
                     gram_capable=True, needs_tpu=False, approximate=True,
                     schemes=frozenset({"order1"})))


# ---------------------------------------------------------------------------
# legacy-kwarg shims
# ---------------------------------------------------------------------------

#: user call-sites that already got their DeprecationWarning this process
_warned_sites: set = set()

#: hard cap on the dedup set: a pathological caller minting fresh call-sites
#: forever (exec'd snippets, generated code) must not grow memory without
#: bound — past the cap new sites still warn, they just stop deduplicating
_MAX_WARNED_SITES = 4096

#: this library's own package directory — frames under it are shim-internal
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.realpath(__file__))) \
    + os.sep


@functools.lru_cache(maxsize=1024)
def _is_own_frame_file(filename: str) -> bool:
    """Whether a frame's co_filename lives under this library's install dir.

    Cached per filename: the frame walk runs on *every* deprecated call
    (even already-deduplicated ones), and realpath stats the filesystem.
    """
    return os.path.realpath(filename).startswith(_PKG_DIR)


def reset_warned_sites() -> None:
    """Forget which call-sites have warned (tests)."""
    _warned_sites.clear()


def _warn_deprecated(message: str) -> None:
    """Emit ``DeprecationWarning`` once per *user call-site*.

    The warning is attributed to the first stack frame whose file lives
    outside this library's own install directory (so internal shims —
    ``sigkernel.sigkernel_gram``, ``sigkernel_gram_blocked``, the losses —
    never absorb it, while a *user* script or package that merely happens
    to be named ``repro`` is correctly treated as the call-site) and
    deduplicated on that frame's (filename, lineno): a training loop
    passing ``use_pallas=`` every step warns once, not once per call,
    while distinct call-sites each get their own warning.  The dedup key
    deliberately excludes the message, so one call mixing several
    deprecated kwarg families (``lam1=`` + ``use_pallas=``) still emits
    exactly one warning per call-site.
    """
    depth = 1  # sys._getframe index; 0 is this helper
    frame = sys._getframe(1)
    while frame is not None and _is_own_frame_file(
            frame.f_code.co_filename):
        frame = frame.f_back
        depth += 1
    if frame is not None:
        site = (frame.f_code.co_filename, frame.f_lineno)
        if site in _warned_sites:
            return
        if len(_warned_sites) < _MAX_WARNED_SITES:
            _warned_sites.add(site)
    # warnings stacklevel n attributes to sys._getframe(n - 1) from here
    warnings.warn(message, DeprecationWarning, stacklevel=depth + 1)


def _validate(backend: str, op: str) -> str:
    """Check a concrete backend name exists and implements ``op``."""
    spec = get(backend)
    if op not in spec.ops:
        raise ValueError(
            f"backend {backend!r} does not implement op {op!r}; "
            f"options: {backends_for(op)}")
    return backend


def check_scheme(backend: str, scheme: str, *, op: str) -> str:
    """Refuse a backend that does not implement the requested stencil.

    The scheme capability contract (ISSUE: no silent downgrades): a backend
    whose :attr:`BackendSpec.schemes` does not contain
    ``GridConfig.scheme`` raises, naming the knob, the backend's supported
    schemes, and the backends that *do* implement the request — it is never
    quietly served with a different discretisation.
    """
    spec = get(backend)
    if scheme not in spec.schemes:
        capable = tuple(n for n in backends_for(op)
                        if scheme in get(n).schemes)
        raise ValueError(
            f"backend {backend!r} does not implement "
            f"GridConfig.scheme={scheme!r} (it supports "
            f"{tuple(sorted(spec.schemes))}); schemes are never silently "
            f"downgraded — pick a capable backend for op {op!r}: {capable}, "
            f"or a supported scheme (docs/solver_guide.md, 'Choosing a "
            f"scheme order')")
    return backend


def canonicalize(backend: str, *, op: str, use_pallas=UNSET,
                 solver=UNSET) -> str:
    """Map legacy ``use_pallas``/``solver`` kwargs onto a backend name.

    ``backend`` wins when it is not ``"auto"`` (validated against ``op``;
    contradictory legacy kwargs are ignored with a warning).
    ``use_pallas=True`` overrides ``solver=`` — the historical precedence of
    ``sigkernel_gram_blocked``.  ``use_pallas=None`` is the historical
    documented "auto" and stays silent; explicit bools and ``solver=``
    strings emit a ``DeprecationWarning`` once per call-site.  Returns a
    backend name (possibly still ``"auto"`` — resolve it with
    :func:`resolve`).
    """
    legacy_given = ((use_pallas is not UNSET and use_pallas is not None)
                    or (solver is not UNSET and solver is not None))
    if backend != "auto":
        if legacy_given:
            _warn_deprecated(
                f"deprecated use_pallas=/solver= ignored because "
                f"backend={backend!r} was passed explicitly")
        return _validate(backend, op)
    if use_pallas is not UNSET and use_pallas is not None:
        _warn_deprecated(
            "use_pallas= is deprecated; pass backend='pallas' / "
            "backend='reference' instead (docs/solver_guide.md)")
        if use_pallas:  # historically overrode solver=
            return "pallas"
        if solver is UNSET or solver is None:
            return "reference"
    if solver is not UNSET and solver is not None:
        _warn_deprecated(
            "solver= is deprecated; pass backend='antidiag' / "
            "backend='reference' instead (docs/solver_guide.md)")
        return "antidiag" if solver == "antidiag" else "reference"
    return "auto"


# ---------------------------------------------------------------------------
# auto-selection
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _autotuned(op: str, shape, dtype, ragged: bool = False) -> Optional[str]:
    """Winning backend from the on-disk autotune cache, or None.

    None (→ static heuristics) whenever the cache is cold, autotuning is
    disabled (``REPRO_DISABLE_AUTOTUNE=1``), the cache file is unreadable,
    or the cached name no longer denotes a live backend serving ``op``.
    Lookups never run a measurement — tuning happens only through
    :func:`repro.bench.autotune.tune` (the bench suite does this).
    ``ragged`` keys variable-length workloads separately: the same padded
    shape does very different work when most of it is masked.
    """
    if shape is None:
        return None
    try:
        from repro.bench import autotune
    except ImportError:
        return None
    if not autotune.enabled():
        return None
    try:
        name = autotune.lookup(op, shape, dtype or "float32", ragged=ragged)
    except (ValueError, TypeError):
        return None
    spec = _REGISTRY.get(name)
    if spec is None or op not in spec.ops:
        return None  # stale entry: backend renamed/removed since tuning
    if spec.needs_tpu and not on_tpu():
        return None  # never let a stale entry force interpret mode
    if spec.approximate:
        # exact-winner cache keys must never return an approximation; the
        # budgeted path goes through resolve_approx → lookup_budget
        return None
    return name


def _autotuned_launch(op: str, shape, dtype, ragged: bool = False):
    """Tuned :class:`repro.core.config.LaunchConfig` for this key, or None.

    Same fail-open discipline as :func:`_autotuned`: any problem — cold
    cache, disabled autotune, unreadable file, a launch dict with invalid
    values, a fingerprint from another machine — yields None and the
    library defaults.  Lookups never measure anything.
    """
    if shape is None:
        return None
    try:
        from repro.bench import autotune
    except ImportError:
        return None
    if not autotune.enabled():
        return None
    try:
        return autotune.lookup_launch(op, shape, dtype or "float32",
                                      ragged=ragged)
    except (ValueError, TypeError):
        return None


def resolve_launch(launch=None, *, op: str, shape=None, dtype=None,
                   ragged: bool = False):
    """Concrete :class:`LaunchConfig`: explicit > autotuned > defaults.

    The companion of :func:`resolve` for kernel *launch parameters*: an
    explicit ``launch=`` from the caller always wins; otherwise the
    autotune cache may hold a swept winner for the same
    ``(op, shape-bucket, dtype, platform, ragged)`` key that stores the
    backend winner; otherwise every knob stays at the library default
    (bitwise-identical to the pre-tuning constants).
    """
    from .config import LaunchConfig, resolve_launch as _check
    if launch is not None:
        return _check(launch)
    tuned = _autotuned_launch(op, shape, dtype, ragged)
    return tuned if tuned is not None else LaunchConfig()


def resolve(backend: str, *, op: str, grid_cells: Optional[int] = None,
            shape=None, dtype=None, allow_fused: bool = True,
            ragged: bool = False, allow_approximate: bool = False,
            scheme: str = "order1") -> str:
    """Resolve ``"auto"`` to a concrete backend name for ``op``.

    When ``shape`` is given (the per-op cache-key shape documented in
    :func:`repro.bench.autotune.cache_key`) and the autotune cache holds a
    measured winner for it, that wins.  Otherwise the static heuristics
    apply: ``grid_cells`` is the refined PDE cell count ``nx·ny``
    (sig-kernel ops only); small grids stay on the serial reference scan
    where the wavefront's skew overhead is not worth paying.

    ``allow_fused=False`` keeps ``"auto"`` off fused-Δ backends — used when
    Δ is not a plain increment matmul (non-linear static-kernel lifts),
    which a fused kernel cannot build in VMEM.  ``ragged=True`` marks a
    variable-length (``lengths=``) workload: its autotune cache key is kept
    separate from the dense key of the same padded shape.

    ``allow_approximate=False`` (the default) means the caller wants the
    exact kernel: backends flagged ``approximate=True`` are *refused* even
    when named explicitly — opting in requires ``features=`` or
    ``error_budget=`` on the Gram/loss entry points, which resolve with
    ``allow_approximate=True``.  ``"auto"`` never returns an approximate
    backend from this function either way (the budgeted route is
    :func:`resolve_approx`).

    ``scheme`` is the requested :class:`repro.GridConfig` stencil: a
    concrete backend (explicit *or* auto/autotuned winner) that does not
    list it in :attr:`BackendSpec.schemes` is refused via
    :func:`check_scheme` — the discretisation is never silently swapped.
    """
    if backend != "auto":
        name = _validate(backend, op)
        if get(name).approximate and not allow_approximate:
            raise ValueError(
                f"backend {name!r} is flagged approximate=True (feature-map "
                f"inner products, not the exact PDE kernel) and an exact "
                f"result was requested; pass features=FeatureConfig(...) or "
                f"error_budget= to opt in (docs/api/public.md, 'Approximate "
                f"kernels'), or pick an exact backend: "
                f"{tuple(n for n in backends_for(op) if not get(n).approximate)}")
        return check_scheme(name, scheme, op=op)
    tuned = _autotuned(op, shape, dtype, ragged)
    if tuned is not None and (allow_fused or not get(tuned).fused) \
            and scheme in get(tuned).schemes:
        return tuned
    if op in ("signature", "logsignature"):
        return "pallas" if on_tpu() else "reference"
    if on_tpu():
        name = "pallas_fused" if op == "gram" and allow_fused else "pallas"
    elif grid_cells is not None and grid_cells >= _ANTIDIAG_MIN_CELLS:
        name = "antidiag"
    else:
        name = "reference"
    return check_scheme(name, scheme, op=op)


def resolve_approx(op: str, shape=None, dtype=None, *,
                   error_budget: float, ragged: bool = False
                   ) -> Optional[Tuple[str, int]]:
    """Cheapest approximate backend meeting ``error_budget``, or None.

    The only road by which ``"auto"`` may legally land on an approximate
    backend: the caller supplied an explicit relative-error budget, and the
    autotune cache holds a *measured* accuracy-vs-speed frontier for this
    ``(op, shape-bucket, dtype, platform)`` key
    (:func:`repro.bench.autotune.tune_frontier`, run by the bench suite's
    ``approx_frontier`` workload) with an entry whose measured relative
    error fits the budget *and* that beat the exact engine's wall clock.
    Returns ``(backend_name, rank)`` or None — same fail-open discipline as
    :func:`_autotuned`: cold cache, disabled autotune, unreadable file,
    foreign machine stamp, or no qualifying point all mean None (→ the
    exact engine).
    """
    if shape is None or error_budget is None:
        return None
    try:
        from repro.bench import autotune
    except ImportError:
        return None
    if not autotune.enabled():
        return None
    try:
        found = autotune.lookup_budget(op, shape, dtype or "float32",
                                       error_budget, ragged=ragged)
    except (ValueError, TypeError):
        return None
    if found is None:
        return None
    name, rank = found
    spec = _REGISTRY.get(name)
    if spec is None or op not in spec.ops or not spec.approximate:
        return None  # stale frontier entry
    return name, int(rank)


def resolve_scheme(op: str, shape=None, dtype=None, *,
                   error_budget: float, ragged: bool = False
                   ) -> Optional[Tuple[str, int, str]]:
    """Cheapest measured *discretisation* meeting ``error_budget``, or None.

    The exact-engine sibling of :func:`resolve_approx`: instead of
    swapping the PDE solve for feature maps, the scheme frontier trades
    stencil order, grid coarseness and interior precision — the autotune
    cache (:func:`repro.bench.autotune.tune_scheme_frontier`, recorded by
    the bench suite's ``scheme_frontier`` workload) holds measured
    ``(scheme, coarsen, interior_dtype)`` points with their relative error
    against the order-1 fine-grid f32 baseline.  Returns the cheapest
    point that fits the budget *and* beat the baseline's wall clock, or
    None under the same fail-open discipline as :func:`resolve_approx`
    (cold cache, autotune disabled, foreign machine, no qualifying
    point).  Only consulted when the caller left ``GridConfig.scheme`` /
    ``interior_dtype`` at their defaults — an explicit choice is never
    overridden.
    """
    if shape is None or error_budget is None:
        return None
    try:
        from repro.bench import autotune
    except ImportError:
        return None
    if not autotune.enabled():
        return None
    try:
        found = autotune.lookup_scheme_budget(op, shape, dtype or "float32",
                                              error_budget, ragged=ragged)
    except (ValueError, TypeError):
        return None
    if found is None:
        return None
    scheme, coarsen, idt = found
    from repro.kernels.sigkernel_pde import stencil
    if scheme not in stencil.SCHEMES or idt not in stencil.INTERIOR_DTYPES:
        return None  # stale frontier entry
    return scheme, int(coarsen), idt


# ---------------------------------------------------------------------------
# op accounting (used by tests / the benchmark smoke job to verify the
# symmetric-Gram fast path really does ~half the PDE solves, and by the
# streaming Path engine to prove interval queries never re-scan a path)
# ---------------------------------------------------------------------------

_count_state = threading.local()


class _op_counter:
    """Context manager counting one op kind issued at *trace* time.

    Counts are per-thread and only reflect traces executed inside the
    context (jit cache hits recompute nothing and therefore count nothing —
    call on fresh shapes).
    """

    _slot: str = ""

    def __init__(self):
        self.total = 0

    def __enter__(self):
        self._prev = getattr(_count_state, self._slot, None)
        setattr(_count_state, self._slot, self)
        return self

    def __exit__(self, *exc):
        setattr(_count_state, self._slot, self._prev)
        return False


def _record(slot: str, n: int) -> None:
    active = getattr(_count_state, slot, None)
    if active is not None:
        active.total += int(n)


class count_pair_solves(_op_counter):
    """Counts PDE pair-solves: the engine reports the batch size it hands to
    each solver call (including any padding), so ``with count_pair_solves()
    as c: ...; c.total`` is the number of Goursat problems solved."""

    _slot = "pair"


class count_packed_slots(_op_counter):
    """Counts the pair slots of the packed PDE kernels: each fused kernel
    reports its programs × pairs per program, so ``c.total`` is the slots
    solved, ``c.pairs`` the real pairs among them (the rest are padding)
    and ``c.fill`` their ratio."""

    _slot = "pack"

    def __init__(self):
        super().__init__()
        self.pairs = 0

    @property
    def fill(self) -> float:
        return self.pairs / self.total if self.total else 1.0


class count_bwd_packed_slots(count_packed_slots):
    """``count_packed_slots`` for the exact backward kernel, whose packs
    the forward's counter does not count."""

    _slot = "bwd_pack"


class count_horner_lanes(_op_counter):
    """Counts the lanes of the Horner signature kernel: each launch reports
    its batch tiles × 128 lanes (the width of a VMEM tile, whatever the
    tile's own), so ``c.total`` is the lanes swept, ``c.paths`` the real
    paths among them and ``c.fill`` their ratio."""

    _slot = "lanes"

    def __init__(self):
        super().__init__()
        self.paths = 0

    @property
    def fill(self) -> float:
        return self.paths / self.total if self.total else 1.0


class count_scan_steps(_op_counter):
    """Counts signature Horner-scan steps (one per increment folded).

    ``repro.core.signature`` reports the increment-stream length of every
    scan it traces, so ``c.total`` is how many path increments were
    re-processed — the quantity the streaming ``repro.Path`` engine drives
    to zero for interval queries and to O(chunk) for ``update()``.
    """

    _slot = "scan"


class count_combines(_op_counter):
    """Counts Chen combines issued by the streaming ``repro.Path`` engine
    (one per interval query; O(chunk) per ``update``)."""

    _slot = "combine"


def record_pair_solves(n: int) -> None:
    """Report ``n`` PDE pair-solves to the active counter (no-op otherwise)."""
    _record("pair", n)


def record_packed_slots(slots: int, pairs: int, slot: str = "pack") -> None:
    """Report ``slots`` packed-kernel slots holding ``pairs`` real pairs
    (``slot="bwd_pack"``: the exact backward's)."""
    _record(slot, slots)
    active = getattr(_count_state, slot, None)
    if active is not None:
        active.pairs += int(pairs)


def record_horner_lanes(lanes: int, paths: int) -> None:
    """Report ``lanes`` Horner-kernel lanes holding ``paths`` real paths."""
    _record("lanes", lanes)
    active = getattr(_count_state, "lanes", None)
    if active is not None:
        active.paths += int(paths)


def record_scan_steps(n: int) -> None:
    """Report ``n`` Horner-scan steps to the active counter."""
    _record("scan", n)


def record_combines(n: int) -> None:
    """Report ``n`` Chen combines to the active counter."""
    _record("combine", n)

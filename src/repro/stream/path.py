"""Streaming ``Path`` engine: O(1) interval signatures over growing paths.

Signatory's ``Path`` class (PAPERS.md, arxiv 2001.00706) showed the right
shape for online signature serving: precompute the signature of every
*prefix* of a path once (one O(L) scan), and every interval query becomes a
single Chen combine of two stored group elements — no re-scan, whatever the
interval.  This module is that engine on top of the repro stack:

* the prefix store is the library's own Horner stream scan
  (:func:`repro.core.signature._signature_stream_from_increments`), so
  ``path.signature(0, j)`` is **bitwise** the reference
  ``repro.signature(points[:j])``;
* interval queries use the truncated-tensor-algebra group structure:
  ``S(x[i:j]) = S(x[:i])^{-1} ⊗ S(x[:j])`` with the inverses precomputed
  (:func:`repro.core.tensoralg.sig_inverse`), so a query is one
  :func:`repro.core.tensoralg.chen` — O(sig_dim), independent of ``j-i``
  and of the path length (verified by the scan/combine counters in
  :mod:`repro.core.dispatch`);
* ``update(new_points)`` extends the path by scanning **only the new
  chunk** and Chen-combining its prefixes onto the stored tip — O(chunk)
  work, zero full-path re-scans;
* buffers are padded to PR 5's power-of-two buckets
  (:func:`repro.core.transforms.bucket_length`) along both the capacity
  and the append-chunk axes, so paths of nearby lengths share one jit
  trace and steady-state appends hit a **warm** trace (instrumented by
  :func:`trace_counts`).

Transform support: ``lead_lag`` composes (its increments are local, so an
interval of the transformed stream *is* the transform of the interval);
``time_aug`` and ``basepoint`` are rejected — the ``[t0, t1]`` grid
renormalises every increment whenever the path grows, and a basepoint
belongs to the whole path, not to its intervals.  Put a physical time
channel in the data instead (docs/api/public.md, "Streaming paths").

Numerical contract: queries are *exact* group arithmetic on the stored
prefixes.  ``signature(0, j)`` (and the no-arg full signature) is bitwise
identical to the reference scan of ``points[:j]``; general ``(i, j)``
intervals agree with a fresh recompute to within a few ULPs (the combine
multiplies two floats the scan folds in a different order).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from ..core import lyndon
from ..core import tensoralg as ta
from ..core import transforms as tf
from ..core.config import TransformPipeline, _pytree_dataclass
from ..core.dispatch import record_combines
from ..core.logsignature import MODES as _LOGSIG_MODES
from ..core.signature import _signature_stream_from_increments

#: jit-trace counters per kernel kind — bumped by a Python side effect
#: inside the jitted bodies, so they advance once per *trace* (shape
#: bucket), never on warm-cache calls.  Tests and the serving loop read
#: them to prove bucketing really bounds retracing.
_TRACE_COUNTS: Dict[str, int] = {"build": 0, "update": 0, "query": 0,
                                 "evict": 0}


def trace_counts() -> Dict[str, int]:
    """Snapshot of the jit-trace counters (build/update/query/evict)."""
    return dict(_TRACE_COUNTS)


def reset_trace_counts() -> None:
    """Zero the jit-trace counters (tests)."""
    for k in _TRACE_COUNTS:
        _TRACE_COUNTS[k] = 0


def _check_pipeline(transforms: Optional[TransformPipeline]
                    ) -> TransformPipeline:
    if transforms is None:
        return TransformPipeline()
    if not isinstance(transforms, TransformPipeline):
        raise TypeError(
            f"transforms= expects a TransformPipeline, got "
            f"{type(transforms).__name__}")
    if transforms.time_aug or transforms.basepoint:
        raise ValueError(
            "repro.Path supports lead_lag only: time_aug renormalises every "
            "increment whenever the path grows (the [t0, t1] grid spans the "
            "whole path) and basepoint belongs to the full path, not its "
            "intervals — incompatible with an incremental prefix store.  "
            "Add a physical time channel to the data instead "
            "(docs/api/public.md, 'Streaming paths & serving')")
    return transforms


# ---------------------------------------------------------------------------
# jitted kernels (module-level so every Path instance shares one trace cache)
# ---------------------------------------------------------------------------

def _gather(store: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows of a (..., M, S) store at positions ``idx``.

    ``idx`` is (n,) int32 (shared across the batch) or (..., n) per-batch;
    returns (..., n, S).
    """
    idx = jnp.asarray(idx, jnp.int32)
    n = idx.shape[-1]
    tgt = (*store.shape[:-2], n, store.shape[-1])
    return jnp.take_along_axis(store, jnp.broadcast_to(idx[..., :, None], tgt),
                               axis=-2)


@functools.partial(jax.jit, static_argnames=("d", "depth"))
def _interval_kernel(prefix: jax.Array, inv_prefix: jax.Array,
                     ql: jax.Array, qr: jax.Array, *, d: int, depth: int
                     ) -> jax.Array:
    """Signatures of the intervals [ql, qr) of transformed increments.

    ``ql`` / ``qr`` are (n,) int32 window bounds in *transformed-step*
    coordinates; one vectorised Chen combine of the stored inverse
    prefixes with the stored prefixes — the only data touched is 2n rows
    of the stores, whatever the window sizes.
    """
    _TRACE_COUNTS["query"] += 1
    record_combines(ql.shape[-1])
    q_right = _gather(prefix, qr - 1)
    inv_left = _gather(inv_prefix, jnp.maximum(ql - 1, 0))
    inv_left = jnp.where((ql > 0)[..., None], inv_left,
                         jnp.zeros((), inv_left.dtype))
    with jax.named_scope("repro.stream.combine"):
        return ta.chen(inv_left, q_right, d, depth)


@functools.partial(jax.jit, static_argnames=("depth", "lead_lag"))
def _build_kernel(points: jax.Array, *, depth: int, lead_lag: bool
                  ) -> Tuple[jax.Array, jax.Array]:
    """Prefix store of an edge-padded point buffer: (Q_1..Q_M, inverses).

    ``points`` is (..., C, d) with the tail edge-padded (repeated last
    point), so padded increments are exactly zero — Horner no-ops — and
    the prefix stream simply repeats the true tip across the padding.
    """
    _TRACE_COUNTS["build"] += 1
    z = points[..., 1:, :] - points[..., :-1, :]
    z = tf.transform_increments(z, False, lead_lag)
    prefix = _signature_stream_from_increments(z, depth)
    inv = ta.sig_inverse(prefix, z.shape[-1], depth)
    return prefix, inv


@functools.partial(jax.jit, static_argnames=("depth", "lead_lag"))
def _update_kernel(points: jax.Array, prefix: jax.Array,
                   inv_prefix: jax.Array, length: jax.Array,
                   chunk: jax.Array, k: jax.Array, *,
                   depth: int, lead_lag: bool):
    """Append an edge-padded chunk: scan the chunk, Chen onto the tip.

    Shapes: ``points`` (..., C, d), ``chunk`` (..., kc, d) with kc ≤ C,
    ``length``/``k`` broadcastable int32 — the true point count so far and
    the true size of this chunk (``k = 0`` makes the whole call a no-op,
    which is what the serving loop's group padding relies on).  The only
    scan is over the kc-row chunk; the stored prefixes are extended by one
    batched Chen combine — never re-read, never re-scanned.
    """
    _TRACE_COUNTS["update"] += 1
    f = 2 if lead_lag else 1
    C = points.shape[-2]
    kc = chunk.shape[-2]
    M = prefix.shape[-2]
    length = jnp.asarray(length, jnp.int32)
    k = jnp.asarray(k, jnp.int32)

    # raw chunk increments, anchored at the current tip; rows at or past
    # the true chunk size are masked to zero (edge padding already makes
    # them zero for real chunks; the mask also covers k = 0 no-op calls)
    last = jnp.take_along_axis(
        points, (length - 1)[..., None, None]
        * jnp.ones((1, points.shape[-1]), jnp.int32), axis=-2)
    z = jnp.diff(jnp.concatenate([last, chunk], axis=-2), axis=-2)
    valid = jnp.arange(kc) < k[..., None]
    z = jnp.where(valid[..., None], z, jnp.zeros((), z.dtype))
    z = tf.transform_increments(z, False, lead_lag)
    d_t = z.shape[-1]
    mc = z.shape[-2]

    # O(chunk): prefix signatures of the chunk alone, and their inverses
    s_chunk = _signature_stream_from_increments(z, depth)
    inv_chunk = ta.sig_inverse(s_chunk, d_t, depth)

    # O(1) per new step: splice onto the stored tip by Chen's identity
    m = f * (length - 1)                                   # steps so far
    q_m = jnp.take_along_axis(
        prefix, (m - 1)[..., None, None]
        * jnp.ones((1, prefix.shape[-1]), jnp.int32), axis=-2)
    inv_q_m = jnp.take_along_axis(
        inv_prefix, (m - 1)[..., None, None]
        * jnp.ones((1, prefix.shape[-1]), jnp.int32), axis=-2)
    q_m = jnp.broadcast_to(q_m, s_chunk.shape)
    inv_q_m = jnp.broadcast_to(inv_q_m, s_chunk.shape)
    with jax.named_scope("repro.stream.combine"):
        new_q = ta.chen(q_m, s_chunk, d_t, depth)
        new_inv = ta.chen(inv_chunk, inv_q_m, d_t, depth)  # (ab)⁻¹ = b⁻¹a⁻¹
    record_combines(2 * mc)

    # scatter the mc new prefixes at offset m, the chunk at offset length
    idx = jnp.arange(M)
    src = idx - m[..., None]                               # (..., M)
    on = (src >= 0) & (src < mc)
    gathered_q = jnp.take_along_axis(
        new_q, jnp.clip(src, 0, mc - 1)[..., None], axis=-2)
    gathered_i = jnp.take_along_axis(
        new_inv, jnp.clip(src, 0, mc - 1)[..., None], axis=-2)
    prefix = jnp.where(on[..., None], gathered_q, prefix)
    inv_prefix = jnp.where(on[..., None], gathered_i, inv_prefix)

    pidx = jnp.arange(C)
    psrc = pidx - length[..., None]
    pon = (psrc >= 0) & (psrc < kc)
    gathered_p = jnp.take_along_axis(
        chunk, jnp.clip(psrc, 0, kc - 1)[..., None], axis=-2)
    points = jnp.where(pon[..., None], gathered_p, points)
    return points, prefix, inv_prefix, length + k


@functools.partial(jax.jit, static_argnames=("C", "M", "f", "d", "depth"))
def _evict_kernel(points: jax.Array, prefix: jax.Array,
                  inv_prefix: jax.Array, length: jax.Array, e: jax.Array, *,
                  C: int, M: int, f: int, d: int, depth: int):
    """Drop the first ``e`` points by a group-inverse splice — no re-scan.

    The evicted prefix ``Q_{f·e}`` is a pivot: every surviving prefix is
    rebased as ``Q'_k = Q_{f·e}⁻¹ ⊗ Q_{f·e+k}`` (and its inverse as
    ``Q'⁻¹_k = Q_{f·e+k}⁻¹ ⊗ Q_{f·e}``) — two *batched* Chen combines over
    the gathered survivor rows, exactly the group identity interval
    queries use.  No increment is ever re-folded: the only scan-shaped
    work is the gather.  ``C``/``M`` are the (static) shrunken point /
    store capacities; gathers clip at the true tip so the tail padding
    repeats it, matching ``_build_kernel``'s edge-pad semantics.
    """
    _TRACE_COUNTS["evict"] += 1
    e = jnp.asarray(e, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    pidx = jnp.clip(e + jnp.arange(C, dtype=jnp.int32), 0, length - 1)
    new_points = _gather(points, pidx)
    t = f * e                                      # transformed pivot step
    sidx = jnp.clip(t + jnp.arange(M, dtype=jnp.int32), 0,
                    f * (length - 1) - 1)
    q = _gather(prefix, sidx)
    iq = _gather(inv_prefix, sidx)
    piv_q = jnp.broadcast_to(_gather(prefix, (t - 1)[None]), q.shape)
    piv_i = jnp.broadcast_to(_gather(inv_prefix, (t - 1)[None]), q.shape)
    with jax.named_scope("repro.stream.combine"):
        new_prefix = ta.chen(piv_i, q, d, depth)
        new_inv = ta.chen(iq, piv_q, d, depth)
    record_combines(2 * M)
    return new_points, new_prefix, new_inv, length - e


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RollingConfig:
    """Rolling-window query plan: ``window`` points every ``stride`` points.

    Static metadata (window/stride set output shapes).  ``window`` counts
    *points*, so the smallest meaningful window is 2 (one increment).
    """

    window: int
    stride: int = 1

    def __post_init__(self):
        for name, lo in (("window", 2), ("stride", 1)):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < lo:
                raise ValueError(
                    f"RollingConfig.{name} must be a Python int >= {lo}, "
                    f"got {v!r}")

    def num_windows(self, length: int) -> int:
        """How many full windows fit in a ``length``-point path."""
        if length < self.window:
            return 0
        return (length - self.window) // self.stride + 1


_pytree_dataclass(RollingConfig, data_fields=(),
                  meta_fields=("window", "stride"))


# ---------------------------------------------------------------------------
# Path
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Path:
    """A (possibly growing) path with precomputed per-prefix signatures.

    Construct with :meth:`from_points`; every instance is immutable —
    :meth:`update` returns a *new* ``Path`` sharing the (functionally
    updated) buffers.  A frozen pytree: instances pass through ``jax.jit``
    / ``jax.grad`` boundaries, and gradients flow from any query back to
    the stored prefixes and on to the original points.

    Data leaves: ``points`` (..., C, d) the bucketed point buffer,
    ``prefix`` / ``inv_prefix`` (..., M, sig_dim) the per-prefix signatures
    ``Q_m = S(x over the first m transformed increments)`` and their group
    inverses, ``length`` the true point count (int32 scalar — all paths in
    a batch share it; buffer content past it is unspecified).  Static
    metadata: ``depth``, the (lead-lag-only) ``transforms`` and the
    optional ``retention`` cap (:meth:`evict` runs automatically inside
    :meth:`update` whenever the length would exceed it).
    """

    points: jax.Array
    prefix: jax.Array
    inv_prefix: jax.Array
    length: jax.Array
    depth: int
    transforms: TransformPipeline = TransformPipeline()
    retention: Optional[int] = None

    # -- construction -------------------------------------------------------

    @classmethod
    def from_points(cls, points: jax.Array, depth: int, *,
                    transforms: Optional[TransformPipeline] = None,
                    retention: Optional[int] = None) -> "Path":
        """Build the prefix store for ``points`` (..., L, d), L ≥ 2.

        One O(L) Horner stream scan (the same scan as
        ``repro.signature(..., stream=True)``), padded up to the
        power-of-two capacity bucket so nearby lengths share a jit trace.

        ``retention=n`` caps the stored history at ``n`` points: every
        :meth:`update` that would exceed it auto-:meth:`evict`\\ s the
        oldest points first, so an endless stream runs in O(n) memory with
        zero re-scans.  The initial points must already fit the cap.
        """
        transforms = _check_pipeline(transforms)
        if retention is not None and (
                not isinstance(retention, int) or isinstance(retention, bool)
                or retention < 2):
            raise ValueError(
                f"retention must be None or a Python int >= 2 (a path keeps "
                f"at least one increment), got {retention!r}")
        points = jnp.asarray(points)
        if points.ndim < 2:
            raise ValueError(
                f"Path.from_points expects (..., L, d) points, got shape "
                f"{points.shape}")
        L = points.shape[-2]
        if L < 2:
            raise ValueError(
                f"Path needs at least 2 points (one increment), got L={L}")
        if retention is not None and L > retention:
            raise ValueError(
                f"initial points ({L}) exceed retention={retention}; slice "
                f"the history yourself — eviction applies to updates")
        if not (isinstance(depth, int) and not isinstance(depth, bool)
                and depth >= 1):
            raise ValueError(f"depth must be a Python int >= 1, got {depth!r}")
        C = tf.bucket_length(L)
        if C > L:
            width = [(0, 0)] * points.ndim
            width[-2] = (0, C - L)
            points = jnp.pad(points, width, mode="edge")
        prefix, inv = _build_kernel(points, depth=depth,
                                    lead_lag=transforms.lead_lag)
        return cls(points=points, prefix=prefix, inv_prefix=inv,
                   length=jnp.asarray(L, jnp.int32), depth=depth,
                   transforms=transforms, retention=retention)

    # -- shape facts --------------------------------------------------------

    @property
    def d(self) -> int:
        """Raw channel count of the stored points."""
        return self.points.shape[-1]

    @property
    def transformed_d(self) -> int:
        """Channel count the signatures are computed over."""
        return self.transforms.transformed_dim(self.d)

    @property
    def capacity(self) -> int:
        """Point capacity of the buffers (the current power-of-two bucket)."""
        return self.points.shape[-2]

    @property
    def sig_dim(self) -> int:
        """Flat signature width of every query result."""
        return self.prefix.shape[-1]

    @property
    def _f(self) -> int:
        """Transformed increments per raw increment (2 under lead-lag)."""
        return 2 if self.transforms.lead_lag else 1

    def __len__(self) -> int:
        return int(self.length)

    # -- queries ------------------------------------------------------------

    def _concrete_length(self, what: str) -> int:
        try:
            return int(self.length)
        except jax.errors.ConcretizationTypeError:
            raise ValueError(
                f"Path.{what} needs a concrete Path (its length drives "
                f"Python-level shape decisions); call it outside jax.jit — "
                f"interval queries with explicit (i, j) trace fine") from None

    def _check_interval(self, i, j):
        if j is None:
            j = self.length
        conc_len = None
        try:
            conc_len = int(self.length)
        except jax.errors.ConcretizationTypeError:
            pass
        if isinstance(i, int) and isinstance(j, int):
            if i < 0 or j - i < 2 or (conc_len is not None and j > conc_len):
                raise ValueError(
                    f"interval [{i}, {j}) must satisfy 0 <= i <= j-2 and "
                    f"j <= length ({conc_len}) — a signature needs at least "
                    f"one increment")
        return i, j

    def signature(self, i: int = 0, j: Optional[int] = None) -> jax.Array:
        """Signature of ``points[i:j]`` — one Chen combine, no re-scan.

        ``j`` defaults to the current length (the full-path signature).
        ``i == 0`` (a concrete zero) returns the stored prefix directly —
        bitwise the reference Horner scan of ``points[:j]``.  General
        intervals combine the precomputed inverse prefix with the prefix:
        exact group arithmetic, a few ULPs from a fresh recompute.
        """
        i, j = self._check_interval(i, j)
        f = self._f
        qr = f * (jnp.asarray(j, jnp.int32) - 1)
        if isinstance(i, int) and i == 0:
            return _gather(self.prefix, (qr - 1)[None])[..., 0, :]
        ql = f * jnp.asarray(i, jnp.int32)
        return _interval_kernel(self.prefix, self.inv_prefix, ql[None],
                                qr[None], d=self.transformed_d,
                                depth=self.depth)[..., 0, :]

    def logsignature(self, i: int = 0, j: Optional[int] = None, *,
                     mode: str = "lyndon") -> jax.Array:
        """Log-signature of ``points[i:j]`` via the Lyndon machinery.

        The interval signature (one Chen combine) is pushed through
        :func:`repro.core.tensoralg.tensor_log` and compressed to the
        requested basis — still no re-scan.
        """
        if mode not in _LOGSIG_MODES:
            raise ValueError(
                f"mode must be one of {_LOGSIG_MODES}, got {mode!r}")
        flat = ta.tensor_log(self.signature(i, j), self.transformed_d,
                             self.depth)
        if mode == "expand":
            return flat
        return lyndon.compress(flat, self.transformed_d, self.depth, mode)

    def rolling(self, window: Union[int, RollingConfig], *,
                stride: int = 1) -> jax.Array:
        """Signatures of every full ``window``-point window, batched.

        ``window`` may be a :class:`RollingConfig` (whose stride wins).
        Returns (..., n_windows, sig_dim) — window ``w`` starts at point
        ``w·stride``.  One *vectorised* Chen combine over all windows; the
        prefix store is gathered, never re-scanned.  Needs a concrete
        ``Path`` (the window count is a Python-level shape).
        """
        cfg = window if isinstance(window, RollingConfig) \
            else RollingConfig(window=window, stride=stride)
        L = self._concrete_length("rolling")
        n = cfg.num_windows(L)
        if n < 1:
            raise ValueError(
                f"no full {cfg.window}-point window fits in a {L}-point "
                f"path")
        f = self._f
        # pad the window count to a power-of-two bucket (repeating the last
        # window) so a growing path revisits one warm query trace per bucket
        nb = tf.bucket_length(n, minimum=1)
        w = jnp.minimum(jnp.arange(nb, dtype=jnp.int32), n - 1)
        starts = w * cfg.stride
        out = _interval_kernel(
            self.prefix, self.inv_prefix, f * starts,
            f * (starts + cfg.window - 1), d=self.transformed_d,
            depth=self.depth)
        return out[..., :n, :]

    # -- incremental extension ----------------------------------------------

    def update(self, new_points: jax.Array) -> "Path":
        """Extend the path with ``new_points`` (..., k, d), k ≥ 1.

        O(chunk) work: the new increments are scanned (the chunk is padded
        to its own power-of-two bucket so steady-state appends of similar
        sizes share one warm jit trace) and Chen-combined onto the stored
        tip — the existing prefixes are never re-read or re-scanned.  When
        the buffers run out of capacity they grow to the next power-of-two
        bucket (an expected, bounded retrace).  Needs a concrete ``Path``.
        """
        new_points = jnp.asarray(new_points)
        if new_points.ndim < 2 or new_points.shape[-1] != self.d:
            raise ValueError(
                f"update expects (..., k, {self.d}) new points, got shape "
                f"{new_points.shape}")
        k = new_points.shape[-2]
        if k < 1:
            raise ValueError("update needs at least one new point")
        L = self._concrete_length("update")
        kc = tf.bucket_length(k, minimum=1)
        if kc > k:
            width = [(0, 0)] * new_points.ndim
            width[-2] = (0, kc - k)
            new_points = jnp.pad(new_points, width, mode="edge")
        points, prefix, inv_prefix = self.points, self.prefix, self.inv_prefix
        need = L + kc
        if need > self.capacity:
            grow = tf.bucket_length(need) - self.capacity
            pw = [(0, 0)] * points.ndim
            pw[-2] = (0, grow)
            points = jnp.pad(points, pw, mode="edge")
            sw = [(0, 0)] * prefix.ndim
            sw[-2] = (0, self._f * grow)
            prefix = jnp.pad(prefix, sw, mode="edge")
            inv_prefix = jnp.pad(inv_prefix, sw, mode="edge")
        points, prefix, inv_prefix, length = _update_kernel(
            points, prefix, inv_prefix, self.length, new_points,
            jnp.asarray(k, jnp.int32), depth=self.depth,
            lead_lag=self.transforms.lead_lag)
        out = dataclasses.replace(
            self, points=points, prefix=prefix, inv_prefix=inv_prefix,
            length=length)
        if self.retention is not None and L + k > self.retention:
            out = out.evict(before=L + k - self.retention)
        return out

    # -- eviction ------------------------------------------------------------

    def evict(self, *, before: int) -> "Path":
        """Drop ``points[:before]`` — O(remaining) group splices, no re-scan.

        The surviving prefixes are rebased through the evicted tip's group
        inverse (``Q'_k = Q_{f·e}⁻¹ ⊗ Q_{f·e+k}``, one *batched* Chen
        combine for the prefixes and one for their inverses), so not a
        single increment is re-folded — ``repro.core.dispatch.
        count_scan_steps`` reads zero across any eviction.  Queries on the
        new path are in its own coordinates (old point ``before + i`` is
        new point ``i``) and agree with a fresh build to a few ULPs.
        Buffers shrink to the new length's power-of-two bucket, releasing
        memory; at least 2 points (one increment) must survive.  Needs a
        concrete ``Path``.
        """
        if not isinstance(before, int) or isinstance(before, bool) \
                or before < 0:
            raise ValueError(
                f"evict(before=) must be a Python int >= 0, got {before!r}")
        L = self._concrete_length("evict")
        if before == 0:
            return self
        if before > L - 2:
            raise ValueError(
                f"evict(before={before}) would leave fewer than 2 of the "
                f"{L} points — a path keeps at least one increment")
        newL = L - before
        f = self._f
        C = tf.bucket_length(newL)
        points, prefix, inv_prefix, length = _evict_kernel(
            self.points, self.prefix, self.inv_prefix, self.length,
            jnp.asarray(before, jnp.int32), C=C, M=f * (C - 1), f=f,
            d=self.transformed_d, depth=self.depth)
        return dataclasses.replace(
            self, points=points, prefix=prefix, inv_prefix=inv_prefix,
            length=length)


_pytree_dataclass(Path,
                  data_fields=("points", "prefix", "inv_prefix", "length"),
                  meta_fields=("depth", "transforms", "retention"))


# ---------------------------------------------------------------------------
# coalesced (admission-batched) updates — the serving loop's hot path
# ---------------------------------------------------------------------------

def coalesced_update(paths: Sequence[Path],
                     chunks: Sequence[jax.Array]) -> List[Path]:
    """Apply one append per path as a SINGLE batched kernel call.

    All paths must share ``(capacity, d, depth, transforms)`` and be
    unbatched (``points`` of shape (C, d)) — the serving loop groups by
    exactly that key.  Chunks are padded to the group's common chunk
    bucket, paths that would overflow are grown first (outside the batch),
    and the group itself is padded to a power-of-two size with no-op
    (``k = 0``) members so the number of distinct traces stays bounded in
    the stream count.  Returns the updated paths, in order.
    """
    if len(paths) != len(chunks):
        raise ValueError(
            f"coalesced_update got {len(paths)} paths but {len(chunks)} "
            f"chunks")
    if not paths:
        return []
    p0 = paths[0]
    if p0.points.ndim != 2:
        raise ValueError(
            "coalesced_update expects unbatched paths ((C, d) points); "
            "batch them through the group axis instead")
    key0 = (p0.capacity, p0.d, p0.depth, p0.transforms)
    ks = [jnp.asarray(c).shape[-2] for c in chunks]
    kc = tf.bucket_length(max(ks), minimum=1)

    prepared_paths: List[Path] = []
    prepared_chunks: List[jax.Array] = []
    for p, c, k in zip(paths, chunks, ks):
        c = jnp.asarray(c)
        if c.ndim != 2 or c.shape[-1] != p0.d:
            raise ValueError(
                f"chunk shape {c.shape} does not match (k, {p0.d})")
        if (p.capacity, p.d, p.depth, p.transforms) != key0:
            raise ValueError(
                "coalesced_update needs a homogeneous group "
                "(capacity, d, depth, transforms); group before calling")
        if kc > k:
            c = jnp.pad(c, ((0, kc - k), (0, 0)), mode="edge")
        L = p._concrete_length("update")
        if L + kc > p.capacity:
            raise ValueError(
                f"path at length {L} cannot take a {kc}-bucket chunk within "
                f"capacity {p.capacity}; grow it first (Path.update does "
                f"this automatically)")
        prepared_paths.append(p)
        prepared_chunks.append(c)

    G = len(prepared_paths)
    Gb = tf.bucket_length(G, minimum=1)
    pad = Gb - G
    stack = lambda xs: jnp.stack(list(xs) + [xs[0]] * pad)  # noqa: E731
    points = stack([p.points for p in prepared_paths])
    prefix = stack([p.prefix for p in prepared_paths])
    inv = stack([p.inv_prefix for p in prepared_paths])
    length = stack([p.length for p in prepared_paths])
    chunk = stack(prepared_chunks)
    kvec = jnp.asarray(ks + [0] * pad, jnp.int32)          # pads are no-ops

    points, prefix, inv, length = _update_kernel(
        points, prefix, inv, length, chunk, kvec, depth=p0.depth,
        lead_lag=p0.transforms.lead_lag)
    out: List[Path] = []
    for g, p in enumerate(prepared_paths):
        new = dataclasses.replace(p, points=points[g], prefix=prefix[g],
                                  inv_prefix=inv[g], length=length[g])
        if p.retention is not None and int(length[g]) > p.retention:
            new = new.evict(before=int(length[g]) - p.retention)
        out.append(new)
    return out

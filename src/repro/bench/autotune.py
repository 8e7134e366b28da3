"""Measurement-driven backend selection and launch-parameter search.

:func:`tune` benchmarks every backend from :mod:`repro.core.dispatch` that
can serve an op on the current platform (TPU-only backends are skipped off
TPU — interpret mode measures nothing meaningful), then sweeps a small
bounded set of :class:`repro.core.config.LaunchConfig` candidates for the
winning backend, and persists both in an on-disk JSON cache keyed by
``(op, platform, dtype, shape-bucket)``.  :func:`lookup` /
:func:`lookup_launch` are the read side: :func:`repro.core.dispatch.resolve`
and :func:`repro.core.dispatch.resolve_launch` consult them when resolving
``"auto"`` / an unset ``launch=`` and fall back to the static shape
heuristics / library-default launch parameters whenever the answer is
``None`` (cache cold, autotuning disabled, a stale/corrupt cache file, or
— launch parameters only — a cache tuned on a different machine).

Design points:

* **Shape buckets** — batch/length-like dimensions of the key shape are
  rounded up to the next power of two, so nearby problem sizes share one
  cache entry and one tuning run; channel count and truncation depth stay
  exact (cost is exponential in depth — bucketing it would tune a
  different problem).  :func:`tune` measures at the *bucketed* shape, so
  the entry is honest for the whole bucket.
* **Lookups never time anything** — a warm cache costs one (memoised) JSON
  read per process; ``tune`` on a warm key returns the cached winner
  without running a single measurement unless ``force=True``.
* **Fail open** — a corrupted cache file, an unknown schema version, or an
  entry naming a backend that no longer exists are all treated as a cold
  cache, never an error.
* **Launch winners are machine-scoped** — tile shapes that win on one
  box (VMEM budget, cache sizes, core count) can lose on another, so every
  tuned entry is stamped with :func:`repro.bench.timer.machine_key`
  (platform | device kind | device memory) and :func:`lookup_launch`
  drops the launch parameters (never the whole entry path — fail-open to
  the library defaults) when the stamp does not match the current machine.
  Launch parameters never change the math, only the speed, so a wrong
  fallback is a performance question, not a correctness one.

Environment variables:

``REPRO_DISABLE_AUTOTUNE=1``
    Disables the cache entirely: ``lookup`` returns ``None`` (so ``auto``
    uses the static heuristics) and ``tune`` still measures when called
    explicitly but does not persist.
``REPRO_AUTOTUNE_CACHE=/path/to/cache.json``
    Overrides the cache location (default ``~/.cache/repro/autotune.json``).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dispatch
from . import timer

SCHEMA = 3  # v3: scheme-frontier entries ("|scheme" keys, "scheme_frontier");
#             v2 added "launch" / "launch_timings" / "machine".  Old files
#             fail open (treated as cold — _entries checks the version), so
#             a schema bump costs one re-tune, never an error.

ENV_DISABLE = "REPRO_DISABLE_AUTOTUNE"
ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_DEFAULT_CACHE = os.path.join("~", ".cache", "repro", "autotune.json")

#: batch size tuning runners use for ops whose key shape carries no batch dim
_TUNE_BATCH = 8


def enabled() -> bool:
    """Autotuning is on unless REPRO_DISABLE_AUTOTUNE is truthy."""
    return os.environ.get(ENV_DISABLE, "").strip().lower() not in (
        "1", "true", "yes", "on")


def cache_path() -> str:
    return os.path.expanduser(os.environ.get(ENV_CACHE) or _DEFAULT_CACHE)


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def bucket(shape) -> Tuple[int, ...]:
    """Round every dimension up to the next power of two (min 1)."""
    return tuple(1 if s <= 1 else 1 << (int(s) - 1).bit_length()
                 for s in shape)


#: how many leading dims of each op's key shape are batch/length-like and
#: safe to bucket to powers of two.  The trailing dims (channel count d,
#: truncation depth) stay EXACT: cost is exponential in depth and
#: polynomial of high degree in d, so bucketing them would tune a wildly
#: different problem (e.g. depth 5 -> 8 is ~d^3 more work).
_BUCKETED_DIMS = {"signature": 1, "logsignature": 1, "sigkernel": 2,
                  "gram": 4}


def key_shape(op: str, shape) -> Tuple[int, ...]:
    """Canonical (bucketed) key shape for ``op``; tuning measures this.

    The per-op meaning of ``shape`` (what the dispatch call sites pass):

    * ``signature`` / ``logsignature``: ``(L, d, depth)`` — increments per
      path, *transformed* channel count, truncation level;
    * ``sigkernel``: ``(nx, ny, d)`` — the *refined* PDE grid
      ``(Lx<<lam1, Ly<<lam2)`` and transformed channel count;
    * ``gram``: ``(Bx, By, nx, ny, d)``.
    """
    if op not in dispatch.OPS:
        raise ValueError(f"unknown op {op!r}; known: {dispatch.OPS}")
    n = _BUCKETED_DIMS[op]
    return bucket(shape[:n]) + tuple(int(s) for s in shape[n:])


def cache_key(op: str, shape, dtype="float32", *, ragged: bool = False,
              approx: bool = False, scheme: bool = False) -> str:
    """``op|platform|dtype|b1xb2x...[|ragged][|approx|scheme]`` on-disk key.

    ``ragged=True`` (variable-length ``lengths=`` workloads) is part of the
    key: the same padded shape does very different work when most of it is
    masked, so a dense winner must never shadow the ragged measurement and
    vice versa.

    ``approx=True`` keys the accuracy-vs-speed *frontier* entry
    (:func:`tune_frontier`) for the same problem.  Frontier entries answer
    a different question than exact-winner entries ("cheapest within a
    caller error budget" vs "fastest exact"), so they live under their own
    suffix and neither lookup can ever shadow the other.

    ``scheme=True`` keys the *discretisation* frontier
    (:func:`tune_scheme_frontier`): measured (scheme, coarsen,
    interior_dtype) points of the exact engine.  Same separation argument —
    it answers "cheapest exact discretisation within a budget", a third
    question with its own suffix.  ``approx`` and ``scheme`` are mutually
    exclusive.
    """
    if approx and scheme:
        raise ValueError("cache_key: approx and scheme are separate "
                         "frontiers — pass at most one")
    dims = "x".join(str(s) for s in key_shape(op, shape))
    key = f"{op}|{jax.default_backend()}|{jnp.dtype(dtype).name}|{dims}"
    if ragged:
        key += "|ragged"
    if approx:
        key += "|approx"
    if scheme:
        key += "|scheme"
    return key


# ---------------------------------------------------------------------------
# cache I/O (memoised by mtime; fail-open on anything unexpected)
# ---------------------------------------------------------------------------

_memo: Dict[str, Tuple[Optional[float], Dict]] = {}


def invalidate_memo() -> None:
    """Drop the in-process cache-file memo (tests, post-write refresh)."""
    _memo.clear()


def _entries(path: str) -> Dict[str, dict]:
    """Entries dict from ``path``; {} for missing/corrupt/stale-schema."""
    try:
        mtime: Optional[float] = os.stat(path).st_mtime
    except OSError:
        mtime = None
    hit = _memo.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    entries: Dict[str, dict] = {}
    if mtime is not None:
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
            if (isinstance(doc, dict) and doc.get("schema") == SCHEMA
                    and isinstance(doc.get("entries"), dict)):
                entries = doc["entries"]
        except (OSError, ValueError):
            entries = {}
    _memo[path] = (mtime, entries)
    return entries


def _store(key: str, entry: dict) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    entries = dict(_entries(path))
    entries[key] = entry
    doc = {"schema": SCHEMA, "entries": entries}
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".autotune-")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    invalidate_memo()


def cache_entry(op: str, shape, dtype="float32", *, ragged: bool = False,
                approx: bool = False, scheme: bool = False) -> Optional[dict]:
    """Full cached record (backend, timings, tuned_at) or None.

    ``approx=True`` reads the feature-map frontier entry
    (:func:`tune_frontier`), ``scheme=True`` the discretisation frontier
    (:func:`tune_scheme_frontier`), instead of the exact-winner entry.
    """
    if not enabled():
        return None
    entry = _entries(cache_path()).get(
        cache_key(op, shape, dtype, ragged=ragged, approx=approx,
                  scheme=scheme))
    return entry if isinstance(entry, dict) else None


def lookup(op: str, shape, dtype="float32", *,
           ragged: bool = False) -> Optional[str]:
    """Cached winning backend name for this key, or None (cold/disabled).

    Never runs a measurement.  The caller (``dispatch.resolve``) validates
    the name against the live registry, so stale entries degrade to the
    static heuristics rather than erroring.
    """
    entry = cache_entry(op, shape, dtype, ragged=ragged)
    if entry is None:
        return None
    name = entry.get("backend")
    return name if isinstance(name, str) else None


def lookup_launch(op: str, shape, dtype="float32", *, ragged: bool = False):
    """Cached winning :class:`LaunchConfig` for this key, or None.

    Never measures.  Returns ``None`` — the library defaults — when the
    cache is cold/disabled, when the entry predates launch sweeps (no
    ``"launch"`` field or an all-default one), when the stored dict fails
    :meth:`LaunchConfig.from_dict` validation, or when the entry's
    ``"machine"`` stamp names a different machine (tile winners do not
    travel).  Entries without a ``"machine"`` stamp are accepted: they can
    only come from a hand-written cache, and rejecting them would make the
    stamp impossible to test.
    """
    from repro.core.config import LaunchConfig
    entry = cache_entry(op, shape, dtype, ragged=ragged)
    if entry is None:
        return None
    raw = entry.get("launch")
    if not isinstance(raw, dict) or not raw:
        return None
    stamp = entry.get("machine")
    if isinstance(stamp, str) and stamp != timer.machine_key():
        return None  # tuned on another box: fail open to defaults
    try:
        launch = LaunchConfig.from_dict(raw)
    except (ValueError, TypeError):
        return None
    return None if launch.is_default else launch


# ---------------------------------------------------------------------------
# tuning
# ---------------------------------------------------------------------------

def candidates(op: str) -> Tuple[str, ...]:
    """Backends worth measuring for ``op`` on the current platform.

    Approximate feature-map backends are excluded: the exact-winner sweep
    compares like-for-like results; approximations compete on the separate
    accuracy-vs-speed frontier (:func:`tune_frontier` / ``approx=True``
    cache keys).
    """
    names = tuple(n for n in dispatch.backends_for(op)
                  if not dispatch.get(n).approximate)
    if not dispatch.on_tpu():
        names = tuple(n for n in names if not dispatch.get(n).needs_tpu)
    return names or tuple(n for n in dispatch.backends_for(op)
                          if not dispatch.get(n).approximate)


def launch_candidates(op: str, backend: str) -> Tuple:
    """Bounded :class:`LaunchConfig` sweep for ``op`` on ``backend``.

    The first candidate is always the all-default config (today's module
    constants), so a sweep can only ever match or beat the untuned
    library.  The lists are deliberately tiny — a handful of power-of-two
    tile shapes per knob — because the sweep runs per cache key and every
    candidate costs ``warmup + repeats`` full op executions.  Knobs that
    the backend ignores are not swept (the reference scan has no tiles).
    """
    from repro.core.config import LaunchConfig
    cands = [LaunchConfig()]
    if op in ("signature", "logsignature"):
        if backend == "pallas":
            # a batch tile under 128 lanes saves no VMEM, so only the
            # length block is swept
            cands += [LaunchConfig(sig_lb=128)]
    elif op == "sigkernel":
        if backend == "pallas":
            cands += [LaunchConfig(pde_strip=64), LaunchConfig(pde_strip=32)]
        elif backend == "antidiag":
            cands += [LaunchConfig(band_chunk=8), LaunchConfig(band_chunk=32)]
    elif op == "gram":
        if backend in ("pallas", "pallas_fused"):
            cands += [LaunchConfig(pde_strip=64),
                      LaunchConfig(gram_row_block=8),
                      LaunchConfig(gram_row_block=32)]
        else:
            cands += [LaunchConfig(gram_row_block=8),
                      LaunchConfig(gram_row_block=32)]
    return tuple(cands)


def _ragged_lengths(batch: int, points: int):
    """Deterministic length spread for ragged tuning runs: [~P/2, P]."""
    import numpy as np
    lo = max(2, points // 2)
    return jnp.asarray(np.linspace(lo, points, batch).round().astype("int32"))


def _ragged_points(n: int) -> int:
    """Point count for a ragged runner targeting a length-like key dim ``n``.

    Ragged call sites compute their cache-key shape *after*
    ``pad_ragged`` bucketing, so the key's length dims are already padded
    (power-of-two) sizes.  The runner must therefore build a batch whose
    padded length axis equals the key dim — ``bucket_length(n)`` points, a
    no-op re-pad — rather than the dense runner's ``n + 1`` points, which
    would re-bucket to ~2n and measure twice the workload the key denotes.
    """
    from repro.core.transforms import bucket_length
    return bucket_length(n)


def _runner(op: str, shape, dtype, backend: str, ragged: bool = False,
            launch=None):
    """Zero-arg jitted callable exercising ``op`` at the bucketed shape.

    With ``ragged=True`` the runner passes a representative ``lengths=``
    spread (half- to full-length) so the measurement reflects the masked
    variable-length workload the key denotes.  ``launch`` (a
    :class:`LaunchConfig`) is forwarded verbatim so launch sweeps measure
    exactly what :func:`lookup_launch` will later apply.
    """
    from repro.core.gram import sigkernel_gram
    from repro.core.logsignature import logsignature
    from repro.core.signature import signature
    from repro.core.sigkernel import sigkernel

    key = jax.random.PRNGKey(0)
    if op in ("signature", "logsignature"):
        L, d, depth = shape
        pts = _ragged_points(max(L, 2)) if ragged else max(L, 2) + 1
        path = (jax.random.normal(key, (_TUNE_BATCH, pts, d))
                * 0.2).astype(dtype)
        lens = _ragged_lengths(_TUNE_BATCH, pts) if ragged else None
        fn = signature if op == "signature" else logsignature
        f = jax.jit(lambda p: fn(p, depth, backend=backend, lengths=lens,
                                 launch=launch))
        return lambda: f(path)
    if op == "sigkernel":
        nx, ny, d = shape
        px = _ragged_points(nx) if ragged else nx + 1
        py = _ragged_points(ny) if ragged else ny + 1
        x = (jax.random.normal(key, (_TUNE_BATCH, px, d)) * 0.1
             ).astype(dtype)
        y = (jax.random.normal(jax.random.PRNGKey(1),
                               (_TUNE_BATCH, py, d)) * 0.1).astype(dtype)
        lx = _ragged_lengths(_TUNE_BATCH, px) if ragged else None
        ly = _ragged_lengths(_TUNE_BATCH, py) if ragged else None
        f = jax.jit(lambda a, b: sigkernel(a, b, backend=backend,
                                           lengths_x=lx, lengths_y=ly,
                                           launch=launch))
        return lambda: f(x, y)
    if op == "gram":
        Bx, By, nx, ny, d = shape
        px = _ragged_points(nx) if ragged else nx + 1
        py = _ragged_points(ny) if ragged else ny + 1
        X = (jax.random.normal(key, (Bx, px, d)) * 0.1).astype(dtype)
        Y = (jax.random.normal(jax.random.PRNGKey(1), (By, py, d)) * 0.1
             ).astype(dtype)
        lx = _ragged_lengths(Bx, px) if ragged else None
        ly = _ragged_lengths(By, py) if ragged else None
        f = jax.jit(lambda a, b: sigkernel_gram(
            a, b, backend=backend, symmetric=False,
            lengths=lx, lengths_y=ly, launch=launch))
        return lambda: f(X, Y)
    raise ValueError(f"no tuning runner for op {op!r}")


def measure(op: str, shape, dtype="float32", *, repeats: int = 3,
            warmup: int = 1, ragged: bool = False) -> Dict[str, float]:
    """Steady-state seconds per call for every candidate backend."""
    shape = key_shape(op, shape)
    return {b: timer.bench(_runner(op, shape, dtype, b, ragged),
                           repeats=repeats, warmup=warmup)
            for b in candidates(op)}


def _launch_json_key(launch) -> str:
    """Stable string key for a launch candidate in ``launch_timings``."""
    return json.dumps(launch.to_dict(), sort_keys=True)


def measure_launch(op: str, shape, dtype, backend: str, *,
                   repeats: int = 3, warmup: int = 1,
                   ragged: bool = False) -> Dict:
    """Seconds per call for every launch candidate of the chosen backend.

    Keys are :class:`LaunchConfig` instances (hashable).  A candidate that
    fails to run — e.g. a tile shape the current kernel geometry rejects —
    is skipped, never raised: the sweep must fail open to the defaults.
    """
    shape = key_shape(op, shape)
    out = {}
    for cand in launch_candidates(op, backend):
        try:
            out[cand] = timer.bench(
                _runner(op, shape, dtype, backend, ragged, cand),
                repeats=repeats, warmup=warmup)
        except Exception:
            continue
    return out


def tune(op: str, shape, dtype="float32", *, repeats: int = 3,
         warmup: int = 1, force: bool = False, ragged: bool = False,
         sweep_launch: bool = True) -> str:
    """Measure candidates, persist the winner, return its name.

    A warm cache key returns the stored winner with **zero** timed runs
    unless ``force=True``.  With autotuning disabled the measurement still
    happens (this is an explicit call) but nothing is persisted.

    With ``sweep_launch=True`` (default) the winning backend's bounded
    :func:`launch_candidates` are also measured and the fastest
    :class:`LaunchConfig` is stored under the same key (``"launch"``),
    stamped with :func:`repro.bench.timer.machine_key` so it never travels
    to a different machine.  The all-default config is always a candidate,
    so a tuned entry is never slower than the untuned library *on the
    machine that tuned it*.
    """
    from repro.core.config import LaunchConfig
    if not force:
        cached = lookup(op, shape, dtype, ragged=ragged)
        if cached is not None and cached in candidates(op):
            return cached
    times = measure(op, shape, dtype, repeats=repeats, warmup=warmup,
                    ragged=ragged)
    winner = min(times, key=times.get)
    best_launch = LaunchConfig()
    launch_times: Dict = {}
    if sweep_launch:
        launch_times = measure_launch(op, shape, dtype, winner,
                                      repeats=repeats, warmup=warmup,
                                      ragged=ragged)
        if launch_times:
            best_launch = min(launch_times, key=launch_times.get)
    if enabled():
        _store(cache_key(op, shape, dtype, ragged=ragged), {
            "backend": winner,
            "timings": times,
            "launch": best_launch.to_dict(),
            "launch_timings": {_launch_json_key(c): t
                               for c, t in launch_times.items()},
            "machine": timer.machine_key(),
            "tuned_at": time.time(),
            "repeats": repeats,
        })
    return winner


# ---------------------------------------------------------------------------
# accuracy-vs-speed frontier (approximate feature-map backends)
# ---------------------------------------------------------------------------

#: default rank sweep for frontier tuning — a few octaves, because the RFF
#: error shrinks like 1/sqrt(rank): doubling twice per point covers the
#: useful budget range without turning the sweep into a benchmark itself
_FRONTIER_RANKS = (8, 32, 128)


def _frontier_data(shape, dtype, ragged: bool):
    """Deterministic Gram inputs at the bucketed key shape (cf. _runner)."""
    Bx, By, nx, ny, d = shape
    key = jax.random.PRNGKey(0)
    px = _ragged_points(nx) if ragged else nx + 1
    py = _ragged_points(ny) if ragged else ny + 1
    X = (jax.random.normal(key, (Bx, px, d)) * 0.1).astype(dtype)
    Y = (jax.random.normal(jax.random.PRNGKey(1), (By, py, d)) * 0.1
         ).astype(dtype)
    lx = _ragged_lengths(Bx, px) if ragged else None
    ly = _ragged_lengths(By, py) if ragged else None
    return X, Y, lx, ly


def tune_frontier(op: str, shape, dtype="float32", *, ranks=_FRONTIER_RANKS,
                  repeats: int = 3, warmup: int = 1, ragged: bool = False,
                  force: bool = False) -> dict:
    """Measure the method × rank accuracy-vs-speed frontier; persist it.

    ``op`` must be ``"gram"`` — the feature maps in
    :mod:`repro.core.features` approximate Gram inner products, nothing
    else.  For every approximate backend in the registry and every rank in
    ``ranks`` this measures steady-state seconds per call and the relative
    Frobenius error against the exact engine's Gram at the *bucketed* key
    shape, plus the exact engine's own wall clock as the bar every frontier
    point must beat.  The result is stored under the ``approx=True`` cache
    key (:func:`cache_key`), machine-stamped: the seconds — both the
    "beats exact" gate and the cheapest-point ordering — only mean anything
    on the box that measured them.

    A warm key returns the stored entry with zero measurements unless
    ``force=True``; with autotuning disabled the measurement still happens
    but nothing is persisted.  A (method, rank) point that fails to run is
    skipped, never raised — an absent point can only make
    :func:`lookup_budget` more conservative.
    """
    from repro.core import features as ft
    from repro.core.gram import sigkernel_gram
    if op != "gram":
        raise ValueError(
            f"frontier tuning only supports op='gram' (got {op!r}): the "
            "feature maps approximate Gram inner products only")
    shape = key_shape(op, shape)
    key = cache_key(op, shape, dtype, ragged=ragged, approx=True)
    if not force:
        entry = _entries(cache_path()).get(key)
        if isinstance(entry, dict) and isinstance(entry.get("frontier"),
                                                  list):
            return entry
    X, Y, lx, ly = _frontier_data(shape, dtype, ragged)
    exact_backend = dispatch.resolve("auto", op="gram", shape=shape,
                                     dtype=dtype, ragged=ragged)
    f_exact = jax.jit(lambda a, b: sigkernel_gram(
        a, b, backend=exact_backend, symmetric=False,
        lengths=lx, lengths_y=ly))
    exact_seconds = timer.bench(lambda: f_exact(X, Y), repeats=repeats,
                                warmup=warmup)
    K = f_exact(X, Y)
    k_norm = max(float(jnp.linalg.norm(K)), 1e-30)
    methods = tuple(n for n in dispatch.backends_for("gram")
                    if dispatch.get(n).approximate)
    points = []
    for method in methods:
        for rank in ranks:
            feats = ft.FeatureConfig(method=method, rank=int(rank))
            f = jax.jit(lambda a, b, fc=feats: sigkernel_gram(
                a, b, features=fc, symmetric=False,
                lengths=lx, lengths_y=ly))
            try:
                Ka = jax.block_until_ready(f(X, Y))
                secs = timer.bench(lambda: f(X, Y), repeats=repeats,
                                   warmup=0)
            except Exception:
                continue  # absent point = conservative, not fatal
            rel = float(jnp.linalg.norm(Ka - K)) / k_norm
            points.append({"backend": method, "rank": int(rank),
                           "rel_err": rel, "seconds": secs})
    entry = {
        "frontier": points,
        "exact_backend": exact_backend,
        "exact_seconds": exact_seconds,
        "machine": timer.machine_key(),
        "tuned_at": time.time(),
        "repeats": repeats,
    }
    if enabled():
        _store(key, entry)
    return entry


def lookup_budget(op: str, shape, dtype="float32", error_budget=None, *,
                  ragged: bool = False) -> Optional[Tuple[str, int]]:
    """Cheapest measured frontier point fitting ``error_budget``, or None.

    Never measures.  Returns ``(backend_name, rank)`` for the fastest
    frontier point whose measured relative error is ``<= error_budget``
    *and* whose wall clock beat the exact engine's — an approximation that
    is both less accurate and slower has no reason to exist.  Fail-open on
    everything else: cold/disabled cache, malformed entry, no qualifying
    point, or a ``"machine"`` stamp naming a different box (the seconds in
    a frontier do not travel; entries without a stamp are accepted, as in
    :func:`lookup_launch`, so hand-written caches remain testable).
    """
    if error_budget is None:
        return None
    budget = float(error_budget)
    entry = cache_entry(op, shape, dtype, ragged=ragged, approx=True)
    if entry is None:
        return None
    stamp = entry.get("machine")
    if isinstance(stamp, str) and stamp != timer.machine_key():
        return None
    points = entry.get("frontier")
    exact_s = entry.get("exact_seconds")
    if not isinstance(points, list) or not isinstance(exact_s, (int, float)):
        return None
    best = None
    for p in points:
        if not isinstance(p, dict):
            continue
        try:
            name = str(p["backend"])
            rank = int(p["rank"])
            rel = float(p["rel_err"])
            secs = float(p["seconds"])
        except (KeyError, TypeError, ValueError):
            continue
        if rel <= budget and secs <= exact_s and (
                best is None or secs < best[2]):
            best = (name, rank, secs)
    return None if best is None else (best[0], best[1])


# ---------------------------------------------------------------------------
# discretisation frontier (scheme × grid coarseness × interior precision)
# ---------------------------------------------------------------------------

#: every non-default discretisation point the scheme frontier measures:
#: (scheme, coarsen, interior_dtype).  ``coarsen=1`` halves the PDE grid
#: (one dyadic level / a stride-2 path subsample) — the order-2 stencil's
#: selling point is matching order-1 accuracy on the coarser grid at ~1/4
#: the cells; bf16 interiors compose with either scheme.  The identity
#: point (order1, 0, float32) IS the baseline and is never listed.
_SCHEME_POINTS = tuple(
    (s, c, dt)
    for s in ("order1", "order2") for c in (0, 1)
    for dt in ("float32", "bfloat16")
    if (s, c, dt) != ("order1", 0, "float32"))


def tune_scheme_frontier(op: str, shape, dtype="float32", *,
                         points=_SCHEME_POINTS, repeats: int = 3,
                         warmup: int = 1, ragged: bool = False,
                         force: bool = False) -> dict:
    """Measure the (scheme, coarsen, interior_dtype) frontier; persist it.

    The exact-engine sibling of :func:`tune_frontier`: every point still
    solves the Goursat PDE — no feature maps — but with a different
    discretisation.  For each point this measures steady-state seconds per
    call and the relative Frobenius error against the order-1 fine-grid
    f32 Gram at the bucketed key shape, plus that baseline's own wall
    clock as the bar every point must beat.  ``coarsen=c`` is applied the
    way the Gram engine will replay it (stride-``2^c`` path subsampling at
    the default refinement; the engine prefers dropping dyadic levels when
    the caller's ``GridConfig`` has them).  Coarsened points are skipped
    for ragged keys — the engine cannot stride-subsample masked batches,
    so measuring them would advertise a point the lookup can never serve.

    Stored under the ``scheme=True`` cache key, machine-stamped.  Warm
    keys return the stored entry with zero measurements unless
    ``force=True``; with autotuning disabled the measurement still happens
    but nothing is persisted.  A point that fails to run is skipped, never
    raised — an absent point only makes :func:`lookup_scheme_budget` more
    conservative.
    """
    from repro.core.config import GridConfig
    from repro.core.gram import sigkernel_gram
    if op != "gram":
        raise ValueError(
            f"scheme-frontier tuning only supports op='gram' (got {op!r}): "
            "the budgeted discretisation swap lives in the Gram engine")
    shape = key_shape(op, shape)
    key = cache_key(op, shape, dtype, ragged=ragged, scheme=True)
    if not force:
        entry = _entries(cache_path()).get(key)
        if isinstance(entry, dict) and isinstance(
                entry.get("scheme_frontier"), list):
            return entry
    X, Y, lx, ly = _frontier_data(shape, dtype, ragged)
    exact_backend = dispatch.resolve("auto", op="gram", shape=shape,
                                     dtype=dtype, ragged=ragged)
    f_exact = jax.jit(lambda a, b: sigkernel_gram(
        a, b, backend=exact_backend, symmetric=False,
        lengths=lx, lengths_y=ly))
    exact_seconds = timer.bench(lambda: f_exact(X, Y), repeats=repeats,
                                warmup=warmup)
    K = f_exact(X, Y)
    k_norm = max(float(jnp.linalg.norm(K)), 1e-30)
    measured = []
    for sch, coarsen, idt in points:
        if ragged and coarsen:
            continue
        step = 1 << int(coarsen)
        Xc, Yc = X[:, ::step], Y[:, ::step]
        if Xc.shape[1] < 2 or Yc.shape[1] < 2:
            continue
        g = GridConfig(scheme=sch, interior_dtype=idt)
        f = jax.jit(lambda a, b, gc=g: sigkernel_gram(
            a, b, backend=exact_backend, symmetric=False, grid=gc,
            lengths=lx, lengths_y=ly))
        try:
            Ka = jax.block_until_ready(f(Xc, Yc))
            secs = timer.bench(lambda: f(Xc, Yc), repeats=repeats, warmup=0)
        except Exception:
            continue  # absent point = conservative, not fatal
        rel = float(jnp.linalg.norm(Ka - K)) / k_norm
        measured.append({"scheme": sch, "coarsen": int(coarsen),
                         "interior_dtype": idt, "rel_err": rel,
                         "seconds": secs})
    entry = {
        "scheme_frontier": measured,
        "exact_backend": exact_backend,
        "exact_seconds": exact_seconds,
        "machine": timer.machine_key(),
        "tuned_at": time.time(),
        "repeats": repeats,
    }
    if enabled():
        _store(key, entry)
    return entry


def lookup_scheme_budget(op: str, shape, dtype="float32", error_budget=None,
                         *, ragged: bool = False
                         ) -> Optional[Tuple[str, int, str]]:
    """Cheapest measured discretisation fitting ``error_budget``, or None.

    Never measures.  Returns ``(scheme, coarsen, interior_dtype)`` for the
    fastest :func:`tune_scheme_frontier` point whose measured relative
    error is ``<= error_budget`` *and* whose wall clock beat the order-1
    fine-grid f32 baseline — a discretisation that is both less accurate
    and slower has no reason to exist.  Fail-open on everything else,
    including a foreign ``"machine"`` stamp (seconds do not travel;
    stampless hand-written entries are accepted, as in
    :func:`lookup_launch`).
    """
    if error_budget is None:
        return None
    budget = float(error_budget)
    entry = cache_entry(op, shape, dtype, ragged=ragged, scheme=True)
    if entry is None:
        return None
    stamp = entry.get("machine")
    if isinstance(stamp, str) and stamp != timer.machine_key():
        return None
    points = entry.get("scheme_frontier")
    exact_s = entry.get("exact_seconds")
    if not isinstance(points, list) or not isinstance(exact_s, (int, float)):
        return None
    best = None
    for p in points:
        if not isinstance(p, dict):
            continue
        try:
            sch = str(p["scheme"])
            coarsen = int(p["coarsen"])
            idt = str(p["interior_dtype"])
            rel = float(p["rel_err"])
            secs = float(p["seconds"])
        except (KeyError, TypeError, ValueError):
            continue
        if rel <= budget and secs <= exact_s and (
                best is None or secs < best[3]):
            best = (sch, coarsen, idt, secs)
    return None if best is None else (best[0], best[1], best[2])

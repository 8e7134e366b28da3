"""Scheme-parameterised Goursat stack: order-2 stencil + mixed precision.

The PR 10 acceptance gates, end to end:

* defaults (``order1`` / ``float32``) are bitwise-identical to an explicit
  default :class:`GridConfig` on every backend — values AND grads;
* ``order2`` coincides with ``order1`` bitwise whenever an axis is
  unrefined (the data-gridline fallback degenerates to order-1 at λ = 0);
* every (scheme, interior_dtype, backend) combination's custom-VJP
  backward matches an independent oracle — ``jax.grad`` through the plain
  (non-custom) reference scan, plus f64 finite differences;
* ``order2`` beats ``order1`` at equal grid and matches its accuracy on a
  ≥2× coarser grid within the gated rel-err budget (f64, antidiag);
* bf16 interiors stay usefully close to f32 at long L and NaNs poison,
  never mask;
* config validation names the field and the accepted values; approximate
  backends refuse non-default schemes ("never silently downgraded");
  Pallas refuses order-2 strips of height 1;
* a warm scheme-frontier autotune entry + ``error_budget=`` reproduces the
  explicit coarser/order-2/bf16 configuration bitwise, and an explicit
  scheme choice is never overridden.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.config import (GRID_INTERIOR_DTYPES, GRID_SCHEMES, GridConfig,
                               LaunchConfig)
from repro.core.gram import sigkernel_gram
from repro.core.sigkernel import delta_matrix, sigkernel

_sk = importlib.import_module("repro.core.sigkernel")

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ("reference", "antidiag", "pallas", "pallas_fused")
COMBOS = [(s, dt) for s in GRID_SCHEMES for dt in GRID_INTERIOR_DTYPES]


def paths(seed, B=2, L=6, d=2, scale=0.2):
    k = jax.random.PRNGKey(seed)
    return (jax.random.normal(k, (B, L, d)) * scale).astype(jnp.float32)


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def _max_rel(got, want):
    den = max(float(jnp.abs(want).max()), 1e-9)
    return float(jnp.abs(got - want).max()) / den


# ---------------------------------------------------------------------------
# defaults are bitwise-stable; order2 degenerates to order1 at λ = 0
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_defaults_bitwise_identical(backend):
    """grid=None, GridConfig() and an explicit order1/float32 GridConfig are
    the same static configuration — values and grads bitwise equal."""
    x, y = paths(0), paths(1)
    explicit = GridConfig(1, 1, scheme="order1", interior_dtype="float32")
    k_def = sigkernel(x, y, grid=GridConfig(1, 1), backend=backend)
    k_exp = sigkernel(x, y, grid=explicit, backend=backend)
    _bitwise(k_def, k_exp)
    g_def = jax.grad(lambda q: sigkernel(
        q, y, grid=GridConfig(1, 1), backend=backend).sum())(x)
    g_exp = jax.grad(lambda q: sigkernel(
        q, y, grid=explicit, backend=backend).sum())(x)
    _bitwise(g_def, g_exp)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("lam1,lam2", [(0, 0), (0, 2)])
def test_order2_equals_order1_on_unrefined_axis(backend, lam1, lam2):
    """With an unrefined axis every cell sits on a data gridline, so the
    order-2 fallback rule makes the schemes coincide *bitwise* (stencil.py
    module docstring) — values and grads."""
    x, y = paths(2), paths(3, L=5)
    g1 = GridConfig(lam1, lam2, scheme="order1")
    g2 = GridConfig(lam1, lam2, scheme="order2")
    _bitwise(sigkernel(x, y, grid=g2, backend=backend),
             sigkernel(x, y, grid=g1, backend=backend))
    d1 = jax.grad(lambda q: sigkernel(
        q, y, grid=g1, backend=backend).sum())(x)
    d2 = jax.grad(lambda q: sigkernel(
        q, y, grid=g2, backend=backend).sum())(x)
    _bitwise(d2, d1)


# ---------------------------------------------------------------------------
# exact backward per (scheme, interior_dtype, backend)
# ---------------------------------------------------------------------------

def _oracle_grad(x, y, grid):
    """jax.grad through the *plain* reference scan (no custom VJP): XLA's
    autodiff of solve_goursat is an independent backward implementation with
    a bitwise-identical forward (same rounding), so it checks each backend's
    one-pass adjoint for f32 AND bf16 interiors."""
    def f(q):
        delta = delta_matrix(q, y)
        return _sk.solve_goursat(delta, grid.lam1, grid.lam2,
                                 scheme=grid.scheme,
                                 interior_dtype=grid.interior_dtype).sum()
    return jax.grad(f)(x)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scheme,idt", COMBOS)
def test_backward_exact_per_combination(backend, scheme, idt):
    x, y = paths(4, L=5), paths(5, L=6)
    g = GridConfig(1, 1, scheme=scheme, interior_dtype=idt)
    got = jax.grad(lambda q: sigkernel(
        q, y, grid=g, backend=backend).sum())(x)
    want = _oracle_grad(x, y, g)
    assert _max_rel(got, want) < (2e-5 if idt == "float32" else 2e-4)


@pytest.mark.parametrize("scheme", GRID_SCHEMES)
def test_backward_matches_finite_differences(scheme):
    """f64 central differences against the one-pass adjoint — the
    discretisation-independent ground truth for the custom VJP."""
    with jax.enable_x64(True):
        key = jax.random.PRNGKey(6)
        d = (jax.random.normal(key, (4, 5)) * 0.3).astype(jnp.float64)
        v = jax.random.normal(jax.random.PRNGKey(7), (4, 5)).astype(
            jnp.float64)
        grid = _sk.solve_goursat(d[None], 1, 1, return_grid=True,
                                 scheme=scheme)
        gbar = jnp.ones((1,), jnp.float64)
        dd = _sk.solve_goursat_grad(d[None], grid, gbar, 1, 1,
                                    scheme=scheme)[0]
        eps = 1e-6
        kp = _sk.solve_goursat((d + eps * v)[None], 1, 1, scheme=scheme)[0]
        km = _sk.solve_goursat((d - eps * v)[None], 1, 1, scheme=scheme)[0]
        fd = (kp - km) / (2 * eps)
        directional = float(jnp.sum(dd * v))
        assert abs(directional - float(fd)) / max(abs(float(fd)), 1e-12) \
            < 1e-6


# ---------------------------------------------------------------------------
# accuracy: order-2 at equal and 2×-coarser grids (f64, antidiag)
# ---------------------------------------------------------------------------

def test_order2_accuracy_gates():
    with jax.enable_x64(True):
        x = (jax.random.normal(jax.random.PRNGKey(0), (2, 5, 2))
             ).astype(jnp.float64)
        y = (jax.random.normal(jax.random.PRNGKey(1), (2, 5, 2))
             ).astype(jnp.float64)

        def solve(lam, scheme):
            g = GridConfig(lam, lam, scheme=scheme)
            return np.asarray(sigkernel(x, y, grid=g, backend="antidiag"))

        truth = solve(6, "order2")

        def err(lam, scheme):
            return float(np.max(np.abs(solve(lam, scheme) - truth)
                                / np.abs(truth)))

        e1_3, e1_4 = err(3, "order1"), err(4, "order1")
        e2_2, e2_3, e2_4 = (err(2, "order2"), err(3, "order2"),
                            err(4, "order2"))
    # order-2 beats order-1 at equal grid, with margin (measured ~20×)
    assert e2_4 * 1.5 < e1_4
    assert e2_3 * 1.5 < e1_3
    # order-2 on a 2× coarser grid matches order-1's accuracy, inside the
    # gated rel-err budget the scheme_frontier workload also enforces
    assert e2_3 < e1_4
    assert e2_3 <= 0.05
    # convergence orders: order-1 halves error ×~4 per level (h²); order-2
    # contracts much faster in the pre-asymptotic range that matters
    assert 3.0 < e1_3 / e1_4 < 6.5
    assert e2_2 / e2_3 > 8.0


# ---------------------------------------------------------------------------
# bf16 interiors: bounded drift at long L, NaNs poison
# ---------------------------------------------------------------------------

def test_bf16_agreement_long_paths():
    """bf16 interior rounding drifts with grid size but stays bounded —
    measured ~0.1 rel at L=32 and ~0.32 at L=128 (each interior cell is
    rounded, so error grows with the number of updates)."""
    for L, lam, gate in [(32, 0, 0.15), (128, 0, 0.60)]:
        x, y = paths(8, B=4, L=L), paths(9, B=4, L=L)
        kf = sigkernel(x, y, grid=GridConfig(lam, lam), backend="antidiag")
        kb = sigkernel(x, y, grid=GridConfig(
            lam, lam, interior_dtype="bfloat16"), backend="antidiag")
        assert bool(jnp.isfinite(kb).all())
        assert float((jnp.abs(kf - kb) / jnp.abs(kf)).max()) < gate


@pytest.mark.parametrize("backend", ("reference", "antidiag", "pallas"))
@pytest.mark.parametrize("idt", GRID_INTERIOR_DTYPES)
def test_nan_poisons_never_masks(backend, idt):
    x, y = paths(10, L=12), paths(11, L=12)
    x = x.at[0, 5, 1].set(jnp.nan)
    g = GridConfig(1, 1, scheme="order2", interior_dtype=idt)
    k = sigkernel(x, y, grid=g, backend=backend)
    assert bool(jnp.isnan(k[0]))
    assert bool(jnp.isfinite(k[1]))


# ---------------------------------------------------------------------------
# validation: every config field names itself and the accepted values
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("field,bad", [
    ("lam1", -1), ("lam1", 1.5), ("lam1", True),
    ("lam2", -1), ("lam2", 2.0), ("lam2", False),
])
def test_gridconfig_lam_validation(field, bad):
    with pytest.raises(ValueError,
                       match=rf"GridConfig\.{field} must be a non-negative "
                             rf"Python int"):
        GridConfig(**{field: bad})


def test_gridconfig_scheme_validation():
    with pytest.raises(ValueError,
                       match=r"GridConfig\.scheme must be one of "
                             r"\('order1', 'order2'\)"):
        GridConfig(scheme="order3")
    with pytest.raises(ValueError,
                       match=r"GridConfig\.interior_dtype must be one of "
                             r"\('float32', 'bfloat16'\)"):
        GridConfig(interior_dtype="float64")


@pytest.mark.parametrize("field", ["pde_strip", "sig_bt", "sig_lb",
                                   "gram_row_block", "band_chunk"])
@pytest.mark.parametrize("bad", [0, -2, 1.5, True])
def test_launchconfig_validation(field, bad):
    with pytest.raises(ValueError,
                       match=rf"LaunchConfig\.{field} must be None or a "
                             rf"positive Python int"):
        LaunchConfig(**{field: bad})


@pytest.mark.parametrize("field", ["pde_strip", "sig_bt", "sig_lb"])
def test_launchconfig_pow2_validation(field):
    with pytest.raises(ValueError,
                       match=rf"LaunchConfig\.{field} must be a power of "
                             rf"two"):
        LaunchConfig(**{field: 3})


# ---------------------------------------------------------------------------
# capability refusals: schemes are never silently downgraded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["rff", "nystroem"])
def test_approx_backends_refuse_order2(backend):
    with pytest.raises(ValueError, match="never silently downgraded"):
        dispatch.check_scheme(backend, "order2", op="gram")
    # and the refusal names a capable backend to switch to
    with pytest.raises(ValueError, match="'reference'"):
        dispatch.check_scheme(backend, "order2", op="gram")


def test_gram_engine_refuses_order2_approx():
    X, Y = paths(12, B=3), paths(13, B=3)
    with pytest.raises(ValueError, match="never silently downgraded"):
        sigkernel_gram(X, Y, symmetric=False, backend="rff",
                       error_budget=0.1, grid=GridConfig(scheme="order2"))


def test_pallas_refuses_order2_strip_of_one():
    x, y = paths(14), paths(15)
    with pytest.raises(ValueError, match=r"pde_strip >= 2"):
        sigkernel(x, y, grid=GridConfig(scheme="order2"), backend="pallas",
                  launch=LaunchConfig(pde_strip=1))


# ---------------------------------------------------------------------------
# error_budget= scheme frontier: warm cache reproduces the explicit config
# ---------------------------------------------------------------------------

def test_budget_hook_replays_frontier_point(tmp_path, monkeypatch):
    from repro.bench import autotune
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "cache.json"))
    monkeypatch.delenv(autotune.ENV_DISABLE, raising=False)
    X, Y = paths(16, B=3, L=6), paths(17, B=2, L=6)
    Lx = X.shape[1] - 1
    key = autotune.cache_key(
        "gram", (X.shape[0], Y.shape[0], Lx << 2, Lx << 2, X.shape[2]),
        "float32", scheme=True)
    # stampless hand-written entry (accepted — seconds only gate locally)
    autotune._store(key, {
        "scheme_frontier": [{"scheme": "order2", "coarsen": 1,
                             "interior_dtype": "bfloat16",
                             "rel_err": 0.01, "seconds": 1e-4}],
        "exact_seconds": 1.0,
    })
    got = sigkernel_gram(X, Y, symmetric=False, grid=GridConfig(2, 2),
                         error_budget=0.1)
    want = sigkernel_gram(X, Y, symmetric=False,
                          grid=GridConfig(1, 1, scheme="order2",
                                          interior_dtype="bfloat16"))
    _bitwise(got, want)


def test_explicit_scheme_never_overridden(tmp_path, monkeypatch):
    """An explicit non-default GridConfig ignores the frontier cache: the
    budget hook only fires from the defaults."""
    from repro.bench import autotune
    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "cache.json"))
    monkeypatch.delenv(autotune.ENV_DISABLE, raising=False)
    X, Y = paths(18, B=3, L=6), paths(19, B=2, L=6)
    Lx = X.shape[1] - 1
    key = autotune.cache_key(
        "gram", (X.shape[0], Y.shape[0], Lx << 2, Lx << 2, X.shape[2]),
        "float32", scheme=True)
    autotune._store(key, {
        "scheme_frontier": [{"scheme": "order1", "coarsen": 1,
                             "interior_dtype": "bfloat16",
                             "rel_err": 0.01, "seconds": 1e-4}],
        "exact_seconds": 1.0,
    })
    g = GridConfig(2, 2, scheme="order2")
    got = sigkernel_gram(X, Y, symmetric=False, grid=g, error_budget=0.1)
    want = sigkernel_gram(X, Y, symmetric=False, grid=g)
    _bitwise(got, want)

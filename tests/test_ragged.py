"""Ragged-batch (variable-length) paths end-to-end.

The contract under test: with ``lengths=``, every entry point behaves as if
each path were truncated to its own true length — *bitwise* for the linear
lift, because padding turns into exactly-zero increments / Δ rows that the
Horner recursion and the Goursat boundary absorb without changing a single
float (docs/solver_guide.md § Ragged batches).  Padding *content* must be
irrelevant, so these tests poison it with NaN.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dispatch
from repro.core import transforms as tf
from repro.core.config import RBF, TransformPipeline
from repro.core.gram import sigkernel_gram
from repro.core.logsignature import logsignature
from repro.core.losses import mmd2, scoring_rule
from repro.core.signature import signature
from repro.core.sigkernel import sigkernel

B, L, D = 4, 11, 2
LENS = np.array([5, 11, 8, 3])
LENS_Y = np.array([7, 4, 13, 9])

PIPELINES = {
    "plain": TransformPipeline(),
    "time_aug": TransformPipeline(time_aug=True),
    "lead_lag": TransformPipeline(lead_lag=True),
    "all": TransformPipeline(time_aug=True, lead_lag=True, basepoint=True),
}


def _paths(seed, b, n, d, scale=0.2):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, n, d)) * scale


def _poison(x, lens):
    """Overwrite padding with NaN — ragged code must never read it."""
    out = np.asarray(x).copy()
    for i, n in enumerate(lens):
        out[i, n:] = np.nan
    return jnp.asarray(out)


X = _paths(0, B, L, D)
Y = _paths(1, B, L + 2, D)
XP = _poison(X, LENS)
YP = _poison(Y, LENS_Y)


# ---------------------------------------------------------------------------
# padded batch vs per-path truncated oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_signature_matches_truncated_oracle_bitwise(name):
    cfg = PIPELINES[name]
    sig = signature(XP, 3, transforms=cfg, lengths=jnp.asarray(LENS))
    for b, n in enumerate(LENS):
        oracle = signature(X[b:b + 1, :n], 3, transforms=cfg)
        np.testing.assert_array_equal(np.asarray(sig[b]),
                                      np.asarray(oracle[0]))


def test_logsignature_matches_truncated_oracle(name="all"):
    cfg = PIPELINES[name]
    ls = logsignature(XP, 3, transforms=cfg, lengths=jnp.asarray(LENS))
    for b, n in enumerate(LENS):
        oracle = logsignature(X[b:b + 1, :n], 3, transforms=cfg)
        np.testing.assert_allclose(np.asarray(ls[b]), np.asarray(oracle[0]),
                                   rtol=1e-6, atol=1e-7)


def test_signature_stream_repeats_final_value_past_true_end():
    cfg = PIPELINES["time_aug"]
    s = signature(XP, 3, transforms=cfg, lengths=jnp.asarray(LENS),
                  stream=True)
    final = signature(XP, 3, transforms=cfg, lengths=jnp.asarray(LENS))
    steps = s.shape[-2]
    for b, n in enumerate(LENS):
        # prefix entries at/past the true end all equal the final signature
        tail = np.asarray(s[b, cfg.transformed_steps(int(n)) - 1:])
        np.testing.assert_array_equal(
            tail, np.broadcast_to(np.asarray(final[b]), tail.shape))
    # the stream axis reflects the bucketed (padded) length
    assert steps == cfg.transformed_steps(tf.bucket_length(XP.shape[1]))


@pytest.mark.parametrize("backend", dispatch.backends_for("sigkernel"))
def test_sigkernel_matches_truncated_oracle_bitwise(backend):
    cfg = PIPELINES["all"]
    k = sigkernel(XP, YP, transforms=cfg, backend=backend,
                  lengths_x=jnp.asarray(LENS), lengths_y=jnp.asarray(LENS_Y))
    for b in range(B):
        oracle = sigkernel(X[b:b + 1, :LENS[b]], Y[b:b + 1, :LENS_Y[b]],
                           transforms=cfg, backend=backend)
        np.testing.assert_array_equal(np.asarray(k[b]), np.asarray(oracle[0]))


@pytest.mark.slow
@pytest.mark.parametrize("backend", [
    b for b in dispatch.backends_for("gram")
    if not dispatch.get(b).approximate])
def test_gram_matches_truncated_oracle(backend):
    cfg = PIPELINES["time_aug"]
    K = sigkernel_gram(XP, YP, backend=backend, transforms=cfg,
                       symmetric=False, lengths=jnp.asarray(LENS),
                       lengths_y=jnp.asarray(LENS_Y))
    for a in range(B):
        for b in range(B):
            oracle = sigkernel_gram(
                X[a:a + 1, :LENS[a]], Y[b:b + 1, :LENS_Y[b]],
                backend=backend, transforms=cfg, symmetric=False)
            np.testing.assert_allclose(
                float(K[a, b]), float(oracle[0, 0]), rtol=1e-6,
                err_msg=f"backend={backend} pair=({a},{b})")


@pytest.mark.slow
def test_gram_rbf_lift_matches_truncated_oracle():
    kernel = RBF(sigma=1.0)
    K = sigkernel_gram(XP, YP, static_kernel=kernel, symmetric=False,
                       backend="reference", lengths=jnp.asarray(LENS),
                       lengths_y=jnp.asarray(LENS_Y))
    for a in range(B):
        for b in range(B):
            oracle = sigkernel_gram(
                X[a:a + 1, :LENS[a]], Y[b:b + 1, :LENS_Y[b]],
                static_kernel=kernel, symmetric=False, backend="reference")
            np.testing.assert_allclose(float(K[a, b]), float(oracle[0, 0]),
                                       rtol=1e-5)


def test_symmetric_fast_path_ragged_matches_dense():
    cfg = PIPELINES["all"]
    lens = jnp.asarray(LENS)
    K_sym = sigkernel_gram(XP, transforms=cfg, lengths=lens)
    K_dense = sigkernel_gram(XP, XP, transforms=cfg, symmetric=False,
                             lengths=lens, lengths_y=lens)
    np.testing.assert_allclose(np.asarray(K_sym), np.asarray(K_dense),
                               rtol=1e-6, atol=1e-7)
    assert np.array_equal(np.asarray(K_sym), np.asarray(K_sym).T)


def test_gram_row_blocked_ragged_matches_unblocked():
    cfg = PIPELINES["time_aug"]
    kw = dict(transforms=cfg, symmetric=False, lengths=jnp.asarray(LENS),
              lengths_y=jnp.asarray(LENS_Y))
    np.testing.assert_array_equal(
        np.asarray(sigkernel_gram(XP, YP, row_block=3, **kw)),
        np.asarray(sigkernel_gram(XP, YP, **kw)))


# ---------------------------------------------------------------------------
# losses over ragged batches
# ---------------------------------------------------------------------------

def test_mmd2_two_differently_ragged_batches():
    cfg = PIPELINES["time_aug"]
    lens, lens_y = jnp.asarray(LENS), jnp.asarray(LENS_Y)
    got = mmd2(XP, YP, transforms=cfg, lengths=lens, lengths_y=lens_y)

    # oracle from per-pair truncated kernels
    def k(a, na, b, nb):
        return float(sigkernel(a[None, :na], b[None, :nb],
                               transforms=cfg)[0])

    kxx = np.array([[k(X[a], LENS[a], X[b], LENS[b]) for b in range(B)]
                    for a in range(B)])
    kyy = np.array([[k(Y[a], LENS_Y[a], Y[b], LENS_Y[b]) for b in range(B)]
                    for a in range(B)])
    kxy = np.array([[k(X[a], LENS[a], Y[b], LENS_Y[b]) for b in range(B)]
                    for a in range(B)])
    want = ((kxx.sum() - np.trace(kxx)) / (B * (B - 1))
            + (kyy.sum() - np.trace(kyy)) / (B * (B - 1))
            - 2.0 * kxy.mean())
    np.testing.assert_allclose(float(got), want, rtol=1e-5)


def test_mmd2_invariant_to_padded_length():
    """The same ragged data padded to different L gives the same loss."""
    cfg = PIPELINES["time_aug"]
    lens = jnp.asarray([3, 5, 4, 6])
    a = mmd2(X[:, :7], Y[:, :7], transforms=cfg, lengths=lens,
             lengths_y=lens)
    b = mmd2(jnp.pad(X[:, :7], ((0, 0), (0, 4), (0, 0))), Y[:, :7],
             transforms=cfg, lengths=lens, lengths_y=lens)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_scoring_rule_ragged():
    cfg = PIPELINES["time_aug"]
    got = scoring_rule(XP, Y[0, :6], transforms=cfg,
                       lengths=jnp.asarray(LENS), length_y=6)
    kxx = np.array([[float(sigkernel(X[a][None, :LENS[a]],
                                     X[b][None, :LENS[b]],
                                     transforms=cfg)[0])
                     for b in range(B)] for a in range(B)])
    kxy = np.array([float(sigkernel(X[a][None, :LENS[a]], Y[None, 0, :6],
                                    transforms=cfg)[0]) for a in range(B)])
    want = 0.5 * (kxx.sum() - np.trace(kxx)) / (B * (B - 1)) - kxy.mean()
    np.testing.assert_allclose(float(got), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# gradients through lengths=
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gram_grad_matches_truncated_oracle_autodiff():
    """∂K/∂X of the ragged Gram == accumulated truncated-batch autodiff,
    and padded positions get exactly-zero gradient."""
    cfg = PIPELINES["all"]
    lens, lens_y = jnp.asarray(LENS), jnp.asarray(LENS_Y)
    g = jax.grad(lambda x: sigkernel_gram(
        x, YP, transforms=cfg, symmetric=False,
        lengths=lens, lengths_y=lens_y).sum())(X)
    for a in range(B):
        def fa(xa, a=a):
            tot = 0.0
            for b in range(B):
                tot = tot + sigkernel_gram(
                    xa[None], Y[b:b + 1, :LENS_Y[b]], transforms=cfg,
                    symmetric=False, backend="reference").sum()
            return tot
        ga = jax.grad(fa)(X[a, :LENS[a]])
        np.testing.assert_allclose(np.asarray(g[a, :LENS[a]]),
                                   np.asarray(ga), rtol=1e-4, atol=1e-6)
        assert not np.any(np.asarray(g[a, LENS[a]:])), \
            f"padding of path {a} leaked gradient"


def test_gram_grad_matches_finite_differences_x64():
    """FD gradcheck through lengths= with time-aug + lead-lag + basepoint
    (f64 so the FD quotient is meaningful)."""
    cfg = PIPELINES["all"]
    with jax.enable_x64(True):
        x = jnp.asarray(np.asarray(X[:2, :6], np.float64))
        y = jnp.asarray(np.asarray(Y[:2, :7], np.float64))
        lens = jnp.asarray([4, 6])
        lens_y = jnp.asarray([7, 3])

        def f(q):
            return sigkernel_gram(q, y, transforms=cfg, symmetric=False,
                                  lengths=lens, lengths_y=lens_y).sum()

        g = jax.grad(f)(x)
        eps = 1e-6
        rng = np.random.default_rng(0)
        for _ in range(6):
            b = int(rng.integers(2))
            i = int(rng.integers(int(lens[b])))
            c = int(rng.integers(D))
            e = jnp.zeros_like(x).at[b, i, c].set(eps)
            fd = (f(x + e) - f(x - e)) / (2 * eps)
            np.testing.assert_allclose(float(g[b, i, c]), float(fd),
                                       rtol=1e-5, atol=1e-8)


def test_signature_grad_zero_on_padding():
    cfg = PIPELINES["all"]
    g = jax.grad(lambda x: signature(
        x, 3, transforms=cfg, lengths=jnp.asarray(LENS)).sum())(X)
    for b, n in enumerate(LENS):
        assert not np.any(np.asarray(g[b, n:]))


# ---------------------------------------------------------------------------
# bucketing / recompilation policy
# ---------------------------------------------------------------------------

def test_bucket_length_policy():
    assert tf.bucket_length(2) == 8       # floor at the minimum bucket
    assert tf.bucket_length(8) == 8
    assert tf.bucket_length(9) == 16
    assert tf.bucket_length(11) == 16
    assert tf.bucket_length(16) == 16
    assert tf.bucket_length(1000) == 1024


def test_ragged_batches_sharing_a_bucket_reuse_one_trace():
    """Two ragged batches whose padded lengths land in the same bucket go
    through ONE jit trace (the acceptance-criteria compile-count check)."""
    traces = []

    @jax.jit
    def f(x, lens):
        traces.append(1)
        return signature(x, 3, transforms=PIPELINES["time_aug"],
                         lengths=lens)

    x1, l1 = tf.pad_ragged(X[:, :11], jnp.asarray([5, 6, 7, 11]))
    x2, l2 = tf.pad_ragged(X[:, :9], jnp.asarray([4, 9, 3, 8]))
    assert x1.shape == x2.shape  # same bucket => same trace key
    r1, r2 = f(x1, l1), f(x2, l2)
    assert len(traces) == 1, "second ragged batch retraced despite bucket"
    # and the bucketed results still match the truncated oracles
    np.testing.assert_array_equal(
        np.asarray(r2[1]),
        np.asarray(signature(X[1:2, :9], 3,
                             transforms=PIPELINES["time_aug"])[0]))


def test_pad_ragged_canonicalises():
    p, lens = tf.pad_ragged(X, np.array([5, 11, 8, 3]))
    assert p.shape == (B, tf.bucket_length(L), D)
    assert lens.dtype == jnp.int32
    # edge padding: repeated last rows (content is irrelevant downstream)
    np.testing.assert_array_equal(np.asarray(p[:, L:]),
                                  np.broadcast_to(np.asarray(X[:, -1:]),
                                                  (B, p.shape[1] - L, D)))


# ---------------------------------------------------------------------------
# time-grid dtype hardening (satellite bugfix)
# ---------------------------------------------------------------------------

def test_time_grid_built_in_f32_for_bf16_at_long_length():
    """bf16 can't even represent integers past 256: a grid built natively in
    bf16 collapses to a handful of distinct steps by L=4096.  The fix builds
    in f32 and casts once — matching np.linspace(f32).astype(bf16)."""
    path = jnp.zeros((1, 4096, 1), jnp.bfloat16)
    out = tf.time_augment(path, 0.0, 1.0)
    assert out.dtype == jnp.bfloat16
    t = np.asarray(out[0, :, 1], np.float32)
    want = np.asarray(
        np.linspace(0.0, 1.0, 4096, dtype=np.float32).astype(jnp.bfloat16),
        np.float32)
    np.testing.assert_array_equal(t, want)
    assert t[-1] == 1.0 and (np.diff(t) >= 0).all()


def test_time_grid_integer_paths_promote_to_f32():
    path = jnp.arange(12, dtype=jnp.int32).reshape(1, 12, 1)
    out = tf.time_augment(path)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out[0, :, 1]),
                               np.linspace(0, 1, 12, dtype=np.float32))


def test_transform_increments_dt_in_f32_for_bf16():
    z = jnp.zeros((1, 4095, 1), jnp.bfloat16)
    out = tf.transform_increments(z, True, False)
    assert out.dtype == jnp.bfloat16
    dt = np.asarray(out[0, :, 1], np.float32)
    want = float(jnp.asarray(np.float32(1.0 / 4095)).astype(jnp.bfloat16))
    assert (dt == want).all()


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_lengths_validation():
    with pytest.raises(TypeError, match="integer-typed"):
        signature(X, 2, lengths=jnp.asarray([1.0, 2.0, 3.0, 4.0]))
    with pytest.raises(ValueError, match="shape"):
        signature(X, 2, lengths=jnp.asarray([5, 6]))
    with pytest.raises(ValueError, match=">= 2"):
        signature(X, 2, lengths=jnp.asarray([1, 5, 5, 5]))
    with pytest.raises(ValueError, match="<="):
        signature(X, 2, lengths=jnp.asarray([5, 5, 5, L + 1]))
    with pytest.raises(ValueError, match="lengths_y= requires Y"):
        sigkernel_gram(X, lengths_y=jnp.asarray(LENS))


def test_align_validation():
    with pytest.raises(ValueError, match="align"):
        tf.pipeline_increments(X, PIPELINES["plain"], jnp.asarray(LENS),
                               align="middle")


def test_ragged_entry_points_silent_on_warnings():
    """lengths= is new API — it must not trip any deprecation path."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        signature(XP, 2, lengths=jnp.asarray(LENS))
        sigkernel_gram(XP, lengths=jnp.asarray(LENS))

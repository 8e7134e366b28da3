"""The benchmark's plain reference against the library's own reference
solver, at small sizes on the CPU and in float64, where the two agree to
rounding; in float32 both sit some 1e-5 from the exact value."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), ROOT)
                if p not in sys.path]

from chipbench import data, reference  # noqa: E402


@pytest.mark.parametrize("lam", [(0, 0), (1, 1), (2, 1)])
@pytest.mark.parametrize("time_aug", [False, True])
def test_gram_matches_library_reference(lam, time_aug):
    import repro
    with jax.enable_x64(True):
        kx, ky = jax.random.split(data.seed_key(3))
        X = data.gbm_paths(kx, 4, 9, 3).astype(jnp.float64)
        Y = data.gbm_paths(ky, 3, 7, 3, sigma=0.4).astype(jnp.float64)
        want = repro.sigkernel_gram(
            X, Y, grid=repro.GridConfig(*lam),
            transforms=repro.TransformPipeline(time_aug=time_aug),
            backend="reference")
        got = reference.gram(reference.increments(X, time_aug),
                             reference.increments(Y, time_aug), *lam,
                             rows=2)
        assert got.dtype == jnp.float64
        np.testing.assert_allclose(got, want, rtol=1e-12)


def test_mmd2_value_and_gradient_match_library():
    import repro
    k1, k2, k3 = jax.random.split(data.seed_key(2**33 + 7), 3)
    f64 = jnp.float64
    with jax.enable_x64(True):
        theta = jax.tree_util.tree_map(
            lambda a: a.astype(f64), data.init_generator(k1, 2, 0.1, 0.02))
        z = jax.random.normal(k2, (4, 8, 2), f64)
        y = data.gbm_paths(k3, 4, 9, 2).astype(f64)
        _mmd2_agrees(repro, theta, z, y)


def _mmd2_agrees(repro, theta, z, y):
    sk = repro.SigKernel(transforms=repro.TransformPipeline(time_aug=True),
                         grid=repro.GridConfig(1, 1), backend="reference")

    def lib(th):
        return sk.mmd2(data.generate(th, z), y)

    def ref(th):
        return reference.mmd2_unbiased(data.generate(th, z), y,
                                       time_aug=True, lam1=1, lam2=1,
                                       rows=2)[0]

    (v1, g1), (v2, g2) = (jax.value_and_grad(f)(theta) for f in (lib, ref))
    assert v2.dtype == jnp.float64
    np.testing.assert_allclose(v2, v1, rtol=1e-10)
    for k in theta:
        np.testing.assert_allclose(g2[k], g1[k], rtol=1e-9)


def test_pair_kernels_are_the_gram_entries():
    s = reference.increments(data.gbm_paths(data.seed_key(5), 5, 6, 2),
                             False)
    K = reference.gram(s, s, 1, 0, rows=5)
    a, b = jnp.array([0, 1, 4]), jnp.array([3, 1, 2])
    np.testing.assert_allclose(reference.pair_kernels(s[a], s[b], 1, 0),
                               K[a, b], rtol=1e-6)


def test_seed_key_uses_all_64_bits():
    keys = {tuple(np.asarray(jax.random.key_data(data.seed_key(s))))
            for s in (1, 2**32 + 1, 2**33 + 1, 2**31 + 5)}
    assert len(keys) == 4

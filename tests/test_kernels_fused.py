"""Fused-Δ Pallas kernels (beyond-paper §Perf it.3): Δ computed in VMEM."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.sigkernel_pde import ops, ref
from repro.core.signature import path_increments
from repro.core.sigkernel import sigkernel_gram

jax.config.update("jax_platform_name", "cpu")


def paths(seed, B, L, d):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, L, d)) * 0.2


@pytest.mark.parametrize("B,Lx,Ly,d,l1,l2", [
    (2, 9, 7, 3, 0, 0), (3, 20, 15, 4, 1, 1), (1, 33, 12, 2, 0, 2)])
def test_fused_forward(B, Lx, Ly, d, l1, l2):
    dx = path_increments(paths(0, B, Lx + 1, d))
    dy = path_increments(paths(1, B, Ly + 1, d))
    delta = jnp.einsum("bid,bjd->bij", dx, dy)
    k_f = ops.solve_fused(dx, dy, l1, l2)
    k_r = ref.solve(delta, l1, l2)
    np.testing.assert_allclose(k_f, k_r, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("Bx,By,L,d", [(3, 4, 8, 3), (2, 5, 12, 2)])
def test_fused_gram(Bx, By, L, d):
    X, Y = paths(2, Bx, L, d), paths(3, By, L, d)
    K_f = ops.gram_fused(path_increments(X), path_increments(Y))
    K_r = sigkernel_gram(X, Y)
    np.testing.assert_allclose(K_f, K_r, rtol=5e-4, atol=1e-5)


def _pad_batch(a, n):
    return jnp.pad(a, ((0, n - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


@pytest.mark.parametrize("scheme,interior_dtype", [
    ("order1", "float32"), ("order2", "bfloat16")])
@pytest.mark.parametrize("lam", [(0, 0), (1, 1)])
@pytest.mark.parametrize("kind,batch", [
    ("fwd", 1), ("fwd", 7), ("fwd", 8), ("fwd", 13),
    ("gram", 5), ("gram", 12)])
def test_packed_matches_single_pair_bitwise(kind, batch, lam, scheme,
                                            interior_dtype):
    """Two to ``PACK`` pairs per program, one per sublane, give each pair
    the bits of the one-pair program: the same ops in the same order."""
    from repro.kernels.sigkernel_pde.kernel import (PACK, build_fwd_fused,
                                                    build_gram_fused)
    T, Lx, Ly, d, Bx = 8, 12, 7, 3, 3
    l1, l2 = lam
    Lxp = -(-Lx // (T >> l1)) * (T >> l1)
    dx = paths(4, batch if kind == "fwd" else Bx, Lxp, d)
    dy = paths(5, batch, Ly, d)
    kw = dict(T=T, lam1=l1, lam2=l2, interpret=True, scheme=scheme,
              interior_dtype=interior_dtype)
    if kind == "fwd":
        one = build_fwd_fused(batch, Lxp, Ly, d, pack=1, **kw)(dx, dy)
    else:
        one = build_gram_fused(Bx, batch, Lxp, Ly, d, pack=1, **kw)(dx, dy)
    for pack in (2, 4, 8, PACK):
        bp = -(-batch // pack) * pack
        if kind == "fwd":
            packed = build_fwd_fused(bp, Lxp, Ly, d, pack=pack, **kw)(
                _pad_batch(dx, bp), _pad_batch(dy, bp))[:batch]
        else:
            packed = build_gram_fused(Bx, bp, Lxp, Ly, d, pack=pack, **kw)(
                dx, _pad_batch(dy, bp))[:, :batch]
        assert packed.shape == one.shape
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(one))


def test_packed_grads_match_reference():
    """``jax.grad`` through the packed forward (13 pairs: a padded pack)
    and through the packed Gram matches autodiff of the reference."""
    B, Lx, Ly, d = 13, 10, 9, 3
    dx = path_increments(paths(6, B, Lx + 1, d))
    dy = path_increments(paths(7, B, Ly + 1, d))
    w = jnp.linspace(0.5, 1.5, B)

    def fused(a, b):
        return jnp.sum(w * ops.solve_fused(a, b, 1, 1))

    def oracle(a, b):
        return jnp.sum(w * ref.solve(jnp.einsum("bid,bjd->bij", a, b), 1, 1))

    for g_f, g_r in zip(jax.grad(fused, (0, 1))(dx, dy),
                        jax.grad(oracle, (0, 1))(dx, dy)):
        np.testing.assert_allclose(g_f, g_r, rtol=5e-4, atol=1e-5)

    dX, dY = dx[:3], dy

    def gram(a, b):
        return jnp.sum(ops.gram_fused(a, b) * w)

    def gram_oracle(a, b):
        return jnp.sum(ref.solve(jnp.einsum("aid,bjd->abij", a, b)) * w)

    for g_f, g_r in zip(jax.grad(gram, (0, 1))(dX, dY),
                        jax.grad(gram_oracle, (0, 1))(dX, dY)):
        np.testing.assert_allclose(g_f, g_r, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("scheme,interior_dtype", [
    ("order1", "float32"), ("order2", "bfloat16")])
@pytest.mark.parametrize("lam", [(0, 0), (1, 1)])
@pytest.mark.parametrize("batch", [1, 7, 8, 13])
def test_packed_backward_matches_single_pair_bitwise(batch, lam, scheme,
                                                     interior_dtype):
    """The exact backward at two to ``PACK`` pairs per program gives each
    pair the dΔ bits of the one-pair program.  Strips of T = 8 rows carry
    the adjoint rows from strip to strip; padded pairs have zero Δ and a
    zero cotangent."""
    from repro.kernels.sigkernel_pde.grad_kernel import build_bwd
    from repro.kernels.sigkernel_pde.kernel import PACK, build_fwd
    T, Lx, Ly = 8, 12, 7
    l1, l2 = lam
    Lxp = -(-Lx // (T >> l1)) * (T >> l1)
    delta = jax.random.normal(jax.random.PRNGKey(9), (batch, Lxp, Ly)) * 0.2
    gbar = jax.random.normal(jax.random.PRNGKey(10), (batch,))
    kw = dict(T=T, lam1=l1, lam2=l2, interpret=True, scheme=scheme,
              interior_dtype=interior_dtype)
    _, cps = build_fwd(batch, Lxp, Ly, save_cps=True, **kw)(delta)
    one = build_bwd(batch, Lxp, Ly, pack=1, **kw)(delta, delta, cps, gbar)
    assert np.all(np.isfinite(one)) and np.any(np.asarray(one) != 0)
    for pack in (2, 4, 8, PACK):
        bp = -(-batch // pack) * pack
        d = _pad_batch(delta, bp)
        packed = build_bwd(bp, Lxp, Ly, pack=pack, **kw)(
            d, d, _pad_batch(cps, bp), _pad_batch(gbar, bp))[:batch]
        assert packed.shape == one.shape
        np.testing.assert_array_equal(np.asarray(packed), np.asarray(one))


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
def test_packed_backward_grads_match_reference(backend):
    """``jax.grad`` through both Pallas backends, whose exact backward
    packs 13 pairs into a padded pack of 16, matches the reference
    backend's gradient."""
    from repro.core.config import GridConfig
    from repro.core.sigkernel import sigkernel
    x, y = paths(11, 13, 11, 3), paths(12, 13, 10, 3)
    w = jnp.linspace(0.5, 1.5, 13)

    def loss(a, b, name):
        return jnp.sum(w * sigkernel(a, b, grid=GridConfig(1, 1),
                                     backend=name))

    for g_p, g_r in zip(jax.grad(loss, (0, 1))(x, y, backend),
                        jax.grad(loss, (0, 1))(x, y, "reference")):
        np.testing.assert_allclose(g_p, g_r, rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("pairs,slots", [
    (1, 1), (2, 2), (3, 4), (5, 8), (13, 16), (16, 16), (17, 32)])
def test_count_packed_slots(pairs, slots):
    """Batches under ``PACK`` take the smallest pack that holds them."""
    from repro.core import dispatch
    dx = path_increments(paths(8, pairs, 6, 2))
    ops._solve_fused_impl.clear_cache()   # the counter counts traces
    with dispatch.count_packed_slots() as c:
        ops.solve_fused(dx, dx)
    assert (c.total, c.pairs) == (slots, pairs)
    assert c.fill == pairs / slots


def test_benchmark_cells_fill_their_packs():
    """The benchmark cells hand the packed kernels whole packs: the MMD
    step's triangles (8,256 pairs) and K_xy (128 × 128), forward and
    backward, and the pooled Gram's 16,384-pair chunks (1024 paths,
    ``row_block=16``)."""
    import repro
    from repro.core import dispatch
    from repro.launch.mesh import make_gram_mesh
    sk = repro.SigKernel(transforms=repro.TransformPipeline(time_aug=True),
                         grid=repro.GridConfig(1, 1), backend="pallas_fused")
    x = jax.ShapeDtypeStruct((128, 128, 3), jnp.float32)
    mesh = make_gram_mesh(1, devices=jax.devices()[:1])
    z = jax.ShapeDtypeStruct((1024, 128, 8), jnp.float32)
    for step, arg, slots in [
            (jax.value_and_grad(lambda a, b: sk.mmd2(a, b)), (x, x),
             128 * 129 // 2 + 128 * 128),
            (lambda Z: repro.sigkernel_gram_sharded(
                Z, mesh=mesh, grid=repro.GridConfig(0, 0),
                backend="pallas_fused", row_block=16), (z,), 16 * 1024)]:
        ops._solve_fused_impl.clear_cache()   # the counter counts traces
        ops._gram_fused_impl.clear_cache()
        ops._grad_flat.clear_cache()
        with dispatch.count_packed_slots() as c, \
                dispatch.count_bwd_packed_slots() as cb:
            jax.eval_shape(step, *arg)
        assert (c.total, c.fill) == (slots, 1.0)
        # the backward differentiates K_xx's triangle and K_xy
        assert (cb.total, cb.fill) == ((slots, 1.0) if len(arg) == 2
                                       else (0, 1.0))


@pytest.mark.parametrize("pairs,slots", [(1, 1), (3, 4), (13, 16), (17, 32)])
def test_count_bwd_packed_slots(pairs, slots):
    """The exact backward pads its batch to whole packs of ``bwd_pack``
    pairs and counts them apart from the forward's."""
    from repro.core import dispatch
    dx = path_increments(paths(13, pairs, 6, 2))
    ops._grad_flat.clear_cache()   # the counter counts traces
    with dispatch.count_bwd_packed_slots() as c:
        jax.eval_shape(jax.grad(lambda a: ops.solve_fused(a, dx).sum()), dx)
    assert (c.total, c.pairs) == (slots, pairs)


@pytest.mark.parametrize("Ly,lam,batch,pack", [
    (127, 1, 16384, 16), (255, 1, 16384, 8), (511, 1, 16384, 4),
    (127, 0, 1, 1), (127, 0, 6, 8)])
def test_fused_pack(Ly, lam, batch, pack):
    """``PACK`` pairs up to W = 384; longer paths and small batches pack
    fewer."""
    from repro.kernels.sigkernel_pde.kernel import fused_pack
    assert fused_pack(Ly << lam, 128, batch) == pack


@pytest.mark.parametrize("Ly,lam,batch,pack", [
    (127, 1, 24640, 16), (255, 1, 16384, 8), (511, 1, 16384, 4),
    (1023, 1, 32, 2), (2047, 1, 32, 1), (127, 0, 1, 1), (127, 0, 6, 8)])
def test_bwd_pack(Ly, lam, batch, pack):
    """The backward packs ``PACK`` pairs up to W = 512 and fewer for longer
    paths, so its six scratches keep the VMEM request under 56 MiB; long
    streams and lone pairs keep the one-pair program."""
    from repro.kernels.sigkernel_pde.grad_kernel import bwd_pack
    from repro.kernels.sigkernel_pde.kernel import strip_width, vmem_limit
    assert bwd_pack(Ly << lam, 128, batch) == pack
    if pack > 1:
        W = strip_width(Ly << lam, 128)
        assert vmem_limit(W, 128, 6, pack) < 56 * 2 ** 20

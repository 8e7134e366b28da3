"""Compile the main-path Pallas kernels for a described (not attached) TPU v5e.

Mosaic refuses layouts that interpret mode accepts (unaligned blocks,
scalar VMEM stores, ...); these compiles catch that without a chip.  Shapes
are those of ``chip_smoke.py``: the sig-MMD step (B=128 pairs, L=128, d=3 +
time, dyadic order 1) and the signature features (B=128, L=1024, d=5,
depth 5).  The topology is described inside a fixture, never at import.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tensoralg import sig_dim
from repro.kernels.sigkernel_pde.grad_kernel import build_bwd
from repro.kernels.sigkernel_pde.kernel import (build_fwd, build_fwd_fused,
                                                build_gram_fused, cps_lanes,
                                                strip_width)
from repro.kernels.signature.kernel import build_horner
from repro.kernels.signature.ops import choose_BT

B, L, D, T = 128, 127, 4, 128          # pairs, Δ rows/cols, channels, strip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off (entries
    written for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiles_to_mosaic(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _geometry(lam):
    R = T >> lam
    Lx = -(-L // R) * R
    return Lx, Lx // R, strip_width(L << lam, T)


@pytest.mark.parametrize("scheme,interior_dtype,lam,save_cps", [
    ("order1", "float32", 1, False),
    ("order2", "bfloat16", 1, False),
    ("order1", "float32", 1, True),
])
def test_pde_forward_compiles(one_chip, scheme, interior_dtype, lam,
                              save_cps):
    Lx, _, _ = _geometry(lam)
    fwd = build_fwd(B, Lx, L, T=T, lam1=lam, lam2=lam, save_cps=save_cps,
                    interpret=False, scheme=scheme,
                    interior_dtype=interior_dtype)
    _compiles_to_mosaic(fwd, one_chip, (B, Lx, L))


def test_pde_fused_forward_compiles(one_chip):
    Lx, _, _ = _geometry(1)
    fwd = build_fwd_fused(B, Lx, L, D, T=T, lam1=1, lam2=1, interpret=False)
    _compiles_to_mosaic(fwd, one_chip, (B, Lx, D), (B, L, D))


def test_pde_fused_gram_compiles(one_chip):
    Lx, _, _ = _geometry(1)
    gram = build_gram_fused(B, B, Lx, L, D, T=T, lam1=1, lam2=1,
                            interpret=False)
    _compiles_to_mosaic(gram, one_chip, (B, Lx, D), (B, L, D))


@pytest.mark.parametrize("scheme,interior_dtype,lam", [
    ("order1", "float32", 1),
    ("order2", "bfloat16", 2),
])
def test_pde_backward_compiles(one_chip, scheme, interior_dtype, lam):
    Lx, n_strips, W = _geometry(lam)
    bwd = build_bwd(B, Lx, L, T=T, lam1=lam, lam2=lam, interpret=False,
                    scheme=scheme, interior_dtype=interior_dtype)
    _compiles_to_mosaic(bwd, one_chip, (B, Lx, L), (B, Lx, L),
                        (B, n_strips, cps_lanes(T), W), (B,))


def test_signature_horner_compiles(one_chip):
    Bs, Lp, d, depth, LB = 128, 1024, 5, 5, 256
    BT = choose_BT(d, depth, LB)
    horner = build_horner(Bs // BT, Lp, d, depth, BT=BT, LB=LB,
                          interpret=False)
    assert sig_dim(d, depth) == 3905
    _compiles_to_mosaic(horner, one_chip, (Bs // BT, Lp, d, BT))

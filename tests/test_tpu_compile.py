"""Compile the main-path Pallas kernels for a described (not attached) TPU v5e.

Mosaic refuses layouts that interpret mode accepts (unaligned blocks,
scalar VMEM stores, ...); these compiles catch that without a chip.  Shapes
are those of ``chip_smoke.py``: the sig-MMD step (B=128 pairs, L=128, d=3 +
time, dyadic order 1) and the signature features (B=128, L=1024, d=5,
depths 5 to 7); the packed fused kernels compile at the benchmark cells'
pair counts besides, and the packed backward at the MMD step's shape and on
a longer path.  The topology is described inside a fixture, never at
import.

Three whole programs are compiled besides, the MMD training step, the
sharded symmetric Gram and the log-signature layer's training step, to pin
the names a profile reads: every kernel's custom call carries a name from
``repro.kernels.KERNEL_NAMES``, and the engine's ops carry its
``jax.named_scope`` layers in their ``op_name``.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import repro
from repro.core.tensoralg import sig_dim
from repro.kernels import KERNEL_NAMES
from repro.kernels.sigkernel_pde import ops as pde_ops
from repro.kernels.sigkernel_pde.grad_kernel import build_bwd, bwd_pack
from repro.kernels.sigkernel_pde.kernel import (PACK, build_fwd,
                                                build_fwd_fused,
                                                build_gram_fused, cps_lanes,
                                                fused_pack, strip_width)
from repro.kernels.signature.kernel import build_horner
from repro.kernels.signature import ops as sig_ops
from repro.kernels.signature.ops import choose_prefix

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


@pytest.fixture
def compiled_kernels(one_chip, monkeypatch):
    """The ``ops.py`` wrappers build Mosaic kernels, not interpreted ones.

    Their jitted traces do not key on the interpret flag, so the caches are
    cleared on the way in and out: no trace crosses into another test."""
    monkeypatch.setattr(pde_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(sig_ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


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


#: the benchmark cells' fused geometries: (pairs, channels, λ) of the
#: pooled Gram's row blocks (d=8, order 0) and of the MMD step's triangles
#: (d=3 plus time, order 1)
FUSED_CELLS = [(16384, 8, 0), (128 * 129 // 2, 4, 1)]


@pytest.mark.parametrize("pairs,d,lam", FUSED_CELLS)
def test_pde_fused_forward_compiles(one_chip, pairs, d, lam):
    Lx, _, _ = _geometry(lam)
    assert pairs % PACK == 0
    fwd = build_fwd_fused(pairs, Lx, L, d, T=T, lam1=lam, lam2=lam,
                          interpret=False)
    _compiles_to_mosaic(fwd, one_chip, (pairs, Lx, d), (pairs, L, d))


@pytest.mark.parametrize("Bx,By,d,lam", [(16, 1024, 8, 0), (128, 128, 4, 1)])
def test_pde_fused_gram_compiles(one_chip, Bx, By, d, lam):
    Lx, _, _ = _geometry(lam)
    gram = build_gram_fused(Bx, By, Lx, L, d, T=T, lam1=lam, lam2=lam,
                            interpret=False)
    _compiles_to_mosaic(gram, one_chip, (Bx, Lx, d), (By, L, d))


def test_pde_fused_long_path_compiles(one_chip):
    """A path of 512 points at dyadic order 1 (W = 1152) packs 4 pairs,
    whose unrolled Δ build stays within ``PACK_ROWS``."""
    Lx, Ly, lam = 128, 511, 1
    assert fused_pack(Ly << lam, T, B) == 4
    fwd = build_fwd_fused(B, Lx, Ly, D, T=T, lam1=lam, lam2=lam,
                          interpret=False)
    _compiles_to_mosaic(fwd, one_chip, (B, Lx, D), (B, Ly, D))


def test_pde_fused_gram_output_is_lane_dense(one_chip):
    """The packed Gram writes its values along the lanes of (Bx, 1, By)
    rows: no more HBM than one (8, 128) tile per 128 values of a row."""
    n = 4096
    gram = build_gram_fused(n, n, 128, L, D, T=T, lam1=0, lam2=0,
                            interpret=False)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in [(n, 128, D), (n, L, D)]]
    mem = jax.jit(gram).lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes <= 8 * 4 * n * n + 2 ** 20


@pytest.mark.parametrize("scheme,interior_dtype,lam,Ly,pack", [
    ("order1", "float32", 1, L, PACK),
    ("order2", "bfloat16", 2, L, 8),
    # a path of 512 points at dyadic order 1 (W = 1152) packs 4 pairs
    ("order1", "float32", 1, 511, 4),
])
def test_pde_backward_compiles(one_chip, scheme, interior_dtype, lam, Ly,
                               pack):
    """The exact backward packs its pairs into the sublanes: 16 at the MMD
    step's shape (W = 384), fewer on longer paths."""
    Lx, n_strips, _ = _geometry(lam)
    W = strip_width(Ly << lam, T)
    assert bwd_pack(Ly << lam, T, B) == pack
    bwd = build_bwd(B, Lx, Ly, T=T, lam1=lam, lam2=lam, interpret=False,
                    scheme=scheme, interior_dtype=interior_dtype)
    _compiles_to_mosaic(bwd, one_chip, (B, Lx, Ly), (B, Lx, Ly),
                        (B, n_strips, cps_lanes(T), W), (B,))


def _horner_compiles(sharding, depth):
    Bs, Lp, d, LB, BT = 128, 1024, 5, 256, 128
    prefix = choose_prefix(d, depth, LB, BT)
    horner = build_horner(Bs // BT, Lp, d, depth, BT=BT, LB=LB,
                          prefix=prefix, interpret=False)
    _compiles_to_mosaic(horner, sharding, (Bs // BT, Lp, d, BT))
    return prefix


def test_signature_horner_compiles(one_chip):
    """Depth 5 holds the whole signature in one program."""
    assert sig_dim(5, 5) == 3905
    assert _horner_compiles(one_chip, 5) == 0


@pytest.mark.parametrize("depth,prefix", [(6, 1), (7, 2)])
def test_signature_horner_deep_compiles(one_chip, depth, prefix):
    """Depths 6 and 7 (19,530 and 97,655 rows, more VMEM than a program
    may take) split the words by their first letters, on full 128-lane
    batch tiles."""
    assert _horner_compiles(one_chip, depth) == prefix


CUSTOM_CALL = re.compile(r"^\s*%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                         re.M)


def _named_layers(text):
    """(kernel roles, op_name strings) of a compiled program's text; each
    kernel's custom call must be named from ``KERNEL_NAMES``."""
    names = [re.sub(r"\.\d+$", "", n) for n in CUSTOM_CALL.findall(text)]
    assert names and set(names) <= set(KERNEL_NAMES.values()), names
    roles = {n.split(".")[1] for n in names}
    return roles, set(re.findall(r'op_name="([^"]*)"', text))


def _passes_through(op_names, scopes):
    """The scopes that are a part of some ``op_name`` path, bare or inside a
    transformation (``jvp(repro.gram.pairs)``)."""
    return {s for s in scopes
            if any(re.search(rf"(^|[/(]){re.escape(s)}([/)]|$)", n)
                   for n in op_names)}


def test_mmd_step_names_its_kernels_and_layers(compiled_kernels, one_chip):
    sk = repro.SigKernel(transforms=repro.TransformPipeline(time_aug=True),
                         grid=repro.GridConfig(1, 1), backend="pallas_fused")
    step = jax.value_and_grad(lambda x, y: sk.mmd2(x, y))
    x = jax.ShapeDtypeStruct((8, 32, 3), jnp.float32, sharding=one_chip)
    roles, op_names = _named_layers(
        jax.jit(step).lower(x, x).compile().as_text())
    assert roles == {"fwd", "fwd_ckpt", "bwd"}
    scopes = {"repro.transform", "repro.gram.pairs", "repro.pde.pullback",
              "repro.gram.reduce"}
    assert _passes_through(op_names, scopes) == scopes


def test_sharded_gram_names_its_kernels_and_layers(compiled_kernels, topo):
    from repro.launch.mesh import make_gram_mesh
    mesh = make_gram_mesh(4, devices=topo.devices)
    z = jax.ShapeDtypeStruct((16, 32, 8), jnp.float32,
                             sharding=NamedSharding(mesh, PartitionSpec()))
    gram = jax.jit(lambda Z: repro.sigkernel_gram_sharded(
        Z, mesh=mesh, grid=repro.GridConfig(0, 0), backend="pallas_fused",
        row_block=4))
    roles, op_names = _named_layers(gram.lower(z).compile().as_text())
    assert roles == {"fwd"}
    scopes = {"repro.transform", "repro.gram.pairs", "repro.gram.shard",
              "repro.gram.reduce"}
    assert _passes_through(op_names, scopes) == scopes


def test_logsig_step_names_its_kernels_and_layers(compiled_kernels,
                                                  one_chip):
    """The ``sigfeat_train`` cell's step at its own shapes: the Horner
    kernel is the only custom call, and the backward, the log and the
    Lyndon projection carry their scopes.  (Short paths compile slower:
    XLA unrolls the backward's short scan.)"""
    import json
    from chipbench.units import logsig_sgd_step
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "chipbench", "configs",
                        "logsig_fig1_d5_L1024_N7.json")
    with open(path) as f:
        cfg = dict(json.load(f), backend="pallas")
    B, L, d, depth = cfg["batch"], cfg["length"], cfg["channels"], cfg["depth"]
    n = sum(logsig_sgd_step.feature_dims(d, depth))
    loss = logsig_sgd_step.program_loss(cfg)
    step = jax.value_and_grad(loss, has_aux=True)
    S = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
         for s in [(d, d), (n,), (B, L, d), (B,)]]
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        text = jax.jit(step).lower({"mix": S[0], "readout": S[1]},
                                   *S[2:]).compile().as_text()
    roles, op_names = _named_layers(text)
    assert roles == {"horner"}
    scopes = set(logsig_sgd_step.SCOPES)
    assert _passes_through(op_names, scopes) == scopes

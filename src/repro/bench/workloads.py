"""Paper-aligned benchmark workloads at smoke / quick / full sizes.

Every workload returns a list of *entries* — plain dicts the suite
serialises into the BENCH JSON:

``{"name": str, "kind": "time" | "accuracy" | "check",
   "seconds": float,          # kind == "time"
   "value": float,            # kind == "accuracy" (relative error)
   "derived": str,            # human-readable extras
   "meta": {...}}             # shape/op context; meta["gate"] = False
                              # excludes an entry from the CI perf gate

Names are stable across runs — :mod:`repro.bench.compare` matches entries
by name.  The cells are the paper's Tables 1–3 and the §3.4
gradient-accuracy study; ``full`` uses the paper's exact (B, L, d, N)
cells, ``quick`` scales them down but keeps every comparison intact, and
``smoke`` is the tiny CI gate.

The legacy ``benchmarks/`` scripts are thin CSV wrappers over this module.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core.config import RBF, delta_from_gram
from repro.core.gram import sigkernel_gram, sigkernel_gram_reduce
from repro.core.logsignature import logsignature
from repro.core.lyndon import logsig_dim
from repro.core.signature import signature, signature_direct
from repro.core.sigkernel import (delta_matrix, sigkernel, solve_goursat,
                                  solve_goursat_antidiag, solve_goursat_grad,
                                  solve_goursat_grad_pde_approx)
from repro.core.tensoralg import sig_dim

from . import autotune, roofline, timer

MODES = ("smoke", "quick", "full")


def _check_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    return mode


def _t(name: str, seconds: float, derived: str = "", _fn=None, _args=(),
       **meta) -> dict:
    """Timed entry; every one carries a ``"roofline"`` dict (achieved vs.
    peak FLOPs/bandwidth).  Pass ``_fn``/``_args`` — the benched callable —
    to upgrade the analytic counts to HLO-derived ones (one extra
    lower+compile, memoised on the entry name)."""
    e = {"name": name, "kind": "time", "seconds": float(seconds),
         "derived": derived, "meta": meta}
    return roofline.attach(e, _fn, _args)


def _acc(name: str, value: float, derived: str = "", **meta) -> dict:
    e = {"name": name, "kind": "accuracy", "value": float(value),
         "derived": derived, "meta": meta}
    return roofline.attach(e)


def _chk(name: str, derived: str = "ok", **meta) -> dict:
    e = {"name": name, "kind": "check", "derived": derived, "meta": meta}
    return roofline.attach(e)


def _paths(seed: int, B: int, L: int, d: int, scale: float) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(seed), (B, L, d)) * scale


# ---------------------------------------------------------------------------
# calibration — a fixed machine-speed probe every BENCH JSON carries, so
# compare.py can normalise away uniform box-speed differences
# ---------------------------------------------------------------------------

def calibration(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    _check_mode(mode)
    x = jnp.full((256, 256), 1.0 / 256.0, jnp.float32)

    @jax.jit
    def probe(x):
        def body(c, _):
            return c @ x, None
        c, _ = jax.lax.scan(body, x, None, length=32)
        return c.sum()

    t = timer.bench(probe, x, repeats=max(repeats, 3))
    return [_t("calibration_matmul_scan", t,
               "fixed 256x256 matmul scan (machine-speed probe)",
               _fn=probe, _args=(x,), gate=False)]


# ---------------------------------------------------------------------------
# Table 1 — truncated signatures: direct (Alg 1) vs Horner (Alg 2),
# autodiff vs time-reversed exact backward
# ---------------------------------------------------------------------------

_TABLE1_CELLS = {
    "smoke": [(4, 32, 3, 4)],
    "quick": [(16, 64, 4, 6), (16, 128, 8, 5), (16, 256, 16, 4)],
    "full": [(128, 256, 4, 6), (128, 512, 8, 5), (128, 1024, 16, 4)],
}


def table1_signatures(mode: str = "quick", repeats: int = 5) -> List[dict]:
    entries = []
    for (B, L, d, N) in _TABLE1_CELLS[_check_mode(mode)]:
        path = _paths(0, B, L, d, 0.2)
        tag = f"table1_B{B}_L{L}_d{d}_N{N}"
        meta = dict(op="signature", B=B, L=L, d=d, depth=N)

        f_direct = jax.jit(lambda p: signature_direct(p, N))
        f_horner = jax.jit(lambda p: signature(p, N, backend="reference"))
        t_dir = timer.bench(f_direct, path, repeats=repeats)
        t_hor = timer.bench(f_horner, path, repeats=repeats)
        entries.append(_t(f"{tag}_fwd_direct", t_dir, **meta))
        entries.append(_t(f"{tag}_fwd_horner", t_hor,
                          f"speedup_vs_direct={t_dir / t_hor:.2f}x",
                          _fn=f_horner, _args=(path,), **meta))

        g_auto = jax.jit(jax.grad(lambda p: signature_direct(p, N).sum()))
        g_rev = jax.jit(jax.grad(
            lambda p: signature(p, N, backend="reference").sum()))
        t_ga = timer.bench(g_auto, path, repeats=repeats)
        t_gr = timer.bench(g_rev, path, repeats=repeats)
        entries.append(_t(f"{tag}_bwd_autodiff", t_ga, **meta))
        entries.append(_t(f"{tag}_bwd_timereversed", t_gr,
                          f"speedup_vs_autodiff={t_ga / t_gr:.2f}x", **meta))
    return entries


# ---------------------------------------------------------------------------
# Table 2 — signature kernels: row-scan vs wavefront forward, autodiff vs
# exact one-pass backward, plus the Gram engine through every usable backend
# ---------------------------------------------------------------------------

_TABLE2_CELLS = {
    "smoke": [(4, 16, 4)],
    "quick": [(16, 64, 8), (16, 128, 16), (8, 256, 32)],
    "full": [(128, 256, 8), (128, 512, 16), (128, 1024, 32)],
}

_GRAM_CELLS = {
    "smoke": [(4, 12, 3)],
    "quick": [(8, 32, 4)],
    "full": [(32, 128, 8)],
}


def _usable_gram_backends() -> List[str]:
    # approximate feature-map backends answer a different question (an
    # approximation of the Gram); they get their own frontier workload
    backends = [b for b in dispatch.backends_for("gram")
                if not dispatch.get(b).approximate]
    if not dispatch.on_tpu():
        # interpret-mode Pallas timings measure nothing meaningful and
        # dominate CPU wall-clock; smoke_checks covers those for correctness
        backends = [b for b in backends if not dispatch.get(b).needs_tpu]
    # reference first so the other rows can report their speedup against it
    return (["reference"] if "reference" in backends else []) + \
        [b for b in backends if b != "reference"]


def table2_sigkernels(mode: str = "quick", repeats: int = 5) -> List[dict]:
    entries = []
    for (B, L, d) in _TABLE2_CELLS[_check_mode(mode)]:
        kx = _paths(0, B, L, d, 0.1)
        ky = _paths(1, B, L, d, 0.1)
        tag = f"table2_B{B}_L{L}_d{d}"
        meta = dict(op="sigkernel", B=B, L=L, d=d)

        f_scan = jax.jit(lambda x, y: solve_goursat(delta_matrix(x, y)))
        f_wave = jax.jit(
            lambda x, y: solve_goursat_antidiag(delta_matrix(x, y)))
        t_scan = timer.bench(f_scan, kx, ky, repeats=repeats)
        t_wave = timer.bench(f_wave, kx, ky, repeats=repeats)
        entries.append(_t(f"{tag}_fwd_rowscan", t_scan, **meta))
        entries.append(_t(f"{tag}_fwd_wavefront", t_wave,
                          f"speedup_vs_rowscan={t_scan / t_wave:.2f}x",
                          _fn=f_wave, _args=(kx, ky), **meta))

        g_auto = jax.jit(jax.grad(
            lambda x, y: solve_goursat(delta_matrix(x, y)).sum()))
        g_exact = jax.jit(jax.grad(lambda x, y: sigkernel(x, y).sum()))
        t_ga = timer.bench(g_auto, kx, ky, repeats=repeats)
        t_ge = timer.bench(g_exact, kx, ky, repeats=repeats)
        entries.append(_t(f"{tag}_bwd_autodiff", t_ga, **meta))
        entries.append(_t(f"{tag}_bwd_exact_alg4", t_ge,
                          f"speedup_vs_autodiff={t_ga / t_ge:.2f}x", **meta))

    entries.extend(gram_backends(mode=mode, repeats=repeats))
    return entries


def gram_backends(mode: str = "quick", repeats: int = 5,
                  backends=None) -> List[dict]:
    """Gram engine entries: every usable backend × {dense, symmetric}."""
    if backends is None:
        backends = _usable_gram_backends()
    entries = []
    for (B, L, d) in _GRAM_CELLS[_check_mode(mode)]:
        X = _paths(2, B, L, d, 0.1)
        Y = _paths(3, B, L, d, 0.1)
        tag = f"table2_gram_B{B}_L{L}_d{d}"
        meta = dict(op="gram", B=B, L=L, d=d)
        t_ref = None
        for b in backends:
            f = jax.jit(lambda x, y, b=b: sigkernel_gram(
                x, y, backend=b, symmetric=False))
            t = timer.bench(f, X, Y, repeats=repeats)
            derived = "" if t_ref is None else \
                f"speedup_vs_reference={t_ref / t:.2f}x"
            if b == "reference":
                t_ref = t
            # HLO-derived counts for the cheap-to-lower CPU backends; the
            # interpret-mode Pallas rows fall back to the analytic model
            hlo_fn = f if b in ("reference", "antidiag") else None
            entries.append(_t(f"{tag}_dense_{b}", t, derived,
                              _fn=hlo_fn, _args=(X, Y), backend=b, **meta))
        # symmetric fast path: ~half the PDE solves of the dense Kxx
        for b in backends:
            f_sym = jax.jit(lambda x, b=b: sigkernel_gram(x, backend=b))
            t_sym = timer.bench(f_sym, X, repeats=repeats)
            entries.append(_t(f"{tag}_symmetric_{b}", t_sym,
                              backend=b, **meta))
    return entries


# ---------------------------------------------------------------------------
# RBF static-kernel lift — the Δ-from-Gram path (API v1), regression-gated
# from day one: one timed Gram entry per mode + an oracle agreement check
# ---------------------------------------------------------------------------

_RBF_CELLS = {
    "smoke": [(4, 12, 3)],
    "quick": [(8, 32, 4)],
    "full": [(32, 128, 8)],
}


def rbf_lift(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    entries = []
    for (B, L, d) in _RBF_CELLS[_check_mode(mode)]:
        X = _paths(4, B, L, d, 0.3)
        Y = _paths(5, B, L, d, 0.3)
        kernel = RBF(sigma=1.0)
        tag = f"rbf_lift_B{B}_L{L}_d{d}"
        meta = dict(op="gram", B=B, L=L, d=d, static_kernel="rbf")

        f = jax.jit(lambda x, y: sigkernel_gram(
            x, y, static_kernel=kernel, symmetric=False))
        t = timer.bench(f, X, Y, repeats=repeats)
        entries.append(_t(f"{tag}_gram", t, **meta))
        g = jax.jit(jax.grad(lambda x, y: sigkernel_gram(
            x, y, static_kernel=kernel, symmetric=False).sum()))
        entries.append(_t(f"{tag}_gram_grad",
                          timer.bench(g, X, Y, repeats=repeats), **meta))

        # oracle: Δ as the double increment of the pointwise RBF Gram,
        # solved pairwise by the reference row scan
        G = kernel.gram(X[:, None], Y[None, :])
        K_oracle = solve_goursat(delta_from_gram(G))
        np.testing.assert_allclose(f(X, Y), K_oracle, rtol=5e-4, atol=1e-5,
                                   err_msg="rbf lift disagrees with oracle")
        entries.append(_chk(f"{tag}_agreement", **meta))
    return entries


# ---------------------------------------------------------------------------
# ragged Gram — variable-length (lengths=) batches through the Gram engine;
# timed per usable backend and agreement-checked against the per-path
# truncated oracle, so the masked hot path is regression-gated like the
# dense one (see docs/solver_guide.md § Ragged batches)
# ---------------------------------------------------------------------------

_RAGGED_CELLS = {
    "smoke": [(4, 12, 3)],
    "quick": [(8, 32, 4)],
    "full": [(32, 128, 8)],
}


def _ragged_spread(B: int, L: int, reverse: bool = False) -> jax.Array:
    """Deterministic half-to-full length spread — the one policy autotune
    measures ragged keys with, so the bench times what the cache tuned."""
    lens = autotune._ragged_lengths(B, L)
    return lens[::-1] if reverse else lens


def ragged_gram(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    from repro.core.config import TransformPipeline
    cfg = TransformPipeline(time_aug=True)
    entries = []
    for (B, L, d) in _RAGGED_CELLS[_check_mode(mode)]:
        X = _paths(6, B, L, d, 0.1)
        Y = _paths(7, B, L, d, 0.1)
        lx = _ragged_spread(B, L)
        ly = _ragged_spread(B, L, reverse=True)
        tag = f"ragged_gram_B{B}_L{L}_d{d}"
        meta = dict(op="gram", B=B, L=L, d=d, ragged=True)

        t_ref = None
        for b in _usable_gram_backends():
            f = jax.jit(lambda x, y, b=b: sigkernel_gram(
                x, y, backend=b, transforms=cfg, symmetric=False,
                lengths=lx, lengths_y=ly))
            t = timer.bench(f, X, Y, repeats=repeats)
            derived = "" if t_ref is None else \
                f"speedup_vs_reference={t_ref / t:.2f}x"
            if b == "reference":
                t_ref = t
            entries.append(_t(f"{tag}_{b}", t, derived, backend=b, **meta))
        g = jax.jit(jax.grad(lambda x, y: sigkernel_gram(
            x, y, transforms=cfg, symmetric=False,
            lengths=lx, lengths_y=ly).sum()))
        entries.append(_t(f"{tag}_grad",
                          timer.bench(g, X, Y, repeats=repeats), **meta))
        f_sym = jax.jit(lambda x: sigkernel_gram(
            x, transforms=cfg, lengths=lx))
        entries.append(_t(f"{tag}_symmetric",
                          timer.bench(f_sym, X, repeats=repeats), **meta))

        # agreement vs the per-path truncated oracle on a sampled pair set
        # (bitwise for the linear lift).  Only smoke — whose cells are tiny
        # — sweeps EVERY registered backend; quick/full would drag
        # interpret-mode Pallas through big grids for hours on CPU, so they
        # check the usable set (same policy as smoke_checks vs gram timing)
        agree_backends = [
            b for b in dispatch.backends_for("gram")
            if not dispatch.get(b).approximate] if mode == "smoke" \
            else _usable_gram_backends()
        lx_np, ly_np = np.asarray(lx), np.asarray(ly)
        pairs = [(i, (i + 1) % B) for i in range(min(B, 4))]
        for b in agree_backends:
            K = sigkernel_gram(X, Y, backend=b, transforms=cfg,
                               symmetric=False, lengths=lx, lengths_y=ly)
            for (i, j) in pairs:
                want = sigkernel_gram(
                    X[i:i + 1, :lx_np[i]], Y[j:j + 1, :ly_np[j]],
                    backend=b, transforms=cfg, symmetric=False)
                np.testing.assert_allclose(
                    float(K[i, j]), float(want[0, 0]), rtol=1e-6,
                    err_msg=f"ragged gram {b} disagrees with truncated "
                            f"oracle at pair ({i},{j})")
            entries.append(_chk(f"{tag}_agreement_{b}", backend=b, **meta))
    return entries


# ---------------------------------------------------------------------------
# distributed / streaming Gram — the PR6 engine: streaming reduce vs dense
# sum (timed + agreement-checked, forward and gradient), plus shard-count
# invariance of sigkernel_gram_sharded on the devices of this process.
# ---------------------------------------------------------------------------

_DISTGRAM_CELLS = {
    "smoke": [(6, 12, 3, 2)],
    "quick": [(16, 32, 4, 4)],
    "full": [(64, 128, 8, 8)],
}

def distributed_gram(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    entries = []
    for (B, L, d, rb) in _DISTGRAM_CELLS[_check_mode(mode)]:
        X = _paths(8, B, L, d, 0.1)
        Y = _paths(9, B, L, d, 0.1)
        tag = f"distgram_B{B}_L{L}_d{d}"
        meta = dict(op="gram_reduce", B=B, L=L, d=d, row_block=rb)

        f_dense = jax.jit(
            lambda x, y: sigkernel_gram(x, y, symmetric=False).sum())
        f_stream = jax.jit(lambda x, y: sigkernel_gram_reduce(
            x, y, row_block=rb))
        t_dense = timer.bench(f_dense, X, Y, repeats=repeats)
        t_stream = timer.bench(f_stream, X, Y, repeats=repeats)
        entries.append(_t(f"{tag}_reduce_dense", t_dense, **meta))
        entries.append(_t(f"{tag}_reduce_stream", t_stream,
                          f"vs_dense={t_dense / t_stream:.2f}x", **meta))
        g_stream = jax.jit(jax.grad(lambda x, y: sigkernel_gram_reduce(
            x, y, row_block=rb), argnums=(0, 1)))
        entries.append(_t(f"{tag}_reduce_stream_grad",
                          timer.bench(g_stream, X, Y, repeats=repeats),
                          **meta))
        # symmetric streaming: upper-triangle pairs with 2/1/0 weights
        f_sym = jax.jit(lambda x: sigkernel_gram_reduce(x, row_block=rb))
        entries.append(_t(f"{tag}_reduce_stream_symmetric",
                          timer.bench(f_sym, X, repeats=repeats), **meta))

        # agreement: streaming == dense oracle, values and gradients
        np.testing.assert_allclose(
            float(f_stream(X, Y)), float(f_dense(X, Y)), rtol=1e-5,
            err_msg="streaming reduce disagrees with dense sum")
        gx, _ = g_stream(X, Y)
        gx_d = jax.grad(lambda x: sigkernel_gram(
            x, Y, symmetric=False).sum())(X)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                                   rtol=1e-4, atol=1e-6,
                                   err_msg="streaming grad disagrees")
        np.testing.assert_allclose(
            float(f_sym(X)), float(sigkernel_gram(X).sum()), rtol=1e-5,
            err_msg="symmetric streaming reduce disagrees")
        entries.append(_chk(f"{tag}_agreement", **meta))

    # shard-count invariance of the sharded engine, in this process on the
    # devices it has (one chip: a 1-device mesh; a 4-chip host or a
    # simulated host mesh: 1 vs 4 vs 8).  A mismatch raises.
    from repro.core.gram import sigkernel_gram_sharded
    from repro.launch.mesh import make_gram_mesh
    B, L, d, _ = _DISTGRAM_CELLS[_check_mode(mode)][0]
    X = _paths(10, B, L, d, 0.1)
    Y = _paths(11, B + 1, L, d, 0.1)
    want = np.asarray(sigkernel_gram(X, Y, symmetric=False))
    n_dev = len(jax.devices())
    counts = sorted({1, n_dev} | {n for n in (4, 8) if n <= n_dev})
    for n in counts:
        K = sigkernel_gram_sharded(X, Y, mesh=make_gram_mesh(n))
        np.testing.assert_allclose(
            np.asarray(K), want, rtol=1e-5, atol=1e-6,
            err_msg=f"sharded Gram on {n} devices disagrees")
    Ks = np.asarray(sigkernel_gram_sharded(X, mesh=make_gram_mesh(n_dev)))
    np.testing.assert_allclose(Ks, Ks.T, rtol=1e-6, atol=1e-7,
                               err_msg="sharded symmetric Gram not symmetric")
    entries.append(_chk("distgram_mesh_invariance", f"devices={counts}",
                        op="gram_sharded", B=B, L=L, d=d))
    return entries


# ---------------------------------------------------------------------------
# Table 3 — log-signatures: epilogue cost per mode + compression ratio
# ---------------------------------------------------------------------------

_TABLE3_CELLS = {
    "smoke": [(4, 32, 3, 3)],
    "quick": [(16, 64, 4, 6), (16, 128, 8, 5), (16, 256, 16, 4)],
    "full": [(128, 256, 4, 6), (128, 512, 8, 5), (128, 1024, 16, 4)],
}


def table3_logsignatures(mode: str = "quick", repeats: int = 5) -> List[dict]:
    entries = []
    for (B, L, d, N) in _TABLE3_CELLS[_check_mode(mode)]:
        path = _paths(0, B, L, d, 0.2)
        tag = f"table3_B{B}_L{L}_d{d}_N{N}"
        meta = dict(op="logsignature", B=B, L=L, d=d, depth=N)
        ratio = f"compress={logsig_dim(d, N)}/{sig_dim(d, N)}"

        f_sig = jax.jit(lambda p: signature(p, N, backend="reference"))
        t_sig = timer.bench(f_sig, path, repeats=repeats)
        entries.append(_t(f"{tag}_signature", t_sig, ratio, **meta))

        for lmode in ("lyndon", "brackets", "expand"):
            f_ls = jax.jit(lambda p, m=lmode: logsignature(
                p, N, mode=m, backend="reference"))
            t_ls = timer.bench(f_ls, path, repeats=repeats)
            entries.append(_t(
                f"{tag}_logsig_{lmode}", t_ls,
                f"epilogue_x{t_ls / max(t_sig, 1e-12):.2f}", **meta))

        f_grad = jax.jit(jax.grad(
            lambda p: logsignature(p, N, backend="reference").sum()))
        entries.append(_t(f"{tag}_logsig_grad",
                          timer.bench(f_grad, path, repeats=repeats), **meta))
    return entries


# ---------------------------------------------------------------------------
# Figure 1 / Figure 2 sweeps — runtime vs truncation level / stream length
# ---------------------------------------------------------------------------

def fig1_truncation_sweep(mode: str = "quick", repeats: int = 3
                          ) -> List[dict]:
    """Signature runtime vs truncation level (paper: B=32, L=1024, d=5)."""
    if _check_mode(mode) == "smoke":
        return []
    B, L, d = (8, 128, 5) if mode == "quick" else (32, 1024, 5)
    path = _paths(0, B, L, d, 0.2)
    entries = []
    for N in range(2, 8):
        f_h = jax.jit(lambda p, N=N: signature(p, N, backend="reference"))
        f_d = jax.jit(lambda p, N=N: signature_direct(p, N))
        g_h = jax.jit(jax.grad(
            lambda p, N=N: signature(p, N, backend="reference").sum()))
        t_h = timer.bench(f_h, path, repeats=repeats)
        t_d = timer.bench(f_d, path, repeats=repeats)
        t_g = timer.bench(g_h, path, repeats=repeats)
        meta = dict(op="signature", B=B, L=L, d=d, depth=N)
        entries.append(_t(f"fig1_N{N}_fwd_horner", t_h,
                          f"direct/horner={t_d / t_h:.2f}", **meta))
        entries.append(_t(f"fig1_N{N}_bwd", t_g, **meta))
    return entries


def fig2_length_sweep(mode: str = "quick", repeats: int = 3) -> List[dict]:
    """Sig-kernel runtime vs stream length (paper: B=32, d=5)."""
    if _check_mode(mode) == "smoke":
        return []
    B, d = (8, 5) if mode == "quick" else (32, 5)
    lengths = [32, 64, 128, 256] if mode == "quick" else \
        [128, 256, 512, 1024, 2048]
    entries = []
    for L in lengths:
        kx = _paths(0, B, L, d, 0.1)
        ky = _paths(1, B, L, d, 0.1)
        f_wave = jax.jit(
            lambda x, y: solve_goursat_antidiag(delta_matrix(x, y)))
        g_exact = jax.jit(jax.grad(lambda x, y: sigkernel(x, y).sum()))
        t_f = timer.bench(f_wave, kx, ky, repeats=repeats)
        t_g = timer.bench(g_exact, kx, ky, repeats=repeats)
        meta = dict(op="sigkernel", B=B, L=L, d=d)
        entries.append(_t(f"fig2_L{L}_fwd", t_f,
                          f"per_pair_us={t_f / B * 1e6:.1f}", **meta))
        entries.append(_t(f"fig2_L{L}_bwd_exact", t_g, **meta))
    return entries


# ---------------------------------------------------------------------------
# §3.4 gradient accuracy — exact one-pass backward vs the second-PDE
# approximation of [30]
# ---------------------------------------------------------------------------

_GRADACC_CELLS = {
    "smoke": ([4, 8], [0, 1]),
    "quick": ([4, 8, 16], [0, 1]),
    "full": ([4, 8, 16, 32, 64], [0, 1, 2]),
}


def grad_accuracy(mode: str = "quick", repeats: int = 0) -> List[dict]:
    del repeats  # deterministic accuracy study, nothing to repeat
    lengths, lams = _GRADACC_CELLS[_check_mode(mode)]
    entries = []
    for L in lengths:
        for lam in lams:
            x = _paths(0, 4, L, 3, 0.3)
            y = _paths(1, 4, L, 3, 0.3)
            delta = delta_matrix(x, y)
            grid = solve_goursat(delta, lam, lam, return_grid=True)
            gbar = jnp.ones(delta.shape[:-2])
            d_true = jax.grad(
                lambda d: solve_goursat(d, lam, lam).sum())(delta)
            d_exact = solve_goursat_grad(delta, grid, gbar, lam, lam)
            d_approx = solve_goursat_grad_pde_approx(
                delta, grid, gbar, lam, lam)
            scale = float(jnp.abs(d_true).max())
            e_exact = float(jnp.abs(d_exact - d_true).max()) / scale
            e_approx = float(jnp.abs(d_approx - d_true).max()) / scale
            meta = dict(op="sigkernel_grad", L=L, lam=lam)
            entries.append(_acc(f"gradacc_L{L}_lam{lam}_exact", e_exact,
                                f"rel_err={e_exact:.2e}", **meta))
            entries.append(_acc(
                f"gradacc_L{L}_lam{lam}_pde_approx", e_approx,
                f"rel_err={e_approx:.2e}", gate=False, **meta))
    return entries


# ---------------------------------------------------------------------------
# smoke checks — tiny shapes through EVERY registered backend (forward +
# grad + the symmetric pair-solve budget); any dispatch regression fails
# here in seconds.  Correctness only: no timing entries.
# ---------------------------------------------------------------------------

def smoke_checks(mode: str = "smoke", repeats: int = 1) -> List[dict]:
    del mode, repeats
    B, L, d = 3, 8, 2
    X = _paths(0, B, L, d, 0.1)
    Y = _paths(1, B, L, d, 0.1)
    entries = []
    K_ref = sigkernel_gram(X, Y, backend="reference", symmetric=False)
    for b in dispatch.backends_for("gram"):
        if dispatch.get(b).approximate:
            # feature-map backends approximate K_ref, they don't match it
            # within exact tolerances — checked separately below
            continue
        K = sigkernel_gram(X, Y, backend=b, symmetric=False)
        np.testing.assert_allclose(K, K_ref, rtol=5e-4, atol=1e-5,
                                   err_msg=f"smoke: {b} disagrees")
        g = jax.grad(
            lambda q: sigkernel_gram(q, Y, backend=b,
                                     symmetric=False).sum())(X)
        assert np.isfinite(np.asarray(g)).all(), \
            f"smoke: {b} grad not finite"
        entries.append(_chk(f"smoke_gram_{b}", backend=b))
    # approximate feature-map backends: finite + in the right ballpark of
    # the exact Gram (the frontier workload measures the error precisely),
    # with a differentiable path and — for rff — zero PDE pair-solves
    from repro.core.features import FeatureConfig
    for b, feats in (("rff", FeatureConfig("rff", rank=128, depth=4)),
                     ("nystroem", FeatureConfig("nystroem", rank=B))):
        with dispatch.count_pair_solves() as c:
            Ka = sigkernel_gram(X, Y, backend=b, symmetric=False,
                                features=feats)
        rel = float(np.abs(np.asarray(Ka) - np.asarray(K_ref)).max()
                    / np.abs(np.asarray(K_ref)).max())
        assert rel < 0.5, f"smoke: {b} rel err {rel:.2f} out of ballpark"
        if b == "rff":
            assert c.total == 0, f"smoke: rff issued {c.total} PDE solves"
        ga = jax.grad(lambda q: sigkernel_gram(
            q, Y, backend=b, symmetric=False, features=feats).sum())(X)
        assert np.isfinite(np.asarray(ga)).all(), \
            f"smoke: {b} grad not finite"
        entries.append(_chk(f"smoke_gram_{b}",
                            f"rel_err={rel:.2e};solves={c.total}",
                            backend=b))
    with dispatch.count_pair_solves() as c:
        sigkernel_gram(X, backend="pallas_fused")
    budget = B * (B + 1) // 2
    assert c.total <= budget, (c.total, budget)
    entries.append(_chk("smoke_symmetric_pair_solves",
                        f"solves={c.total}<=budget={budget}"))
    for b in dispatch.backends_for("sigkernel"):
        k = sigkernel(X, Y, backend=b)
        np.testing.assert_allclose(
            k, sigkernel(X, Y, backend="reference"), rtol=5e-4, atol=1e-5,
            err_msg=f"smoke: sigkernel {b} disagrees")
        entries.append(_chk(f"smoke_sigkernel_{b}", backend=b))
    return entries


# ---------------------------------------------------------------------------
# accuracy-vs-speed frontier — the approximate feature-map backends
# (rff / nystroem) swept over rank, each point measured for wall clock AND
# relative Frobenius error against the exact Gram, then persisted via
# autotune.tune_frontier so backend="auto" + error_budget= can legally pick
# the cheapest approximation that fits the caller's budget
# ---------------------------------------------------------------------------

#: (gram key shape, rank sweep) per mode — key shape as autotune.cache_key
#: documents it: (Bx, By, nx, ny, d)
_FRONTIER_CELLS = {
    "smoke": ((4, 4, 12, 12, 3), (8, 32)),
    "quick": ((8, 8, 32, 32, 4), (8, 32, 128)),
    "full": ((32, 32, 128, 128, 8), (32, 128, 512)),
}


def approx_frontier(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    """Frontier entries: one timed + one accuracy row per (method, rank).

    Timings are ``gate=False`` — approximation wall clock at bench shapes
    is dominated by fixed overheads and too noisy to gate — but the
    relative-error rows are gated: the estimators are deterministic (fixed
    feature keys), so an error regression is a real math regression.  The
    sweep also *persists* the frontier (``force=True`` re-measures every
    run), which is what arms :func:`repro.core.dispatch.resolve_approx`
    for this shape bucket on this machine.
    """
    shape, ranks = _FRONTIER_CELLS[_check_mode(mode)]
    entry = autotune.tune_frontier("gram", shape, ranks=ranks,
                                   repeats=repeats, force=True)
    bshape = autotune.key_shape("gram", shape)
    meta = dict(op="gram", shape=list(bshape))
    entries = [_t("approx_frontier_exact", entry["exact_seconds"],
                  f"backend={entry['exact_backend']}", gate=False, **meta)]
    for p in entry["frontier"]:
        tag = f"approx_frontier_{p['backend']}_r{p['rank']}"
        entries.append(_t(
            f"{tag}_time", p["seconds"],
            f"vs_exact={entry['exact_seconds'] / p['seconds']:.2f}x",
            gate=False, backend=p["backend"], rank=p["rank"], **meta))
        entries.append(_acc(
            f"{tag}_rel_err", p["rel_err"], f"rel_err={p['rel_err']:.2e}",
            backend=p["backend"], rank=p["rank"], **meta))
    # budget round-trip on the freshly-persisted frontier.  gate=False: at
    # tiny shapes no point may beat the exact engine's wall clock, and
    # "None (exact wins)" is then the *correct* answer, not a regression.
    found = autotune.lookup_budget("gram", shape, "float32", 0.5)
    entries.append(_chk("approx_frontier_budget_lookup",
                        f"budget=0.5->{found}", gate=False, **meta))
    return entries


# ---------------------------------------------------------------------------
# discretisation frontier — scheme order × grid coarseness × interior
# precision swept by autotune.tune_scheme_frontier: every point is the EXACT
# engine under a different GridConfig, measured for wall clock and relative
# Frobenius error against the order-1 fine-grid f32 baseline, then persisted
# so backend="auto" + error_budget= can legally trade discretisation for
# speed (dispatch.resolve_scheme)
# ---------------------------------------------------------------------------

#: gram key shape per mode, as autotune.cache_key documents it
_SCHEME_CELLS = {
    "smoke": (4, 4, 12, 12, 3),
    "quick": (8, 8, 32, 32, 4),
    "full": (16, 16, 128, 128, 8),
}

#: the PR acceptance budget: order-2 on the 2x-coarser grid must match the
#: order-1 fine-grid Gram within this relative Frobenius error
_SCHEME_COARSE_BUDGET = 0.05


def scheme_frontier(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    """Frontier entries: one timed + one accuracy row per discretisation.

    Timings are ``gate=False`` (fixed overheads dominate at bench shapes)
    but the relative-error rows are gated: every point is deterministic
    exact-engine arithmetic, so an error regression is a real math
    regression.  The order-2 coarse-grid point additionally carries a hard
    in-run budget assert — the scheme's selling point is matching order-1
    accuracy at a quarter of the cells, and this is where that claim is
    continuously measured.  The sweep persists the frontier (force=True),
    arming :func:`repro.core.dispatch.resolve_scheme` for this shape
    bucket on this machine.
    """
    shape = _SCHEME_CELLS[_check_mode(mode)]
    entry = autotune.tune_scheme_frontier("gram", shape, repeats=repeats,
                                          force=True)
    bshape = autotune.key_shape("gram", shape)
    meta = dict(op="gram", shape=list(bshape))
    entries = [_t("scheme_frontier_exact", entry["exact_seconds"],
                  f"backend={entry['exact_backend']}", gate=False, **meta)]
    coarse_o2 = None
    for p in entry["scheme_frontier"]:
        dt = "bf16" if p["interior_dtype"] == "bfloat16" else "f32"
        tag = f"scheme_frontier_{p['scheme']}_c{p['coarsen']}_{dt}"
        entries.append(_t(
            f"{tag}_time", p["seconds"],
            f"vs_exact={entry['exact_seconds'] / p['seconds']:.2f}x",
            gate=False, scheme=p["scheme"], coarsen=p["coarsen"],
            interior_dtype=p["interior_dtype"], **meta))
        entries.append(_acc(
            f"{tag}_rel_err", p["rel_err"], f"rel_err={p['rel_err']:.2e}",
            scheme=p["scheme"], coarsen=p["coarsen"],
            interior_dtype=p["interior_dtype"], **meta))
        if (p["scheme"], p["coarsen"], p["interior_dtype"]) == \
                ("order2", 1, "float32"):
            coarse_o2 = p
    assert coarse_o2 is not None, "order2/coarsen=1/f32 point did not run"
    assert coarse_o2["rel_err"] <= _SCHEME_COARSE_BUDGET, (
        f"order-2 on the 2x-coarser grid misses the order-1 fine baseline "
        f"by rel_err={coarse_o2['rel_err']:.2e} "
        f"(budget {_SCHEME_COARSE_BUDGET})")
    entries.append(_chk(
        "scheme_frontier_order2_coarse_budget",
        f"rel_err={coarse_o2['rel_err']:.2e}<={_SCHEME_COARSE_BUDGET}",
        **meta))
    # budget round-trip on the freshly-persisted frontier.  gate=False: at
    # tiny shapes no point may beat the baseline's wall clock, and "None
    # (order-1 fine wins)" is then the correct answer, not a regression.
    found = autotune.lookup_scheme_budget("gram", shape, "float32",
                                          _SCHEME_COARSE_BUDGET)
    entries.append(_chk("scheme_frontier_budget_lookup",
                        f"budget={_SCHEME_COARSE_BUDGET}->{found}",
                        gate=False, **meta))
    return entries


# ---------------------------------------------------------------------------
# autotune round-trip — tune the smoke shapes, then verify backend="auto"
# with a warm cache is never slower than the worst fixed backend
# ---------------------------------------------------------------------------

#: per-op key shapes the smoke suite tunes (see autotune.cache_key)
_AUTOTUNE_SMOKE_SHAPES: Dict[str, tuple] = {
    "sigkernel": (24, 24, 3),
    "gram": (4, 4, 12, 12, 3),
}


def autotune_auto(mode: str = "smoke", repeats: int = 2) -> List[dict]:
    del mode
    if not autotune.enabled():
        return [_chk("autotune_disabled",
                     "REPRO_DISABLE_AUTOTUNE set; skipped", gate=False)]
    entries = []
    for op, shape in _AUTOTUNE_SMOKE_SHAPES.items():
        winner = autotune.tune(op, shape, repeats=repeats, force=True)
        record = autotune.cache_entry(op, shape)
        times = record["timings"]
        bshape = autotune.key_shape(op, shape)
        for b, t in sorted(times.items()):
            entries.append(_t(f"autotune_{op}_{b}", t, op=op,
                              shape=list(bshape), backend=b))

        key = jax.random.PRNGKey(7)
        if op == "gram":
            Bx, By, nx, ny, d = bshape
            Xa = jax.random.normal(key, (Bx, nx + 1, d)) * 0.1
            Ya = jax.random.normal(jax.random.PRNGKey(8),
                                   (By, ny + 1, d)) * 0.1
            f = jax.jit(lambda x, y: sigkernel_gram(
                x, y, backend="auto", symmetric=False))
        else:
            nx, ny, d = bshape
            Xa = jax.random.normal(key, (8, nx + 1, d)) * 0.1
            Ya = jax.random.normal(jax.random.PRNGKey(8),
                                   (8, ny + 1, d)) * 0.1
            f = jax.jit(lambda x, y: sigkernel(x, y, backend="auto"))
        t_auto = timer.bench(f, Xa, Ya, repeats=repeats)
        worst = max(times.values())
        # the acceptance contract: warm-cache auto never loses to the worst
        # fixed backend (2x + 5ms of slack absorbs CI timer noise)
        assert t_auto <= worst * 2.0 + 5e-3, (
            f"auto ({t_auto * 1e6:.1f}us) slower than worst fixed backend "
            f"({worst * 1e6:.1f}us) for op={op} despite a warm cache")
        entries.append(_t(f"autotune_{op}_auto", t_auto,
                          f"winner={winner};worst_fixed={worst * 1e6:.1f}us",
                          op=op, shape=list(bshape)))
        entries.append(_chk(f"autotune_{op}_winner", f"winner={winner}",
                            op=op))
    return entries


# ---------------------------------------------------------------------------
# streaming Path engine — incremental append+query vs full recompute
# ---------------------------------------------------------------------------

_PATH_CELLS = {
    "smoke": [(64, 3, 3)],
    "quick": [(256, 4, 4)],
    "full": [(1024, 4, 5), (4096, 3, 4)],
}


def path_update(mode: str = "smoke", repeats: int = 3) -> List[dict]:
    """Streaming serving pattern: one-tick append + full-signature query.

    ``incremental`` is the ``repro.Path`` engine (O(chunk) scan + one Chen
    combine against the prefix store); ``full_recompute`` is what serving
    had to do before this subsystem existed — re-scan all L+1 points per
    tick.  The agreement entry pins the two to each other.  The timed
    appends run at a pre-grown capacity so they exercise the steady-state
    warm trace, never the (rare, bounded) growth retrace.
    """
    from repro.stream import Path

    entries = []
    for (L, d, N) in _PATH_CELLS[_check_mode(mode)]:
        pts = _paths(0, 1, L, d, 0.2)[0]
        tick = _paths(1, 1, 1, d, 0.2)[0]
        tag = f"path_update_L{L}_d{d}_N{N}"
        meta = dict(op="path_update", L=L, d=d, depth=N)

        base = Path.from_points(pts, N).update(tick)   # pre-grow + warm

        def append_query(p, t):
            return p.update(t).signature()

        t_inc = timer.bench(append_query, base, tick, repeats=repeats)
        entries.append(_t(f"{tag}_incremental", t_inc, **meta))

        full = jnp.concatenate([pts, tick, tick])
        f_full = jax.jit(lambda pp: signature(pp, N, backend="reference"))
        t_full = timer.bench(f_full, full, repeats=repeats)
        entries.append(_t(
            f"{tag}_full_recompute", t_full,
            f"speedup_incremental={t_full / t_inc:.2f}x",
            _fn=f_full, _args=(full,), **meta))

        got = append_query(base, tick)
        want = f_full(full)
        denom = max(float(jnp.abs(want).max()), 1e-30)
        rel = float(jnp.abs(got - want).max()) / denom
        entries.append(_acc(f"{tag}_agreement", rel,
                            "incremental vs full recompute", **meta))
        assert rel < 5e-5, f"Path incremental drifted from recompute: {rel}"
    return entries

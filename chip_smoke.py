"""Smoke run of the library's main paths on a TPU, through the public API.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded Gram on a 4-chip host only

One chip runs three phases in this one process:

* ``mmd``: Sig-MMD generator training — a few jitted SGD steps of
  ``SigKernel.mmd2`` at 128 paths per side, L=128, d=3 with time
  augmentation, dyadic order (1, 1), once on ``backend="pallas"`` and once
  on ``backend="pallas_fused"``;
* ``signature``: ``signature`` / ``logsignature`` forward and gradient at
  B=128, L=1024, d=5, depth 5 on the Pallas Horner kernel;
* ``serving``: ``SigFeatureServer`` over 64 streams — ticks, flushes, then
  window queries.

Every phase checks values and gradients against ``backend="reference"``
under ``jax.default_matmul_precision("highest")`` (on a subset where the
reference's memory would not fit), and every phase that runs a Pallas
kernel asserts that its compiled HLO holds a ``tpu_custom_call``.  The last
line of stdout is ``{"ok": true, "device": {...}}``.  With no TPU, or when a
check fails, the script exits non-zero without printing it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HIGHEST = "highest"


def log(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(RuntimeError):
    pass


def require(cond, msg: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not cond:
        raise CheckFailed(msg)


def peak_bytes() -> list:
    return [d.memory_stats().get("peak_bytes_in_use") for d in jax.devices()]


def compile_on_chip(name: str, fn, *args, pallas: bool = True):
    """jit + compile ``fn`` for ``args``; with ``pallas`` assert the program
    holds a compiled Mosaic kernel.  Returns the compiled executable."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    if pallas:
        n = compiled.as_text().count("tpu_custom_call")
        require(n > 0, f"{name}: no tpu_custom_call in the compiled HLO")
        log(f"[{name}] compiled in {dt:.3f} s, {n} tpu_custom_call sites")
    else:
        log(f"[{name}] compiled in {dt:.3f} s")
    return compiled


def close(name: str, got, want, tol: float, scale=None) -> None:
    """max |got − want| ≤ tol · scale for every leaf, else raise; ``scale``
    defaults to the leaf's max |want|."""
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        require(g.shape == w.shape, f"{name}: shape {g.shape} != {w.shape}")
        require(np.all(np.isfinite(g)), f"{name}: non-finite values")
        err = float(np.max(np.abs(g - w)))
        scale = float(np.max(np.abs(w))) if scale is None else scale
        log(f"[{name}] max abs err {err:.3e} (scale {scale:.3e})")
        require(err <= tol * scale, f"{name}: error {err} > {tol} * {scale}")


# ---------------------------------------------------------------------------
# phase 1: Sig-MMD generator training
# ---------------------------------------------------------------------------

def generate(theta, z):
    """Linear-SDE generator: noise (B, L-1, d) -> paths (B, L, d) from 0."""
    dt = 1.0 / z.shape[1]
    inc = theta["drift"] * dt + (z @ theta["vol"]) * jnp.sqrt(dt)
    path = jnp.cumsum(inc, axis=1)
    return jnp.concatenate([jnp.zeros_like(path[:, :1]), path], axis=1)


def mmd_loss(repro, backend: str):
    """``loss(theta, noise, data)``: sig-kernel MMD² of generated paths."""
    sk = repro.SigKernel(transforms=repro.TransformPipeline(time_aug=True),
                         grid=repro.GridConfig(1, 1), backend=backend)
    return lambda th, z, y: sk.mmd2(generate(th, z), y)


def sgd_step(loss, lr: float):
    def step(th, z, y):
        val, g = jax.value_and_grad(loss)(th, z, y)
        return jax.tree_util.tree_map(lambda p, q: p - lr * q, th, g), val
    return step


def mmd_phase(repro, backend: str, seed: int, B: int = 128, L: int = 128,
              d: int = 3, steps: int = 3, lr: float = 0.05,
              n: int = 16) -> None:
    from repro.data.synthetic import gbm_paths
    k_data, k_vol, k_noise = jax.random.split(jax.random.PRNGKey(seed), 3)
    Y = gbm_paths(k_data, B, L, d)
    theta = {"drift": jnp.zeros((d,)),
             "vol": 0.1 * jnp.eye(d) + 0.02 * jax.random.normal(k_vol, (d, d))}
    noises = jax.random.normal(k_noise, (steps, B, L - 1, d))
    loss = mmd_loss(repro, backend)
    step = sgd_step(loss, lr)

    name = f"mmd/{backend}"
    compiled = compile_on_chip(name, step, theta, noises[0], Y)
    vals = []
    for i in range(steps):
        t0 = time.perf_counter()
        theta, val = compiled(theta, noises[i], Y)
        val = float(val)
        log(f"[{name}] step {i}: mmd2 {val:.6e}, "
            f"{time.perf_counter() - t0:.3f} s")
        vals.append(val)
    require(np.all(np.isfinite(vals)), f"{name}: non-finite loss {vals}")
    require(np.all(np.isfinite(np.asarray(theta["vol"]))), name)

    # reference check on n paths per side: loss value, its gradient, and
    # an n x n sub-Gram with the gradient of a weighted sum of it
    z, y = noises[0][:n], Y[:n]
    X = generate(theta, z)
    sk_ref = repro.SigKernel(transforms=repro.TransformPipeline(time_aug=True),
                             grid=repro.GridConfig(1, 1), backend="reference")
    # MMD² is a difference of Gram means: its rounding scales with the Grams
    k_scale = max(float(jnp.max(jnp.abs(sk_ref.gram(a, b))))
                  for a, b in ((X, X), (X, y), (y, y)))
    got = jax.jit(jax.value_and_grad(loss))(theta, z, y)
    want = jax.jit(jax.value_and_grad(mmd_loss(repro, "reference")))(
        theta, z, y)
    close(f"{name} mmd2", got[0], want[0], 1e-4, scale=k_scale)
    close(f"{name} d mmd2/d theta", got[1], want[1], 1e-3)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, n))

    def wsum(b):
        sk = repro.SigKernel(
            transforms=repro.TransformPipeline(time_aug=True),
            grid=repro.GridConfig(1, 1), backend=b)
        return lambda x: jnp.sum(w * sk.gram(x, y))

    gram_vg = compile_on_chip(f"{name} sub-gram",
                              jax.value_and_grad(wsum(backend)), X)
    got = gram_vg(X)
    want = jax.jit(jax.value_and_grad(wsum("reference")))(X)
    close(f"{name} sub-gram sum", got[0], want[0], 1e-4)
    close(f"{name} sub-gram grad", got[1], want[1], 1e-3)


# ---------------------------------------------------------------------------
# phase 2: signature / log-signature features
# ---------------------------------------------------------------------------

def signature_phase(repro, seed: int, B: int = 128, L: int = 1024,
                    d: int = 5, depth: int = 5, n: int = 16) -> None:
    k_path, k_w1, k_w2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    paths = jax.random.normal(k_path, (B, L, d)).cumsum(axis=1) * 0.03
    for cls, k_w in ((repro.Signature, k_w1), (repro.LogSignature, k_w2)):
        name = f"signature/{cls.__name__}"
        feat = cls(depth=depth, backend="pallas")
        ref = cls(depth=depth, backend="reference")
        dim = jax.eval_shape(feat, paths[:1]).shape[-1]
        w = jax.random.normal(k_w, (B, dim))

        def fwd_grad(p, f):
            out = f(p)
            g = jax.grad(lambda q: jnp.sum(w[:q.shape[0]] * f(q)))(p)
            return out, g

        compiled = compile_on_chip(name, fwd_grad, paths, feat)
        t0 = time.perf_counter()
        out, g = jax.block_until_ready(compiled(paths, feat))
        log(f"[{name}] forward+grad {time.perf_counter() - t0:.3f} s, "
            f"features {out.shape}")
        want_out, want_g = jax.jit(fwd_grad)(paths[:n], ref)
        close(f"{name} values", out[:n], want_out, 1e-4)
        close(f"{name} grad", g[:n], want_g, 1e-3)


# ---------------------------------------------------------------------------
# phase 3: tick-stream serving
# ---------------------------------------------------------------------------

def serving_phase(repro, seed: int, n_streams: int = 64, d: int = 3,
                  depth: int = 4, init_len: int = 32, flushes: int = 4,
                  chunks_per_flush: int = 8, chunk: int = 8) -> None:
    from repro.serve import SigFeatureServer
    key = jax.random.PRNGKey(seed)
    steps = jax.random.normal(
        key, (n_streams, init_len + flushes * chunks_per_flush * chunk, d))
    pts = np.asarray(steps.cumsum(axis=1) * 0.05)
    server = SigFeatureServer(depth)
    t0 = time.perf_counter()
    for s in range(n_streams):
        server.open_stream(f"s{s}", pts[s, :init_len])
    log(f"[serving] opened {n_streams} streams in "
        f"{time.perf_counter() - t0:.3f} s")
    pos = init_len
    for f in range(flushes):
        t0 = time.perf_counter()
        for _ in range(chunks_per_flush):
            for s in range(n_streams):
                server.append(f"s{s}", pts[s, pos:pos + chunk])
            pos += chunk
        n = server.flush()
        log(f"[serving] flush {f}: {n} streams, "
            f"{time.perf_counter() - t0:.3f} s")
    require(pos == pts.shape[1], "serving: ticks left unsent")
    stats = server.stats()
    log(f"[serving] stats {json.dumps({k: v for k, v in stats.items() if k != 'trace_counts'})}")
    win = min(64, pos // 2)
    windows = [(0, pos), (pos - win, pos), (3, pos // 2)]
    for s in (0, n_streams // 2, n_streams - 1):
        for i, j in windows:
            got = server.signature(f"s{s}", i, j)
            want = repro.signature(jnp.asarray(pts[s, i:j]), depth,
                                   backend="reference")
            close(f"serving s{s}[{i}:{j}] signature", got, want, 1e-4)
        got = server.logsignature(f"s{s}", pos - win, pos)
        want = repro.logsignature(jnp.asarray(pts[s, pos - win:pos]), depth,
                                  backend="reference")
        close(f"serving s{s} logsignature", got, want, 1e-4)
    # the offline batch signature of the same windows (the Pallas kernel)
    batch = jnp.asarray(pts[:, pos - win:pos])
    offline = compile_on_chip("serving offline", lambda p: repro.signature(
        p, depth, backend="pallas"), batch)(batch)
    online = jnp.stack([server.signature(f"s{s}", pos - win, pos)
                        for s in range(n_streams)])
    close("serving online vs offline", online, offline, 1e-4)


# ---------------------------------------------------------------------------
# four chips: the sharded Gram against one device
# ---------------------------------------------------------------------------

def four_chip_phase(repro, seed: int, B: int = 256, L: int = 128,
                    d: int = 4, row_block: int = 64) -> None:
    from repro.launch.mesh import make_gram_mesh
    require(len(jax.devices()) >= 4, f"need 4 chips, have {jax.devices()}")
    kx, ky, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(kx, (B, L, d)).cumsum(axis=1) * 0.02
    Y = jax.random.normal(ky, (B, L, d)).cumsum(axis=1) * 0.02
    w = jax.random.normal(kw, (B, B))
    mesh = make_gram_mesh(4)
    log(f"[four-chips] mesh {dict(mesh.shape)} over "
        f"{[dv.id for dv in mesh.devices.flat]}")

    def sharded(x, y):
        K = repro.sigkernel_gram_sharded(x, y, mesh=mesh, row_block=row_block,
                                         backend="pallas_fused")
        return jnp.sum(w * K), K

    def single(x, y):
        K = repro.sigkernel_gram(x, y, row_block=row_block,
                                 backend="pallas_fused")
        return jnp.sum(w * K), K

    for name, fn in (("four-chips/sharded", sharded),
                     ("four-chips/single", single)):
        vg = jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)
        compiled = compile_on_chip(name, vg, X, Y)
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(X, Y))
        log(f"[{name}] value+grad {time.perf_counter() - t0:.3f} s, "
            f"peak bytes per device {peak_bytes()}")
        if name.endswith("sharded"):
            (_, K_sh), g_sh = out
            text = compiled.as_text()
            # each device's Gram kernel solves row blocks of its own
            # (B / data, B / model) quarter of the tiles
            nd, nm = mesh.shape["data"], mesh.shape["model"]
            for local in (f"f32[{B // nd},{B // nm}]",
                          f"f32[{row_block},1,{B // nm}]"):
                require(local in text, f"no per-device {local} in the HLO")
            peaks = peak_bytes()[:4]
            log(f"[{name}] per-device peaks after the sharded run {peaks}")
            require(min(peaks) > 0.25 * max(peaks),
                    f"tiles not dealt over all four devices: peaks {peaks}")
        else:
            (_, K_one), g_one = out
    close("four-chips Gram", K_sh, K_one, 1e-5)
    close("four-chips grad", g_sh, g_one, 1e-4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Gram on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2

    import repro
    from repro.core import dispatch
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[setup] {len(jax.devices())} x {dev.device_kind}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")
    for op in ("gram", "sigkernel", "signature"):
        log(f"[setup] backend='auto' resolves to "
            f"{dispatch.resolve('auto', op=op)!r} for op {op!r}")

    if args.four_chips:
        phases = [("four-chips", lambda: four_chip_phase(repro, args.seed))]
    else:
        phases = [
            ("mmd/pallas", lambda: mmd_phase(repro, "pallas", args.seed)),
            ("mmd/pallas_fused",
             lambda: mmd_phase(repro, "pallas_fused", args.seed)),
            ("signature", lambda: signature_phase(repro, args.seed)),
            ("serving", lambda: serving_phase(repro, args.seed)),
        ]
    with jax.default_matmul_precision(HIGHEST):
        for name, run in phases:
            t0 = time.perf_counter()
            run()
            log(f"[{name}] phase done in {time.perf_counter() - t0:.3f} s, "
                f"peak bytes {peak_bytes()}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

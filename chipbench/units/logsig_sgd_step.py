"""Unit: one jitted SGD step of a log-signature feature layer.

The layer mixes the channels of each path by a learned matrix, takes the
depth-N log-signature in Lyndon coordinates through ``repro.LogSignature``
and reads it out linearly; the loss is the mean squared error against
per-path targets (Signatory, arXiv 2001.00706: the log-signature as a
differentiable layer with learned transforms of the stream before it).
The step takes the next batch of paths and targets from a pool made on
the device, computes ``jax.value_and_grad`` and applies the update, all in
one compiled program.

Correctness follows the training rule of ``mmd_sgd_step``: the first
``check_steps`` steps (the warm-up step of set-up and the window's first)
are followed by the plain reference (``chipbench/reference_sig.py``) from
the same parameters and data.  Compared are each step's loss, the first
gradient as the optimizer applied it, the parameters' change over the
check steps, and the first step's features, level by level, so that the
small top levels are held as tightly as the first.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, counts_sig, data, hlo_scopes, reference_sig
from chipbench.units.mmd_sgd_step import moving_leaves, norm_gap, sgd_update

#: the program spans whose device ops the trace readers attribute
SCOPES = ("repro.signature.bwd", "repro.logsig.log", "repro.logsig.project")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def mixed_paths(theta, x, increment_dtype: str):
    """Paths with their channels mixed by ``theta["mix"]``; with
    ``increment_dtype`` below float32 the increments are rounded to it (the
    lower-precision control)."""
    mixed = jnp.matmul(x, theta["mix"], precision=data.HIGHEST)
    if increment_dtype == "float32":
        return mixed
    z = jnp.diff(mixed, axis=1).astype(increment_dtype).astype(jnp.float32)
    return jnp.concatenate([mixed[:, :1], mixed[:, :1]
                            + jnp.cumsum(z, axis=1)], axis=1)


def program_loss(cfg: dict):
    """Mean squared error of the layer, and its features, through the
    public API."""
    import repro
    layer = repro.LogSignature(depth=cfg["depth"], mode=cfg["mode"],
                               backend=cfg["backend"])

    def loss(theta, x, y):
        feats = layer(mixed_paths(theta, x, cfg["increment_dtype"]))
        pred = jnp.matmul(feats, theta["readout"], precision=data.HIGHEST)
        return jnp.mean((pred - y) ** 2), feats
    return loss


def feature_dims(d: int, depth: int) -> list:
    """Lyndon coordinates per level (Witt's formula, via the reference's
    own word list)."""
    return [len(ix) for ix in reference_sig.lyndon_index(d, depth)]


def init_params(key, cfg: dict) -> dict:
    d, m = cfg["channels"], cfg["model"]
    k_mix, k_out = jax.random.split(key)
    n = sum(feature_dims(d, cfg["depth"]))
    return {"mix": jnp.eye(d) + m["mix_jitter"]
            * jax.random.normal(k_mix, (d, d)),
            "readout": m["readout_scale"] * jax.random.normal(k_out, (n,))}


def work(cfg: dict) -> dict:
    """Work of one step by layer, from the configuration's shapes.  The
    step solves no signature-kernel PDE: its PDE forward is nought."""
    return {"pde_fwd": counts.Work(0.0, 0.0),
            "horner": counts_sig.horner(cfg["batch"], cfg["length"] - 1,
                                        cfg["channels"], cfg["depth"])}


class Unit:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list):
        from repro.core import dispatch
        lanes = getattr(dispatch, "count_horner_lanes", None)
        if lanes is None:
            raise RuntimeError(
                "the program has no Horner lane counter "
                "(repro.core.dispatch.count_horner_lanes), so it predates "
                "the deep-signature kernel layout this cell runs")
        if traffic["loop"] != "closed":
            raise ValueError("logsig_sgd_step runs in a closed loop")
        B, L, d = cfg["batch"], cfg["length"], cfg["channels"]
        self.cfg, self.devices = cfg, devices
        self.lr = cfg["learning_rate"]
        self.pool_size = traffic["pool_batches"]
        self.check_steps = traffic["check_steps"]
        k_data, k_target, k_theta = jax.random.split(data.seed_key(seed), 3)

        @jax.jit
        def init(k_data, k_target, k_theta):
            keys = jax.random.split(k_data, self.pool_size)
            pool = jax.vmap(lambda k: data.gbm_paths(
                k, B, L, d, cfg["data"]["mu"], cfg["data"]["sigma"]))(keys)
            targets = jax.random.normal(k_target, (self.pool_size, B))
            return pool, targets, init_params(k_theta, cfg)

        self.pool, self.targets, self.theta0 = init(k_data, k_target,
                                                    k_theta)
        self.loss = program_loss(cfg)
        s0 = jnp.zeros((), jnp.int32)
        with lanes() as fill:
            lowered = jax.jit(self.program_step).lower(
                self.theta0, s0, self.pool, self.targets)
        log(f"[setup] Horner lane fill {fill.fill:.4f}: {fill.paths} paths "
            f"in {fill.total} lanes")
        compiled = lowered.compile()
        self.step = compiled
        self.work = dict(work(cfg), op_names=hlo_scopes.scoped_ops(
            compiled.as_text(), SCOPES))
        self.theta, self.s = self.theta0, s0
        self.check_thetas, self.check_losses = [self.theta0], []
        self.first_features = None
        self.run_step()                              # warm-up unit
        self.losses = []

    def program_step(self, theta, s, pool, targets):
        """The timed program: step ``s`` from ``theta``, traced once."""
        x, y = self.inputs(s, pool, targets)
        (val, feats), grads = jax.value_and_grad(self.loss, has_aux=True)(
            theta, x, y)
        return sgd_update(theta, grads, self.lr), s + 1, val, feats

    def inputs(self, s, pool, targets):
        """Paths and targets of step ``s``."""
        i = s % self.pool_size
        return (jax.lax.dynamic_index_in_dim(pool, i, keepdims=False),
                jax.lax.dynamic_index_in_dim(targets, i, keepdims=False))

    def run_step(self):
        """One step; the first ``check_steps`` keep their parameters and
        loss on the device for the comparison, the first its features."""
        self.theta, self.s, val, feats = jax.block_until_ready(
            self.step(self.theta, self.s, self.pool, self.targets))
        if self.first_features is None:
            self.first_features = feats
        if len(self.check_losses) < self.check_steps:
            self.check_thetas.append(self.theta)
            self.check_losses.append(val)
        return val

    def run(self) -> None:
        """One timed unit: a whole training step, finished on the device."""
        self.losses.append(self.run_step())

    def outcome(self) -> tuple:
        losses = np.asarray([float(v) for v in self.losses])
        return len(losses), int(np.sum(~np.isfinite(losses)))

    def check(self) -> dict:
        """Readings of the program against the reference (frees the step)."""
        while len(self.check_losses) < self.check_steps:
            self.run_step()
        cfg, thetas = self.cfg, self.check_thetas
        self.step = self.theta = None
        theta, ref_losses, ref_grads, ref_levels = thetas[0], [], [], None
        for s in range(self.check_steps):
            x, y = self.inputs(jnp.int32(s), self.pool, self.targets)
            val, grads, levels = reference_sig.value_and_grad(
                theta, x, y, depth=cfg["depth"],
                block=cfg["reference_block"],
                segment=cfg["reference_segment"])
            ref_losses.append(float(val))
            ref_grads.append(grads)
            ref_levels = levels if ref_levels is None else ref_levels
            theta = sgd_update(theta, grads, self.lr)
        # differences of the parameters, taken in float64 on the host
        f64 = [{k: np.asarray(v, np.float64) for k, v in t.items()}
               for t in (*thetas, theta)]
        first_grad = {k: (f64[0][k] - f64[1][k]) / self.lr for k in f64[0]}
        change = {k: f64[-2][k] - f64[0][k] for k in f64[0]}
        ref_change = {k: f64[-1][k] - f64[0][k] for k in f64[0]}
        losses = np.asarray([float(v) for v in self.check_losses])
        moved = moving_leaves(ref_grads[0])
        return {"loss_gap": float(np.max(np.abs(losses - ref_losses)
                                         / np.asarray(ref_losses))),
                "grad_gap": norm_gap(first_grad, ref_grads[0]),
                "change_gap": norm_gap({k: change[k] for k in moved},
                                       {k: ref_change[k] for k in moved}),
                "feature_gap": feature_gap(self.first_features, ref_levels)}


def feature_gap(features, ref_levels: list) -> float:
    """Worst level's ‖features − reference‖ / ‖reference‖ over the paths,
    the Lyndon coordinates grouped by word length."""
    got = np.asarray(features, np.float64)
    gaps, off = [], 0
    for want in ref_levels:
        want = np.asarray(want, np.float64)
        n = want.shape[-1]
        gaps.append(np.linalg.norm(got[:, off:off + n] - want)
                    / np.linalg.norm(want))
        off += n
    return float(max(gaps))

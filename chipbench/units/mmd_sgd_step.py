"""Unit: one jitted SGD step of Sig-MMD generator training.

The step draws fresh generator noise from the seed, takes the next data
batch from a pool made on the device, computes ``jax.value_and_grad`` of
``repro.SigKernel(...).mmd2`` and applies the update, all in one program.

Correctness follows the training rule: the compiled step's first
``check_steps`` steps are the warm-up step of set-up and the window's
first steps (more are run after the window if it held too few), and the
plain reference follows the same steps from the same parameters, noise
and data.  Compared are
each step's loss, against the scale of the Gram means it is a difference
of; the first gradient as the optimizer applied it; and the parameters'
change over the check steps; the last two by the worst leaf.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, data, reference


def sgd_update(theta, grads, lr: float):
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, theta, grads)


def program_loss(cfg: dict):
    """MMD² of generated paths against data, through the public API."""
    import repro
    sk = repro.SigKernel(
        transforms=repro.TransformPipeline(time_aug=cfg["time_aug"]),
        grid=repro.GridConfig(*cfg["dyadic_order"],
                              interior_dtype=cfg["interior_dtype"]),
        backend=cfg["backend"])
    return lambda theta, z, y: sk.mmd2(data.generate(theta, z), y)


def reference_loss(cfg: dict):
    lam1, lam2 = cfg["dyadic_order"]
    return lambda theta, z, y: reference.mmd2_unbiased(
        data.generate(theta, z), y, time_aug=cfg["time_aug"], lam1=lam1,
        lam2=lam2, rows=cfg["reference_rows"])


def norm_gap(got: dict, want: dict) -> float:
    """Worst leaf's |‖got‖ − ‖want‖| over max(‖want‖, median leaf ‖want‖)."""
    g = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
         for k, v in got.items()}
    w = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
         for k, v in want.items()}
    med = statistics.median(w.values())
    return float(np.max([abs(g[k] - w[k]) / max(w[k], med) for k in w]))


def moving_leaves(ref_grad: dict) -> list:
    """Leaves whose reference gradient is not nought to rounding: a norm of
    at least a thousandth of the median leaf's.  The others move under the
    optimizer by round-off alone."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in ref_grad.items()}
    med = statistics.median(norms.values())
    return [k for k, n in norms.items() if n >= 1e-3 * med]


def work(cfg: dict) -> dict:
    """Work of one step by layer, from the configuration's shapes."""
    B, L, d = cfg["paths_per_side"], cfg["length"], cfg["channels"]
    steps, ch = L - 1, d + 1 if cfg["time_aug"] else d
    cells = counts.refined_cells(steps, steps, *cfg["dyadic_order"])
    fwd_pairs, bwd_pairs = counts.mmd2_unbiased_pairs(B, B)
    streams = 2 * counts.stream_bytes(B, steps, ch)
    return {"pde_fwd": counts.pde_forward(fwd_pairs, cells, streams),
            "pde_bwd": counts.pde_backward(
                bwd_pairs, cells, streams, counts.stream_bytes(B, steps, ch))}


class Unit:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list):
        if (cfg["static_kernel"], cfg["estimator"], traffic["loop"]) != (
                "linear", "unbiased", "closed"):
            raise ValueError("mmd_sgd_step runs the linear kernel's "
                             "unbiased MMD² in a closed loop")
        B, L, d = cfg["paths_per_side"], cfg["length"], cfg["channels"]
        self.cfg, self.devices = cfg, devices
        self.lr = cfg["learning_rate"]
        self.pool_size = traffic["pool_batches"]
        self.check_steps = traffic["check_steps"]
        gen = cfg["generator"]
        k_data, k_theta, self.k_noise = jax.random.split(
            data.seed_key(seed), 3)

        @jax.jit
        def init(k_data, k_theta):
            keys = jax.random.split(k_data, self.pool_size)
            pool = jax.vmap(lambda k: data.gbm_paths(
                k, B, L, d, cfg["data"]["mu"], cfg["data"]["sigma"]))(keys)
            theta = data.init_generator(k_theta, d, gen["vol"],
                                        gen["jitter"])
            return pool, theta

        self.pool, self.theta0 = init(k_data, k_theta)
        self.noise_shape = (B, L - 1, d)
        self.loss = program_loss(cfg)
        self.step = jax.jit(self.program_step)
        self.theta, self.s = self.theta0, jnp.zeros((), jnp.int32)
        self.check_thetas, self.check_losses = [self.theta0], []
        self.run_step()                              # warm-up unit
        self.losses = []
        self.work = work(cfg)

    def program_step(self, theta, s, pool, k_noise):
        """The timed program: step ``s`` from ``theta``, traced once."""
        z, y = self.inputs(s, pool, k_noise)
        val, grads = jax.value_and_grad(self.loss)(theta, z, y)
        return sgd_update(theta, grads, self.lr), s + 1, val

    def inputs(self, s, pool, k_noise):
        """Noise and data batch of step ``s`` (traced inside the step)."""
        z = jax.random.normal(jax.random.fold_in(k_noise, s),
                              self.noise_shape)
        y = jax.lax.dynamic_index_in_dim(pool, s % self.pool_size,
                                         keepdims=False)
        return z, y

    def run_step(self):
        """One step; the first ``check_steps`` keep their parameters and
        loss on the device for the comparison."""
        self.theta, self.s, val = jax.block_until_ready(
            self.step(self.theta, self.s, self.pool, self.k_noise))
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
        pool, thetas = self.pool, self.check_thetas
        self.step = self.theta = None
        ref = jax.jit(jax.value_and_grad(reference_loss(self.cfg),
                                         has_aux=True))
        theta, ref_losses, scales, ref_grads = thetas[0], [], [], []
        for s in range(self.check_steps):
            z, y = self.inputs(jnp.int32(s), pool, self.k_noise)
            (val, scale), grads = ref(theta, z, y)
            ref_losses.append(float(val))
            scales.append(float(scale))
            ref_grads.append(grads)
            theta = sgd_update(theta, grads, self.lr)
        # differences of the parameters, taken in float64 on the host
        f64 = [{k: np.asarray(v, np.float64) for k, v in t.items()}
               for t in (*thetas, theta)]
        first_grad = {k: (f64[0][k] - f64[1][k]) / self.lr for k in f64[0]}
        change = {k: f64[-2][k] - f64[0][k] for k in f64[0]}
        ref_change = {k: f64[-1][k] - f64[0][k] for k in f64[0]}
        # MMD² is a difference of Gram means, so its rounding scales with
        # them and not with the (small) difference
        losses = [float(v) for v in self.check_losses]
        loss_gap = float(np.max(np.abs(np.subtract(losses, ref_losses))
                                / np.asarray(scales)))
        moved = moving_leaves(ref_grads[0])
        return {"loss_gap": loss_gap,
                "grad_gap": norm_gap(first_grad, ref_grads[0]),
                "change_gap": norm_gap({k: change[k] for k in moved},
                                       {k: ref_change[k] for k in moved})}

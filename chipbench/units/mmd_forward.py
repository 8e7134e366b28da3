"""Unit: one forward unbiased Sig-MMD² between generated paths and data.

The evaluation that sits beside Sig-MMD training (validation, or a
two-sample test between steps): the generator's parameters stay fixed,
each unit draws fresh generator noise from the seed, takes the next data
batch from a pool made on the device and computes
``repro.SigKernel(...).mmd2`` in one jitted program, with no gradient.  It
shares the configuration, the loss and the reference of ``mmd_sgd_step``,
so a change to the backward kernels moves that cell and not this one.

Correctness: the first ``checked_units`` units (the warm-up unit of set-up
and the window's first, more being run after the window if it held too
few) are computed again by the plain reference from the same noise and
data; each loss is compared against the scale of the Gram means it is a
difference of.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.units import mmd_sgd_step


def work(cfg: dict) -> dict:
    """Work of one unit by layer: the forward of an SGD step."""
    return {"pde_fwd": mmd_sgd_step.work(cfg)["pde_fwd"]}


class Unit(mmd_sgd_step.Unit):
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list):
        checked = traffic["checked_units"]
        super().__init__(cfg, dict(traffic, check_steps=checked), seed,
                         devices)
        self.work = work(cfg)

    def program_step(self, theta, s, pool, k_noise):
        """The timed program: the loss of unit ``s``; ``theta`` unchanged."""
        z, y = self.inputs(s, pool, k_noise)
        return theta, s + 1, self.loss(theta, z, y)

    def check(self) -> dict:
        """Loss readings of the program against the reference."""
        while len(self.check_losses) < self.check_steps:
            self.run_step()
        pool, theta = self.pool, self.theta0
        self.step = self.theta = None
        ref = jax.jit(mmd_sgd_step.reference_loss(self.cfg))
        gaps = []
        for s, got in enumerate(self.check_losses):
            z, y = self.inputs(jnp.int32(s), pool, self.k_noise)
            val, scale = ref(theta, z, y)
            gaps.append(abs(float(got) - float(val)) / float(scale))
        return {"loss_gap": float(np.max(gaps))}

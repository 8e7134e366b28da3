"""Unit: one pooled two-sample Gram, forward only, dealt over a device mesh.

Each unit calls ``repro.sigkernel_gram_sharded(Z)`` with ``Y`` omitted (the
symmetric path) on ``make_gram_mesh(chips)``.  The pooled samples cycle
through a pool made on the device, replicated on every chip.

Correctness: once the window has closed, ``checked_pairs`` Gram entries,
spread over a sample of the units, all drawn from the seed, are compared
with the plain reference.  The entries are drawn from every residue class
of the upper-triangle enumeration modulo the device count, so tiles that
every device solved are among them, and each is read at (a, b) and at its
mirrored place (b, a).  Every run compares as many, however many units
its window held.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from chipbench import counts, data, reference


def pooled_sample(key, n: int, length: int, dim: int, spec: dict):
    """n paths: the first half GBM at ``sigma_p``, the rest at ``sigma_q``."""
    kp, kq = jax.random.split(key)
    half = n // 2
    return jnp.concatenate([
        data.gbm_paths(kp, half, length, dim, spec["mu"], spec["sigma_p"]),
        data.gbm_paths(kq, n - half, length, dim, spec["mu"],
                       spec["sigma_q"])])


def work(cfg: dict) -> dict:
    """Work of one Gram by layer, from the configuration's shapes."""
    N, steps = cfg["paths"], cfg["length"] - 1
    ch = cfg["channels"] + 1 if cfg["time_aug"] else cfg["channels"]
    return {"pde_fwd": counts.pde_forward(
        counts.symmetric_gram_pairs(N),
        counts.refined_cells(steps, steps, *cfg["dyadic_order"]),
        counts.stream_bytes(N, steps, ch))}


class Unit:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list):
        if (cfg["static_kernel"], traffic["loop"]) != ("linear", "closed"):
            raise ValueError("gram_sharded runs the linear kernel in a "
                             "closed loop")
        import repro
        from repro.launch.mesh import make_gram_mesh
        N, L, d = cfg["paths"], cfg["length"], cfg["channels"]
        self.cfg, self.devices = cfg, devices
        self.pool_size = traffic["pool_samples"]
        self.kept_max = traffic["checked_units"]
        self.checked_pairs = traffic["checked_pairs"]
        mesh = make_gram_mesh(len(devices), devices=devices)
        replicated = NamedSharding(mesh, PartitionSpec())

        @jax.jit
        def init(key):
            keys = jax.random.split(key, self.pool_size)
            return tuple(jax.lax.with_sharding_constraint(
                pooled_sample(k, N, L, d, cfg["data"]), replicated)
                for k in keys)

        self.pool = init(data.seed_key(seed))
        grid = repro.GridConfig(*cfg["dyadic_order"],
                                interior_dtype=cfg["interior_dtype"])
        transforms = repro.TransformPipeline(time_aug=cfg["time_aug"])
        self.gram = jax.jit(lambda Z: repro.sigkernel_gram_sharded(
            Z, mesh=mesh, grid=grid, transforms=transforms,
            backend=cfg["backend"], row_block=cfg["row_block"]))
        jax.block_until_ready(self.gram(self.pool[0]))   # warm-up unit
        self.units = 0
        self.kept = []                 # (pool index, Gram)
        self.rng = random.Random(seed)

        self.work = work(cfg)

    def run(self) -> None:
        """One timed unit: a whole pooled Gram, finished on the device."""
        i = self.units
        K = jax.block_until_ready(self.gram(self.pool[i % self.pool_size]))
        self.units += 1
        # reservoir sample of the units, drawn from the seed
        if len(self.kept) < self.kept_max:
            self.kept.append((i % self.pool_size, K))
        else:
            j = self.rng.randrange(self.units)
            if j < self.kept_max:
                self.kept[j] = (i % self.pool_size, K)

    def outcome(self) -> tuple:
        bad = sum(not bool(jnp.all(jnp.isfinite(K))) for _, K in self.kept)
        return self.units, bad

    def sample_pairs(self, n: int) -> tuple:
        """``checked_pairs`` draws of (kept unit, a, b): upper-triangle
        pairs taken in turn from each residue class mod the device count."""
        a_all, b_all = np.triu_indices(n)
        D = len(self.devices)
        slot = np.asarray([self.rng.randrange(len(self.kept))
                           for _ in range(self.checked_pairs)])
        t = np.asarray([i % D + D * self.rng.randrange(len(a_all) // D)
                        for i in range(self.checked_pairs)])
        return slot, a_all[t], b_all[t]

    def check(self) -> dict:
        """Readings of the program against the reference (frees the Gram)."""
        self.gram = None
        lam1, lam2 = self.cfg["dyadic_order"]
        slot, a, b = self.sample_pairs(self.cfg["paths"])
        sample = np.asarray([p for p, _ in self.kept])[slot]
        s = jnp.stack([reference.increments(z, self.cfg["time_aug"])
                       for z in self.pool])
        want = np.asarray(jax.jit(reference.pair_kernels, static_argnums=(
            2, 3))(s[sample, a], s[sample, b], lam1, lam2), np.float64)
        grams = [np.asarray(K, np.float64) for _, K in self.kept]
        got_ab = np.asarray([grams[u][i, j] for u, i, j in zip(slot, a, b)])
        got_ba = np.asarray([grams[u][j, i] for u, i, j in zip(slot, a, b)])
        err = np.abs(np.concatenate([got_ab - want, got_ba - want])) \
            / np.abs(np.concatenate([want, want]))
        # np.max keeps a NaN, where the builtin max would drop it
        return {"gram_rel_err": float(np.max(err))}

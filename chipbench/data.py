"""Inputs made from ``--seed``: keys, GBM paths and the linear-SDE generator.

Copied from ``chip_smoke.py`` and ``repro.data.synthetic`` so that the
benchmark's inputs never change with the program under test.  Every
function here is pure JAX and is traced inside the cells' own jitted
set-up calls, so the data is made on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that uses all 64 bits of ``seed``.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a Python int, so
    seeds that differ above bit 32 would collide.
    """
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def gbm_paths(key, batch: int, length: int, dim: int, mu: float = 0.0,
              sigma: float = 0.2) -> jax.Array:
    """Geometric-Brownian-motion paths (batch, length, dim), started at 0."""
    dt = 1.0 / max(length - 1, 1)
    dw = jax.random.normal(key, (batch, length - 1, dim)) * jnp.sqrt(dt)
    logp = jnp.cumsum((mu - 0.5 * sigma ** 2) * dt + sigma * dw, axis=1)
    logp = jnp.concatenate([jnp.zeros((batch, 1, dim)), logp], axis=1)
    return jnp.exp(logp) - 1.0


def init_generator(key, dim: int, vol: float, jitter: float) -> dict:
    """Linear-SDE generator parameters: zero drift, ``vol·I`` plus noise."""
    return {"drift": jnp.zeros((dim,)),
            "vol": vol * jnp.eye(dim)
            + jitter * jax.random.normal(key, (dim, dim))}


def generate(theta: dict, z: jax.Array) -> jax.Array:
    """Linear-SDE generator: noise (B, L-1, d) -> paths (B, L, d) from 0."""
    dt = 1.0 / z.shape[1]
    inc = (theta["drift"] * dt
           + jnp.matmul(z, theta["vol"], precision=HIGHEST) * jnp.sqrt(dt))
    path = jnp.cumsum(inc, axis=1)
    return jnp.concatenate([jnp.zeros_like(path[:, :1]), path], axis=1)

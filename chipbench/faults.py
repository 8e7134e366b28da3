"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault patches the program (or the unit's step) for the duration of a
``with planted(name):`` block.  The CPU tests and ``calibrate.py`` use
them; the benchmark's own runs never do.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _patched(obj, attr: str, make):
    real = getattr(obj, attr)
    setattr(obj, attr, make(real))
    try:
        yield
    finally:
        setattr(obj, attr, real)


def state_unchanged():
    """The training step returns the parameters it was given."""
    from chipbench.units import mmd_sgd_step

    def make(step):
        def frozen(self, theta, s, pool, k_noise):
            _, s_next, val = step(self, theta, s, pool, k_noise)
            return theta, s_next, val
        return frozen
    return _patched(mmd_sgd_step.Unit, "program_step", make)


def half_batch():
    """MMD² over the first half of each side's batch."""
    from repro.core import losses

    def make(mmd2):
        return lambda X, Y, **kw: mmd2(X[: X.shape[0] // 2],
                                       Y[: Y.shape[0] // 2], **kw)
    return _patched(losses, "mmd2", make)


def gram_altered():
    """Every cross Gram K_xy of the loss off by a relative 1e-3."""
    from repro.core import losses

    def make(gram):
        def altered(X, Y=None, **kw):
            K = gram(X, Y, **kw)
            return K * 1.001 if Y is not None else K
        return altered
    return _patched(losses, "sigkernel_gram", make)


def _solved_pairs(change):
    from repro.core import gram
    return _patched(gram, "_solve_pairs_chunked",
                    lambda solve: lambda *a, **kw: change(solve(*a, **kw)))


def pairs_altered():
    """Every pair value of the sharded Gram off by a relative 5e-2: the
    Gram's limit passes relative errors under 1.5e-2."""
    return _solved_pairs(lambda k: k * 1.05)


def half_left_out():
    """Each chip solves the first half of its pairs; the rest read 0."""
    return _solved_pairs(lambda k: k.at[k.shape[0] // 2:].set(0.0))


def exchange_left_out():
    """Only the first chip's pair values reach the gathered Gram."""
    from repro.core import gram

    def make(get_shard_map):
        def patched():
            shard_map = get_shard_map()

            def no_exchange(f, *, mesh, **kw):
                def local(*args):
                    out = f(*args)
                    first = jax.lax.axis_index(tuple(mesh.axis_names)) == 0
                    return jnp.where(first, out, jnp.zeros_like(out))
                return shard_map(local, mesh=mesh, **kw)
            return no_exchange
        return patched
    return _patched(gram, "get_shard_map", make)


#: the faults each unit can have
FAULTS = {
    "mmd_sgd_step": {"state_unchanged": state_unchanged,
                     "half_batch": half_batch,
                     "answer_altered": gram_altered},
    "gram_sharded": {"exchange_left_out": exchange_left_out,
                     "answer_altered": pairs_altered,
                     "half_left_out": half_left_out},
}


def planted(unit: str, name: str):
    """Context in which fault ``name`` of ``unit`` is planted."""
    return FAULTS[unit][name]()

"""Faults planted in the log-signature layer and in the forward MMD², to
show that ``correct`` catches them.

Each fault patches the program (or the unit's loss) for the duration of a
``with planted(name):`` block.  The CPU tests and ``calibrate_sig.py`` use
them; the benchmark's own runs never do.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from chipbench import faults
from chipbench.faults import _patched


def top_level_dropped():
    """The signature's top level reads 0 before the log."""
    logsignature = importlib.import_module("repro.core.logsignature")

    def make(log):
        def dropped(sig, d, depth):
            return log(sig.at[..., sig.shape[-1] - d ** depth:].set(0.0), d,
                       depth)
        return dropped
    return _patched(logsignature, "_log", make)


def increment_skipped():
    """The middle increment of every path is left out."""
    logsignature = importlib.import_module("repro.core.logsignature")

    def make(increments):
        def skipped(*args, **kw):
            z = increments(*args, **kw)
            return z.at[..., z.shape[-2] // 2, :].set(0.0)
        return skipped
    return _patched(logsignature, "_effective_increments", make)


def half_batch_gradient():
    """The loss of the whole batch, the gradient of its first half only."""
    from chipbench.units import logsig_sgd_step

    def make(program_loss):
        def patched(cfg):
            loss = program_loss(cfg)

            def half(theta, x, y):
                h = x.shape[0] // 2
                v1, f1 = loss(theta, x[:h], y[:h])
                v2, f2 = loss(jax.lax.stop_gradient(theta), x[h:], y[h:])
                return (v1 + v2) / 2, jnp.concatenate([f1, f2])
            return half
        return patched
    return _patched(logsig_sgd_step, "program_loss", make)


#: the faults each unit can have
FAULTS = {"logsig_sgd_step": {"top_level_dropped": top_level_dropped,
                              "increment_skipped": increment_skipped,
                              "half_batch_gradient": half_batch_gradient},
          "mmd_forward": {"half_batch": faults.half_batch,
                          "answer_altered": faults.gram_altered}}


def planted(unit: str, name: str):
    """Context in which fault ``name`` of ``unit`` is planted."""
    return FAULTS[unit][name]()

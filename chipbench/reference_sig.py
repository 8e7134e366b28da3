"""Plain reference for the log-signature cells: jax.numpy, float32.

The signature of a piecewise-linear path is the product, in the truncated
tensor algebra, of the exponentials of its increments (Chen's identity):
``S = exp(z_1) ⊗ exp(z_2) ⊗ ... ⊗ exp(z_{L−1})``, with
``exp(z) = Σ_k z^{⊗k} / k!``.  The log-signature is the truncated series
``log(1 + u) = Σ_n (−1)^(n+1) u^{⊗n} / n`` of ``u = S − 1``, read in the
Lyndon-word coordinates: the coefficient of each Lyndon word in that
expansion, words ordered by length, then lexicographically (the
``mode="lyndon"`` coordinates of ``repro.LogSignature``).  Lyndon words
come from Duval's algorithm.

Level k of an element is held flat, (paths, d^k), word ``w_1 … w_k`` at
``Σ_i w_i·d^(k−i)``: the first letter varies slowest.

Departures from the plain definition, for memory alone: the scan over
increments is rematerialised in segments (``jax.checkpoint``), so autodiff
keeps one state per segment and not one per step, and the model's loss is
summed over blocks of paths.  Gradients are autodiff of this scan.

This module imports nothing of the program under test, and takes from it
no weights, tables or intermediate results.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.data import HIGHEST


def lyndon_words(d: int, depth: int) -> list:
    """Lyndon words over ``range(d)`` of length 1..depth, by length then
    lexicographically (Duval's algorithm)."""
    words, w = [], [-1]
    while w:
        w[-1] += 1
        words.append(tuple(w))
        m = len(w)
        while len(w) < depth:
            w.append(w[len(w) - m])
        while w and w[-1] == d - 1:
            w.pop()
    return sorted(words, key=lambda u: (len(u), u))


@functools.lru_cache(maxsize=None)
def lyndon_index(d: int, depth: int) -> tuple:
    """Per level, the flat positions of its Lyndon words within the level."""
    out = [[] for _ in range(depth)]
    for w in lyndon_words(d, depth):
        out[len(w) - 1].append(sum(a * d ** (len(w) - 1 - i)
                                   for i, a in enumerate(w)))
    return tuple(np.asarray(ix, np.int32) for ix in out)


def outer(a, b):
    """(n, d^i) ⊗ (n, d^j) -> (n, d^(i+j))."""
    return (a[:, :, None] * b[:, None, :]).reshape(a.shape[0], -1)


def product(a: list, b: list, depth: int) -> list:
    """Truncated product of two elements with no scalar part (levels 1..N)."""
    return [sum(outer(a[i - 1], b[k - i - 1]) for i in range(1, k))
            if k > 1 else jnp.zeros_like(a[0]) for k in range(1, depth + 1)]


def exp_increment(z, depth: int) -> list:
    """Levels of exp(z) for increments z (n, d): z^{⊗k} / k!."""
    levels = [z]
    for k in range(2, depth + 1):
        levels.append(outer(levels[-1], z) / k)
    return levels


def chen(a: list, b: list, depth: int) -> list:
    """(1 + a) ⊗ (1 + b), truncated, without its scalar part."""
    ab = product(a, b, depth)
    return [a[k] + b[k] + ab[k] for k in range(depth)]


def signature(z, depth: int, segment: int) -> list:
    """Levels of the signature of increments z (n, L−1, d), a scan of Chen
    products rematerialised every ``segment`` steps (zero increments pad
    the last segment: exp(0) = 1)."""
    n, steps, d = z.shape
    z = jnp.pad(z, ((0, 0), (0, -steps % segment), (0, 0)))
    zs = z.reshape(n, -1, segment, d).transpose(1, 2, 0, 3)

    def step(levels, zt):
        return chen(levels, exp_increment(zt, depth), depth), None

    @jax.checkpoint
    def run_segment(levels, zseg):
        return jax.lax.scan(step, levels, zseg)[0], None

    init = [jnp.zeros((n, d ** k), z.dtype) for k in range(1, depth + 1)]
    return jax.lax.scan(run_segment, init, zs)[0]


def log(levels: list, depth: int) -> list:
    """log(1 + u) = Σ_n (−1)^(n+1) u^{⊗n} / n, truncated at ``depth``."""
    out, power = list(levels), list(levels)
    for n in range(2, depth + 1):
        power = product(power, levels, depth)
        out = [o + (-1.0) ** (n + 1) / n * p for o, p in zip(out, power)]
    return out


def logsignature_levels(paths, depth: int, segment: int) -> list:
    """Per level, the Lyndon coordinates of the log-signature of (n, L, d)
    paths."""
    d = paths.shape[-1]
    z = paths[:, 1:] - paths[:, :-1]
    lg = log(signature(z, depth, segment), depth)
    return [lvl[:, ix] for lvl, ix in zip(lg, lyndon_index(d, depth))]


def model_features(theta: dict, x, depth: int, segment: int) -> list:
    """The feature layer: channels mixed by ``theta["mix"]``, then the
    log-signature, per level."""
    mixed = jnp.matmul(x, theta["mix"], precision=HIGHEST)
    return logsignature_levels(mixed, depth, segment)


def model_loss(theta: dict, x, y, depth: int, segment: int, batch: int):
    """Σ over the given paths of (readout · features − y)² / batch, and the
    features per level."""
    levels = model_features(theta, x, depth, segment)
    feats = jnp.concatenate(levels, axis=-1)
    pred = jnp.matmul(feats, theta["readout"], precision=HIGHEST)
    return jnp.sum((pred - y) ** 2) / batch, levels


@functools.lru_cache(maxsize=None)
def _block_value_and_grad(depth: int, segment: int, batch: int):
    return jax.jit(jax.value_and_grad(
        functools.partial(model_loss, depth=depth, segment=segment,
                          batch=batch), has_aux=True))


def value_and_grad(theta: dict, x, y, *, depth: int, block: int,
                   segment: int):
    """Mean-squared-error loss of the layer over (B, L, d) paths ``x`` with
    targets ``y`` (B,), its gradient, and the features per level, summed
    over blocks of ``block`` paths."""
    with jax.default_matmul_precision("highest"):
        part = _block_value_and_grad(depth, segment, x.shape[0])
        loss, grads, levels = 0.0, None, []
        for lo in range(0, x.shape[0], block):
            (val, lv), g = part(theta, x[lo:lo + block], y[lo:lo + block])
            loss = loss + val
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            levels.append(lv)
        return loss, grads, [jnp.concatenate(ls) for ls in zip(*levels)]

"""Deep signatures on the Horner kernel: the split by leading letters.

A signature too deep for one program's VMEM is computed by programs that
each hold the words of one prefix (``kernels/signature/kernel.py``).  These
tests run the kernel in interpret mode: every split gives the whole
kernel's numbers bit for bit, the public path splits when the budget asks
for it and still matches the pure-JAX reference in value and gradient,
truncation at a lower depth is a prefix of the deeper signature, and the
lane counter reports how full the batch tiles are.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import dispatch
from repro.core.lyndon import logsig_dim
from repro.core.tensoralg import sig_dim
from repro.kernels.signature import ops as sig_ops
from repro.kernels.signature.kernel import (assemble, build_horner,
                                            prefix_rows, vmem_bytes)


def _increments(seed, tiles, L, d, BT, scale=0.3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (tiles, L, d, BT)) * scale


@pytest.mark.parametrize("prefix", [1, 2, 3])
def test_prefix_split_is_bitwise_the_whole_kernel(prefix):
    """Two batch tiles and two length blocks, split by 1 to 3 letters."""
    d, depth, BT, LB, L = 3, 4, 8, 4, 8
    z = _increments(prefix, 2, L, d, BT)

    def run(p):
        out = build_horner(2, L, d, depth, BT=BT, LB=LB, prefix=p,
                           interpret=True)(z)
        return np.asarray(assemble(out, d, depth, p))

    whole = run(0)
    assert whole.shape == (2, sig_dim(d, depth), BT)
    np.testing.assert_array_equal(run(prefix), whole)


def test_prefix_rows_and_vmem_count_tiles():
    assert prefix_rows(5, 7, 2) == [1, 1, 5, 25, 125, 625, 3125]
    assert sum(prefix_rows(5, 7, 0)) == sig_dim(5, 7) == 97655
    # a lane tile is 128 lanes whatever the batch tile: 8 lanes cost as
    # much VMEM as 128
    assert vmem_bytes(5, 7, 2, 256, 8) == vmem_bytes(5, 7, 2, 256, 128)
    assert [sig_ops.choose_prefix(5, n, 256, 128) for n in (4, 5, 6, 7)] \
        == [0, 0, 1, 2]


def _paths(seed, B, L, d, scale=0.2):
    steps = jax.random.normal(jax.random.PRNGKey(seed), (B, L, d))
    return jnp.cumsum(steps, axis=1) * scale / np.sqrt(L)


def test_public_path_splits_at_depth_7_and_matches_the_reference():
    """d=5, depth 7 takes the two-letter split (25 programs) on the
    library's own budget; values and gradients match the pure-JAX path."""
    p = _paths(0, 2, 4, 5)
    assert sig_ops.choose_prefix(5, 7, 3, 128) == 2
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (2, logsig_dim(5, 7)))

    def value_and_grad(backend):
        f = repro.LogSignature(depth=7, backend=backend)
        return jax.value_and_grad(lambda q: jnp.sum(w * f(q)))(p), f(p)

    (v_k, g_k), out_k = value_and_grad("pallas")
    (v_r, g_r), out_r = value_and_grad("reference")
    np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(v_k, v_r, rtol=1e-5)
    np.testing.assert_allclose(g_k, g_r, rtol=1e-4, atol=1e-7)


def test_small_budget_splits_the_public_path_bitwise(monkeypatch):
    """Under a budget that fits no whole signature the wrapper splits the
    words; the numbers are those of the unsplit kernel."""
    p = _paths(2, 3, 9, 3)
    whole = repro.signature(p, 4, backend="pallas")
    monkeypatch.setattr(sig_ops, "_VMEM_BUDGET", 1)
    assert sig_ops.choose_prefix(3, 4, 8, 128) == 3
    jax.clear_caches()
    try:
        split = repro.signature(p, 4, backend="pallas")
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(split, whole)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_lower_depth_is_a_prefix_of_the_deeper_signature(backend):
    """Levels 1-5 of a depth-7 signature are the depth-5 signature, to f32
    rounding."""
    p = _paths(3, 2, 6, 5)
    deep = repro.signature(p, 7, backend=backend)
    low = repro.signature(p, 5, backend=backend)
    np.testing.assert_allclose(deep[:, :sig_dim(5, 5)], low, rtol=1e-6,
                               atol=1e-12)


@pytest.mark.parametrize("B,launch,fill", [
    (128, None, 1.0),
    (4, None, 4 / 128),
    (5, repro.LaunchConfig(sig_bt=2), 5 / (3 * 128)),
])
def test_lane_counter_reports_the_fill_of_the_batch_tiles(B, launch, fill):
    p = _paths(4, B, 5, 2)
    jax.clear_caches()
    with dispatch.count_horner_lanes() as c:
        jax.eval_shape(lambda q: repro.signature(q, 3, backend="pallas",
                                                 launch=launch), p)
    assert c.paths == B
    assert c.fill == pytest.approx(fill)

"""Streaming ``repro.Path``: prefix store, O(1) queries, incremental update.

Contracts under test (ISSUE 9 acceptance criteria):

* prefix queries ``signature(0, j)`` are **bitwise** the reference
  full-recompute oracle (the prefix store IS the reference stream scan),
  and agree with the Pallas exact backend to its own cross-backend
  tolerance; general ``(i, j)`` intervals are exact group arithmetic —
  tight-allclose vs a fresh recompute and exactly consistent under
  Chen-splicing;
* interval / rolling queries perform ZERO Horner scan steps and O(1)
  Chen combines (asserted via the op counters in ``repro.core.dispatch``,
  which record at trace time);
* ``update()`` scans only the appended chunk (scan-step counter == chunk
  bucket, not path length) and reuses a warm jit trace for same-bucket
  appends (asserted via ``repro.stream.trace_counts``);
* buffers use the PR 5 power-of-two buckets: nearby lengths share one
  build trace;
* gradients flow through the stored prefixes back to the input points.

Counter tests use distinctive (d, depth) combinations so their kernels
are traced fresh inside the test regardless of what ran earlier in the
process (the counters record nothing on warm-cache calls, by design).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dispatch
from repro.core.config import TransformPipeline
from repro.core.logsignature import logsignature
from repro.core.signature import signature
from repro.stream import (Path, RollingConfig, coalesced_update,
                          trace_counts)

jax.config.update("jax_platform_name", "cpu")


def _pts(seed, *shape, scale=0.3):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


PIPELINES = {
    "plain": TransformPipeline(),
    "lead_lag": TransformPipeline(lead_lag=True),
}


# ---------------------------------------------------------------------------
# interval queries vs the full-recompute oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", PIPELINES, ids=PIPELINES.keys())
def test_prefix_queries_bitwise_vs_reference(pipeline):
    tp = PIPELINES[pipeline]
    pts = _pts(0, 13, 3)
    p = Path.from_points(pts, depth=3, transforms=tp)
    for j in (2, 5, 11, 13):
        oracle = signature(pts[:j], 3, transforms=tp, backend="reference")
        assert _bitwise(p.signature(0, j), oracle), j
    # the no-arg full signature is the j = length prefix
    assert _bitwise(p.signature(),
                    signature(pts, 3, transforms=tp, backend="reference"))


def test_prefix_queries_vs_pallas_backend():
    # the Pallas kernel is exact but uses its own op order: compare to its
    # own cross-backend tolerance (tests/test_kernels_signature.py)
    pts = _pts(1, 10, 3)
    p = Path.from_points(pts, depth=3)
    for j in (4, 10):
        oracle = signature(pts[:j], 3, backend="pallas")
        got = p.signature(0, j)
        denom = max(float(jnp.abs(oracle).max()), 1e-6)
        assert float(jnp.abs(got - oracle).max()) / denom < 5e-5, j


@pytest.mark.parametrize("pipeline", PIPELINES, ids=PIPELINES.keys())
@pytest.mark.parametrize("i,j", [(1, 3), (3, 8), (5, 13), (11, 13)])
def test_interval_queries_vs_recompute(pipeline, i, j):
    tp = PIPELINES[pipeline]
    pts = _pts(2, 13, 3)
    p = Path.from_points(pts, depth=3, transforms=tp)
    oracle = signature(pts[i:j], 3, transforms=tp, backend="reference")
    got = p.signature(i, j)
    # exact group arithmetic: a few ULPs of cancellation vs the fresh scan
    np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-5)


def test_interval_queries_chen_consistent():
    # exactness the float tolerance can't show: splicing two interval
    # signatures that share an endpoint (points[2:8] ends where points[7:14]
    # starts) through Chen reproduces the whole interval to machine roundoff
    from repro.core.tensoralg import chen
    pts = _pts(3, 16, 2)
    p = Path.from_points(pts, depth=4)
    a = p.signature(2, 8)
    b = p.signature(7, 14)
    ab = p.signature(2, 14)
    np.testing.assert_allclose(chen(a, b, 2, 4), ab, rtol=2e-6, atol=1e-7)


def test_logsignature_intervals():
    pts = _pts(4, 12, 3)
    p = Path.from_points(pts, depth=3)
    for mode in ("lyndon", "brackets", "expand"):
        oracle = logsignature(pts[3:9], 3, mode=mode, backend="reference")
        got = p.logsignature(3, 9, mode=mode)
        np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-5)
    # prefix logsignatures ride on the bitwise prefix store
    oracle0 = logsignature(pts[:7], 3, backend="reference")
    np.testing.assert_allclose(p.logsignature(0, 7), oracle0,
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# O(1) queries: zero scan steps, one combine (counters record at trace)
# ---------------------------------------------------------------------------

def test_interval_query_is_one_combine_no_scan():
    # d=4 / depth=2 is unique to this test -> the query kernel traces here
    pts = _pts(5, 40, 4)
    p = Path.from_points(pts, depth=2)
    with dispatch.count_scan_steps() as sc, dispatch.count_combines() as cc:
        p.signature(3, 37)
    assert sc.total == 0, "interval query re-scanned the path"
    assert cc.total == 1, cc.total
    # warm repeat records nothing (same trace) and still agrees
    with dispatch.count_scan_steps() as sc2:
        q = p.signature(3, 37)
    assert sc2.total == 0
    oracle = signature(pts[3:37], 2, backend="reference")
    np.testing.assert_allclose(q, oracle, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# update(): O(chunk) scans, warm traces, agreement vs recompute
# ---------------------------------------------------------------------------

def test_update_agrees_with_recompute():
    pts = _pts(6, 11, 3)
    more = _pts(7, 6, 3)
    p = Path.from_points(pts, depth=3).update(more)
    full = jnp.concatenate([pts, more])
    assert len(p) == 17
    np.testing.assert_allclose(
        p.signature(), signature(full, 3, backend="reference"),
        rtol=1e-5, atol=1e-6)
    # interval straddling the append boundary
    np.testing.assert_allclose(
        p.signature(8, 15), signature(full[8:15], 3, backend="reference"),
        rtol=1e-4, atol=1e-5)


def test_update_scans_only_the_chunk():
    # d=5 / depth=2 unique -> both kernels trace inside the counters.
    # Capacity 64 holds a long path; the 3-point chunk buckets to 4.
    pts = _pts(8, 50, 5)
    chunk = _pts(9, 3, 5)
    with dispatch.count_scan_steps() as sc_build:
        p = Path.from_points(pts, depth=2)
    assert sc_build.total == p.capacity - 1, "build scans the buffer once"
    with dispatch.count_scan_steps() as sc, dispatch.count_combines():
        p2 = p.update(chunk)
    assert sc.total == 4, (
        f"update() scanned {sc.total} steps for a 3-point chunk "
        f"(bucket 4) on a 50-point path — full re-scan detected")
    full = jnp.concatenate([pts, chunk])
    np.testing.assert_allclose(
        p2.signature(), signature(full, 2, backend="reference"),
        rtol=1e-5, atol=1e-6)


def test_update_reuses_warm_trace_per_bucket():
    # d=6 / depth=2 unique -> fresh trace-count deltas for this geometry
    pts = _pts(10, 20, 6)
    p = Path.from_points(pts, depth=2)
    before = trace_counts()
    p = p.update(_pts(11, 1, 6))
    after_first = trace_counts()
    assert after_first["update"] - before["update"] == 1
    # same chunk bucket, same capacity -> zero new traces, many appends
    for seed in range(12, 18):
        p = p.update(_pts(seed, 1, 6))
    assert trace_counts()["update"] == after_first["update"], \
        "same-bucket appends retraced the update kernel"
    full = jnp.concatenate([_pts(10, 20, 6)]
                           + [_pts(s, 1, 6) for s in range(11, 18)])
    np.testing.assert_allclose(
        p.signature(), signature(full, 2, backend="reference"),
        rtol=1e-5, atol=1e-6)


def test_build_bucket_trace_reuse():
    # d=7 / depth=2 unique; lengths 9 and 15 share the 16-bucket
    before = trace_counts()
    p1 = Path.from_points(_pts(20, 9, 7), depth=2)
    mid = trace_counts()
    p2 = Path.from_points(_pts(21, 15, 7), depth=2)
    after = trace_counts()
    assert p1.capacity == p2.capacity == 16
    assert mid["build"] - before["build"] == 1
    assert after["build"] == mid["build"], \
        "same-bucket builds retraced the build kernel"


def test_update_grows_capacity():
    pts = _pts(22, 14, 2)
    p = Path.from_points(pts, depth=3)
    assert p.capacity == 16
    more = _pts(23, 9, 2)
    p2 = p.update(more)
    assert p2.capacity == 32 and len(p2) == 23
    full = jnp.concatenate([pts, more])
    np.testing.assert_allclose(
        p2.signature(), signature(full, 3, backend="reference"),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        p2.signature(10, 20), signature(full[10:20], 3,
                                        backend="reference"),
        rtol=1e-4, atol=1e-5)


def test_update_lead_lag():
    tp = TransformPipeline(lead_lag=True)
    pts = _pts(24, 9, 2)
    more = _pts(25, 4, 2)
    p = Path.from_points(pts, depth=2, transforms=tp).update(more)
    full = jnp.concatenate([pts, more])
    np.testing.assert_allclose(
        p.signature(), signature(full, 2, transforms=tp,
                                 backend="reference"),
        rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# rolling windows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,stride", [(2, 1), (5, 1), (4, 3), (13, 5)])
def test_rolling_vs_oracle(window, stride):
    pts = _pts(26, 17, 3)
    p = Path.from_points(pts, depth=3)
    out = p.rolling(window, stride=stride)
    cfg = RollingConfig(window=window, stride=stride)
    assert out.shape == (cfg.num_windows(17), p.sig_dim)
    for w in range(out.shape[0]):
        s0 = w * stride
        oracle = signature(pts[s0:s0 + window], 3, backend="reference")
        np.testing.assert_allclose(out[w], oracle, rtol=1e-4, atol=1e-5,
                                   err_msg=f"window {w}")


def test_rolling_config_and_validation():
    pts = _pts(27, 10, 2)
    p = Path.from_points(pts, depth=2)
    cfg = RollingConfig(window=4, stride=2)
    out = p.rolling(cfg)
    np.testing.assert_allclose(out, p.rolling(4, stride=2))
    with pytest.raises(ValueError, match="window"):
        RollingConfig(window=1)
    with pytest.raises(ValueError, match="stride"):
        RollingConfig(window=3, stride=0)
    with pytest.raises(ValueError, match="window fits"):
        p.rolling(11)


def test_rolling_is_combines_not_scans():
    # d=3 / depth=5 unique to this test
    pts = _pts(28, 33, 3)
    p = Path.from_points(pts, depth=5)
    with dispatch.count_scan_steps() as sc, dispatch.count_combines() as cc:
        out = p.rolling(8, stride=4)
    assert sc.total == 0, "rolling re-scanned the path"
    assert out.shape[0] == 7
    assert cc.total == 8, cc.total     # bucketed window count (7 -> 8)


# ---------------------------------------------------------------------------
# pytree / jit / grad
# ---------------------------------------------------------------------------

def test_path_is_a_pytree_through_jit():
    pts = _pts(29, 9, 2)
    p = Path.from_points(pts, depth=3)

    @jax.jit
    def query(path):
        return path.signature(2, 7)

    np.testing.assert_allclose(query(p), p.signature(2, 7),
                               rtol=1e-6, atol=1e-7)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    p_back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert _bitwise(p_back.signature(), p.signature())


def test_gradients_flow_through_stored_prefixes():
    pts = _pts(30, 10, 2)

    def via_path(points):
        p = Path.from_points(points, depth=3)
        return jnp.sum(p.signature(2, 8) ** 2)

    def direct(points):
        return jnp.sum(signature(points[2:8], 3,
                                 backend="reference") ** 2)

    g_path = jax.grad(via_path)(pts)
    g_direct = jax.grad(direct)(pts)
    assert bool(jnp.all(jnp.isfinite(g_path)))
    np.testing.assert_allclose(g_path, g_direct, rtol=1e-3, atol=1e-4)
    # points outside [i, j) must not receive gradient from the query
    assert float(jnp.abs(g_path[9]).max()) == 0.0


def test_gradients_through_update():
    base = _pts(31, 8, 2)

    def loss(chunk):
        p = Path.from_points(base, depth=2).update(chunk)
        return jnp.sum(p.signature() ** 2)

    def loss_direct(chunk):
        full = jnp.concatenate([base, chunk])
        return jnp.sum(signature(full, 2, backend="reference") ** 2)

    chunk = _pts(32, 3, 2)
    g = jax.grad(loss)(chunk)
    g_ref = jax.grad(loss_direct)(chunk)
    np.testing.assert_allclose(g, g_ref, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# coalesced updates (the serving hot path)
# ---------------------------------------------------------------------------

def test_coalesced_update_matches_solo_updates():
    chunks = [_pts(40, 1, 3), _pts(41, 3, 3), _pts(42, 2, 3)]
    paths = [Path.from_points(_pts(43 + i, 9 + i, 3), depth=3)
             for i in range(3)]
    got = coalesced_update(paths, chunks)
    for p, c, out in zip(paths, chunks, got):
        solo = p.update(c)
        assert len(out) == len(solo)
        # same group arithmetic; the batched kernel pads the group and the
        # chunk bucket, both exact no-ops
        np.testing.assert_allclose(out.signature(), solo.signature(),
                                   rtol=1e-6, atol=1e-7)


def test_coalesced_update_is_one_kernel_invocation():
    # d=2 / depth=5 unique -> the batched update traces inside the counter
    paths = [Path.from_points(_pts(50 + i, 10, 2), depth=5)
             for i in range(3)]
    chunks = [_pts(60 + i, 1, 2) for i in range(3)]
    before = trace_counts()
    with dispatch.count_scan_steps() as sc:
        coalesced_update(paths, chunks)
    assert trace_counts()["update"] - before["update"] == 1
    # one batched scan over the shared chunk bucket — not one per stream
    assert sc.total == 1, sc.total
    # group padded to the power-of-two bucket (3 -> 4): same trace again
    # for any group size in the bucket
    before = trace_counts()
    coalesced_update(paths[:4 - 1], chunks[:4 - 1])
    assert trace_counts()["update"] == before["update"]


def test_coalesced_update_validates_groups():
    p16 = Path.from_points(_pts(70, 9, 2), depth=2)    # capacity 16
    p32 = Path.from_points(_pts(71, 20, 2), depth=2)   # capacity 32
    with pytest.raises(ValueError, match="homogeneous"):
        coalesced_update([p16, p32], [_pts(72, 1, 2), _pts(73, 1, 2)])
    with pytest.raises(ValueError, match="chunks"):
        coalesced_update([p16], [])


# ---------------------------------------------------------------------------
# validation & transform restrictions
# ---------------------------------------------------------------------------

def test_transform_restrictions():
    pts = _pts(80, 8, 2)
    with pytest.raises(ValueError, match="lead_lag only"):
        Path.from_points(pts, depth=2,
                         transforms=TransformPipeline(time_aug=True))
    with pytest.raises(ValueError, match="lead_lag only"):
        Path.from_points(pts, depth=2,
                         transforms=TransformPipeline(basepoint=True))
    Path.from_points(pts, depth=2,
                     transforms=TransformPipeline(lead_lag=True))


def test_interval_validation():
    p = Path.from_points(_pts(81, 8, 2), depth=2)
    for bad in [(-1, 5), (3, 4), (5, 5), (0, 9)]:
        with pytest.raises(ValueError, match="interval"):
            p.signature(*bad)
    with pytest.raises(ValueError, match="at least 2 points"):
        Path.from_points(_pts(82, 1, 2), depth=2)
    with pytest.raises(ValueError, match="at least one new point"):
        p.update(jnp.zeros((0, 2)))
    with pytest.raises(ValueError, match="new points"):
        p.update(jnp.zeros((3, 5)))


# ---------------------------------------------------------------------------
# eviction & retention (ISSUE 10): drop history by group-inverse splices —
# zero re-scans, O(retained) memory for endless streams
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", [TransformPipeline(),
                                      TransformPipeline(lead_lag=True)],
                         ids=["plain", "lead_lag"])
def test_evict_matches_fresh_build(pipeline):
    pts = _pts(90, 21, 3)
    p = Path.from_points(pts, depth=3, transforms=pipeline).evict(before=7)
    fresh = Path.from_points(pts[7:], depth=3, transforms=pipeline)
    assert len(p) == len(fresh) == 14
    assert p.capacity == fresh.capacity  # buffers shrank to the new bucket
    for i, j in [(0, None), (0, 5), (2, 9), (4, 14)]:
        np.testing.assert_allclose(p.signature(i, j), fresh.signature(i, j),
                                   rtol=2e-5, atol=2e-6)
    # the evicted path's full signature still matches the reference scan
    np.testing.assert_allclose(
        p.signature(),
        signature(pts[7:][None], 3, transforms=pipeline)[0],
        rtol=2e-5, atol=2e-6)


def test_evict_is_combines_not_scans():
    # distinctive (d, depth) so the evict kernel traces inside the counters
    pts = _pts(91, 19, 4)
    p = Path.from_points(pts, depth=3)
    with dispatch.count_scan_steps() as scans, \
            dispatch.count_combines() as combines:
        pe = p.evict(before=5)
    assert scans.total == 0          # not one increment re-folded
    # two batched Chen combines over the shrunken store (C=16 -> M=15)
    assert combines.total == 2 * (pe.capacity - 1)
    np.testing.assert_allclose(
        pe.signature(), signature(pts[5:][None], 3)[0],
        rtol=2e-5, atol=2e-6)


def test_evict_validation():
    p = Path.from_points(_pts(92, 10, 2), depth=2)
    assert p.evict(before=0) is p
    for bad in (-1, 1.5, True):
        with pytest.raises(ValueError, match="evict"):
            p.evict(before=bad)
    with pytest.raises(ValueError, match="at least one increment"):
        p.evict(before=9)
    p.evict(before=8)  # leaves exactly 2 points: fine


def test_retention_caps_memory_with_zero_rescans():
    cap = 16
    p = Path.from_points(_pts(93, 8, 3), depth=2, retention=cap)
    with dispatch.count_scan_steps() as scans:
        history = np.asarray(p.points[:len(p)])
        for step in range(12):
            chunk = _pts(94 + step, 4, 3)
            history = np.concatenate([history, np.asarray(chunk)])
            p = p.update(chunk)
            assert len(p) <= cap
            assert p.capacity <= 2 * cap  # O(retention) memory, forever
    # scans only ever folded chunk buckets, never the retained history
    assert scans.total <= 2 * 4  # <= traces (2 shapes) x chunk bucket
    np.testing.assert_allclose(
        p.signature(), signature(history[-len(p):][None], 2)[0],
        rtol=5e-5, atol=5e-6)


def test_retention_validation():
    pts = _pts(95, 10, 2)
    for bad in (1, 0, -3, 2.5, True):
        with pytest.raises(ValueError, match="retention"):
            Path.from_points(pts, depth=2, retention=bad)
    with pytest.raises(ValueError, match="retention"):
        Path.from_points(pts, depth=2, retention=8)  # 10 points > cap 8
    Path.from_points(pts, depth=2, retention=10)


def test_coalesced_update_honours_retention():
    ps = [Path.from_points(_pts(96 + i, 12, 2), depth=2, retention=14)
          for i in range(3)]
    chunks = [_pts(99 + i, 4, 2) for i in range(3)]
    got = coalesced_update(ps, chunks)
    for p, chunk, base in zip(got, chunks, range(3)):
        assert len(p) == 14
        full = np.concatenate([np.asarray(_pts(96 + base, 12, 2)),
                               np.asarray(chunk)])
        np.testing.assert_allclose(
            p.signature(), signature(full[-14:][None], 2)[0],
            rtol=5e-5, atol=5e-6)


def test_gradients_flow_through_evict():
    pts = _pts(97, 12, 2)

    def loss(x):
        return Path.from_points(x, depth=2).evict(before=4).signature().sum()

    g = jax.grad(loss)(pts)
    assert np.isfinite(np.asarray(g)).all()
    # evicted points cancel through the inverse splice (up to f32 round-off)
    np.testing.assert_allclose(np.asarray(g[:3]), 0.0, atol=1e-5)
    assert float(jnp.abs(g[5:]).max()) > 0


@pytest.mark.parametrize("kernel", ["query", "evict"])
def test_chen_combines_carry_their_profile_scope(kernel):
    """The combines show as ``repro.stream.combine`` in a profile's
    ``op_name`` (docs/solver_guide.md, "Reading a profile")."""
    from repro.core.tensoralg import sig_dim
    from repro.stream import path as sp
    d, depth, M = 2, 3, 8
    store = jax.ShapeDtypeStruct((M, sig_dim(d, depth)), jnp.float32)
    n = jax.ShapeDtypeStruct((4,), jnp.int32)
    i = jax.ShapeDtypeStruct((), jnp.int32)
    if kernel == "query":
        lowered = sp._interval_kernel.lower(store, store, n, n, d=d,
                                            depth=depth)
    else:
        pts = jax.ShapeDtypeStruct((M + 1, d), jnp.float32)
        lowered = sp._evict_kernel.lower(pts, store, store, i, i, C=M + 1,
                                         M=M, f=1, d=d, depth=depth)
    assert "/repro.stream.combine/" in lowered.compile().as_text()

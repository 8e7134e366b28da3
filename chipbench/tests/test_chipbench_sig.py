"""The log-signature cells on the CPU: the plain reference against the
program, the Horner work count, the readers of the new per-layer metrics,
and the ``logsig_sgd_step`` and ``mmd_forward`` units driven through whole
runs at a small size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, counts_sig, device, faults_sig, hlo_scopes, \
    reference_sig
from chipbench.metrics import horner_ms, horner_roofline, sig_bwd_ms
from chipbench.run import Context
from chipbench.tests.helpers import no_compile_cache
from chipbench.trace_reduce import Event, Reduced, UNIT_SPAN, device_op
from chipbench.units import logsig_sgd_step

SEED = 2**31 + 23
#: ``sigfeat_train`` cut to a size a CPU test run holds; limits as committed
SIG_SMALL = {"batch": 4, "length": 16, "channels": 3, "depth": 4,
             "reference_block": 2, "reference_segment": 4}
MMD_SMALL = {"paths_per_side": 8, "length": 10, "reference_rows": 2}


def _mobius(n):
    out, p = 1, 2
    while p * p <= n:
        if n % p == 0:
            n //= p
            if n % p == 0:
                return 0
            out = -out
        p += 1
    return -out if n > 1 else out


def witt(d, n):
    return sum(_mobius(m) * d ** (n // m) for m in range(1, n + 1)
               if n % m == 0) // n


@pytest.mark.parametrize("d,depth", [(2, 6), (3, 5), (5, 7)])
def test_reference_lyndon_words_count_as_witt_says(d, depth):
    words = reference_sig.lyndon_words(d, depth)
    for n in range(1, depth + 1):
        assert sum(len(w) == n for w in words) == witt(d, n)
    assert len(set(words)) == len(words)
    assert all(w < w[i:] + w[:i] for w in words for i in range(1, len(w)))
    if (d, depth) == (5, 7):
        assert [witt(5, n) for n in range(1, 8)] == [5, 10, 40, 150, 624,
                                                    2580, 11160]
        assert len(words) == 14569


def _cfg(**kw):
    return dict(SIG_SMALL, **dict({"mode": "lyndon", "backend": "auto",
                                   "increment_dtype": "float32"}, **kw))


def _model_inputs(B=4, L=16, d=3, depth=4):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jnp.cumsum(jax.random.normal(k1, (B, L, d)), axis=1) * 0.05
    y = jax.random.normal(k2, (B,))
    theta = logsig_sgd_step.init_params(
        k3, {"channels": d, "depth": depth,
             "model": {"mix_jitter": 0.02, "readout_scale": 0.5}})
    return theta, x, y


@pytest.mark.parametrize("backend,split", [("reference", False),
                                           ("pallas", False),
                                           ("pallas", True)])
def test_program_matches_the_reference(backend, split, monkeypatch):
    """Features, gradient and one SGD step of the layer, at B=4, L=16,
    d=3, depth 4, on the pure-JAX path and the kernel in interpret mode,
    whole and split by leading letters (a VMEM budget that fits no
    signature whole)."""
    from repro.kernels.signature import ops as sig_ops
    theta, x, y = _model_inputs()
    loss = logsig_sgd_step.program_loss(_cfg(backend=backend))
    if split:
        monkeypatch.setattr(sig_ops, "_VMEM_BUDGET", 1)
        assert sig_ops.choose_prefix(3, 4, 15, 128) == 3
        # a fresh jit, so that no trace of the whole kernel is reused
        monkeypatch.setattr(sig_ops, "_horner_flat", jax.jit(
            sig_ops._horner_flat.__wrapped__, static_argnums=(1, 2)))
    (val, feats), grads = jax.value_and_grad(loss, has_aux=True)(theta, x, y)
    ref_val, ref_grads, levels = reference_sig.value_and_grad(
        theta, x, y, depth=4, block=3, segment=4)
    np.testing.assert_allclose(feats, jnp.concatenate(levels, axis=-1),
                               rtol=2e-5, atol=1e-9)
    np.testing.assert_allclose(val, ref_val, rtol=1e-5)
    for k in theta:
        np.testing.assert_allclose(grads[k], ref_grads[k], rtol=1e-4,
                                   atol=1e-7)
        step = theta[k] - 0.1 * grads[k]
        np.testing.assert_allclose(step, theta[k] - 0.1 * ref_grads[k],
                                   rtol=1e-6, atol=1e-8)


def test_reference_blocks_and_segments_do_not_change_the_answer():
    theta, x, y = _model_inputs()
    a = reference_sig.value_and_grad(theta, x, y, depth=4, block=4,
                                     segment=15)
    b = reference_sig.value_and_grad(theta, x, y, depth=4, block=1,
                                     segment=4)
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6)
    for k in theta:
        np.testing.assert_allclose(a[1][k], b[1][k], rtol=1e-5, atol=1e-9)


def test_horner_count_is_the_hand_count():
    # d=2, N=2: A_1 + z (2); level 2: z/2 (2), + A_1 (2), ⊗ z and + A_2
    # (2·4) -> 14
    assert counts_sig.horner_flops_per_step(2, 2) == 14
    # d=2, N=3 adds level 3: z/3 (2), + A_1 (2), z/2 (2), ⊗ (4), + A_2
    # (4), ⊗ z and + A_3 (2·8): 30 more
    assert counts_sig.horner_flops_per_step(2, 3) == 44
    assert counts_sig.horner_flops_per_step(3, 2) == 3 + 3 + 3 + 18
    w = counts_sig.horner(128, 1023, 5, 7)
    assert w.flops == 128 * 1023 * counts_sig.horner_flops_per_step(5, 7)
    assert w.bytes == 4 * 128 * (1023 * 5 + 97655)
    least, bound = counts.roofline_seconds(
        w, device.PEAKS["TPU v5 lite"]["vpu_f32_flops_per_s"],
        device.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"])
    assert bound == "compute" and least == pytest.approx(5.2e-3, rel=0.01)


HLO = "\n".join([
    "ENTRY %main {",
    '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata='
    '{op_name="jit(step)/transpose(jvp(repro.logsig.log))/mul"}',
    '  %while.4 = (s32[]) while((s32[]) %t), body=%body, metadata='
    '{op_name="jit(step)/repro.signature.bwd/while"}',
    '  ROOT %fusion.7 = f32[8]{0} fusion(f32[8]{0} %q), metadata='
    '{op_name="jit(step)/repro.signature.bwd/while/body/add"}',
    "  %copy.1 = f32[8]{0} copy(f32[8]{0} %q)",
    '  %fusion.9 = f32[8]{0} fusion(f32[8]{0} %q), metadata='
    '{op_name="jit(step)/repro.signature.bwd_other/add"}',
    "}"])


def test_scoped_ops_are_read_from_the_compiled_text():
    ops = hlo_scopes.scoped_ops(HLO, logsig_sgd_step.SCOPES)
    assert ops == {"repro.signature.bwd": {"while.4", "fusion.7"},
                   "repro.logsig.log": {"fusion.3"},
                   "repro.logsig.project": frozenset()}


def context(work, units=2):
    ops = [("%signature.horner._horner_flat.2 = f32[1,25,3907,128]{3,2,1,0} "
            "custom-call(f32[1,1024,5,128]{3,2,1,0} %z)", 0, 40e6),
           ("%while.4 = (s32[]) while((s32[]) %t)", 40e6, 140e6),
           ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %q)", 50e6, 130e6),
           ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 140e6, 150e6)]
    trace = Reduced({"/device:TPU:0": [device_op(t, a, b) for t, a, b in ops]},
                    [Event(UNIT_SPAN, 0, 1) for _ in range(units)])
    lines = []
    return Context(trace=trace, units=units, log=lines.append, lines=lines,
                   devices=[None], peaks=device.PEAKS["TPU v5 lite"],
                   work=work)


def test_new_readers_read_the_kernel_and_the_backward_span():
    work = dict(logsig_sgd_step.work({"batch": 128, "length": 1024,
                                      "channels": 5, "depth": 7}),
                op_names=hlo_scopes.scoped_ops(HLO, logsig_sgd_step.SCOPES))
    ctx = context(work)
    assert horner_ms.read(ctx, "sigfeat") == pytest.approx(20.0)
    # the while's self time (20 ms) and its body's fusion (80 ms), per unit
    assert sig_bwd_ms.read(ctx, "sigfeat") == pytest.approx(50.0)
    share = horner_roofline.read(ctx, "sigfeat")
    least = work["horner"].flops / device.PEAKS["TPU v5 lite"][
        "vpu_f32_flops_per_s"]
    assert share == pytest.approx(100 * least / 20e-3)
    assert 0 < share < 100


def test_new_readers_fall_silent_where_nothing_names_them():
    ctx = context({"pde_fwd": counts.Work(1e9, 1e6)})
    assert sig_bwd_ms.read(ctx, "sigfeat") is None
    assert horner_roofline.read(ctx, "sigfeat") is None
    none = context({})
    none.trace = None
    assert horner_ms.read(none, "sigfeat") is None


def _run(workload, overrides, seconds=0.2):
    from chipbench import run
    with no_compile_cache():
        return run.run(workload, SEED, seconds, False, require_chip=False,
                       overrides=overrides)


@pytest.fixture(scope="module")
def sound_sigfeat():
    return _run("sigfeat_train", _cfg())


def test_sigfeat_sound_run_is_correct(sound_sigfeat):
    res = sound_sigfeat
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap",
                                  "feature_gap"}
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_sigfeat_lower_precision_control_is_not_correct():
    res = _run("sigfeat_train", _cfg(increment_dtype="bfloat16"))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault",
                         sorted(faults_sig.FAULTS["logsig_sgd_step"]))
def test_sigfeat_planted_fault_is_not_correct(fault):
    with faults_sig.planted("logsig_sgd_step", fault):
        res = _run("sigfeat_train", _cfg())
    assert not res["correct"], res["checks"]


def test_mmd_eval_sound_run_is_correct_and_checks_eight_units():
    res = _run("mmd_eval", MMD_SMALL, seconds=0.0)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap"}
    assert res["attempted"] == 1 and res["failed"] == 0


def test_mmd_eval_lower_precision_control_is_not_correct():
    res = _run("mmd_eval", dict(MMD_SMALL, interior_dtype="bfloat16"))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(faults_sig.FAULTS["mmd_forward"]))
def test_mmd_eval_planted_fault_is_not_correct(fault):
    with faults_sig.planted("mmd_forward", fault):
        res = _run("mmd_eval", MMD_SMALL)
    assert not res["correct"], res["checks"]


def test_mmd_eval_readings_do_not_depend_on_the_window():
    assert _run("mmd_eval", MMD_SMALL, seconds=0.0)["checks"] == \
        _run("mmd_eval", MMD_SMALL)["checks"]


def test_mmd_forward_work_is_the_training_steps_forward():
    from chipbench.units import mmd_forward, mmd_sgd_step
    cfg = {"paths_per_side": 128, "length": 128, "channels": 3,
           "time_aug": True, "dyadic_order": [1, 1]}
    assert mmd_forward.work(cfg) == {
        "pde_fwd": mmd_sgd_step.work(cfg)["pde_fwd"]}


def test_reference_is_the_scan_of_chen_products():
    """The reference signature of one straight segment is its exponential,
    and of two segments their Chen product, level by level."""
    z = jnp.asarray([[[0.3, -0.2], [0.1, 0.4]]])
    one = reference_sig.signature(z[:, :1], 3, segment=2)
    exp = reference_sig.exp_increment(z[:, 0], 3)
    for a, b in zip(one, exp):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    two = reference_sig.signature(z, 3, segment=1)
    want = reference_sig.chen(exp, reference_sig.exp_increment(z[:, 1], 3),
                              3)
    for a, b in zip(two, want):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    # level 2 of a straight segment: z ⊗ z / 2
    np.testing.assert_allclose(one[1].reshape(2, 2),
                               np.outer(z[0, 0], z[0, 0]) / 2, rtol=1e-6)
    # log of exp(z) is z alone
    lg = reference_sig.log(exp, 3)
    np.testing.assert_allclose(lg[0], z[:, 0], rtol=1e-6)
    for lvl in lg[1:]:
        np.testing.assert_allclose(lvl, 0.0, atol=1e-7)


"""``kernel_ms``: PDE kernel time per role, read by the names the program
gives its kernels."""

import os

import pytest

from chipbench import counts, device, trace_reduce
from chipbench.metrics import engine_ms, kernel_ms, pde_bwd_roofline, \
    pde_fwd_roofline
from chipbench.run import Context
from chipbench.tests.test_chipbench_trace import BWD, FWD, PAD, RERUN
from chipbench.trace_reduce import Event, Reduced, UNIT_SPAN, device_op

GRAM = FWD.replace("solve_fused", "gram_fused")
#: PR 12's kernel events, each with its name as the program now gives it
#: (``repro.kernels.KERNEL_NAMES``)
NAMED = {
    FWD: FWD.replace("jvp_jit__solve_fused_impl__",
                     "sigkernel_pde.fwd._solve_fused_impl"),
    GRAM: GRAM.replace("jvp_jit__gram_fused_impl__",
                       "sigkernel_pde.fwd._gram_fused_impl"),
    BWD: BWD.replace("transpose_jvp_jit__grad_flat___",
                     "sigkernel_pde.bwd._grad_flat"),
    RERUN: RERUN.replace("transpose_jvp_jit__solve_flat___",
                         "sigkernel_pde.fwd_ckpt._solve_flat"),
    PAD: PAD,
}
WHILE = "%while.2 = (s32[], f32[8,16]) while((s32[], f32[8,16]) %tuple.4)"


def context(devices: dict, units: int = 1) -> Context:
    lines = []
    trace = Reduced({k: [device_op(text, a, b) for text, a, b in v]
                     for k, v in devices.items()},
                    [Event(UNIT_SPAN, 0, 1) for _ in range(units)])
    return Context(trace=trace, units=units, log=lines.append, lines=lines,
                   devices=[None] * len(devices),
                   peaks=device.PEAKS["TPU v5 lite"],
                   work={"pde_fwd": counts.Work(4e9, 1e6),
                         "pde_bwd": counts.Work(6e9, 2e6)})


def step(names=NAMED):
    """One unit on one chip: K_xx, K_xy forward, the rerun, the backward."""
    return {"/device:TPU:0": [(names[FWD], 0, 10e6),
                              (names[GRAM], 10e6, 22e6),
                              (names[PAD], 22e6, 23e6),
                              (names[RERUN], 23e6, 41e6),
                              (names[BWD], 41e6, 79e6)]}


def test_roles_are_told_apart():
    ctx = context(step(), units=2)
    assert kernel_ms.read(ctx, "fwd.train") == pytest.approx(11.0)
    assert kernel_ms.read(ctx, "fwd_ckpt.train") == pytest.approx(9.0)
    assert kernel_ms.read(ctx, "bwd.train") == pytest.approx(19.0)
    assert ctx.lines == []


def test_a_kernel_inside_a_while_counts_its_self_time():
    ctx = context({"/device:TPU:0": [
        (WHILE, 0, 100e6), (NAMED[FWD], 10e6, 60e6),
        (NAMED[PAD], 60e6, 62e6), (NAMED[FWD], 62e6, 92e6)]})
    assert kernel_ms.read(ctx, "fwd.eval") == pytest.approx(80.0)
    assert engine_ms.read(ctx) == pytest.approx(20.0)     # while 18, pad 2


def test_the_chip_with_the_most_counts():
    ctx = context({f"/device:TPU:{i}": [(NAMED[GRAM], 0, ms * 1e6),
                                        (NAMED[PAD], ms * 1e6, 50e6)]
                   for i, ms in enumerate((30, 41, 29, 35))}, units=2)
    assert kernel_ms.read(ctx, "fwd.eval") == pytest.approx(20.5)


def test_nothing_is_read_where_no_such_kernel_ran():
    ctx = context({"/device:TPU:0": [(NAMED[FWD], 0, 10e6)]})
    assert kernel_ms.read(ctx, "bwd.train") is None
    assert kernel_ms.read(ctx, "fwd_ckpt.train") is None
    assert kernel_ms.read(Context(trace=None), "fwd.train") is None


def test_a_kernel_without_a_name_is_logged():
    names = dict(NAMED, **{GRAM: GRAM})
    ctx = context(step(names))
    assert kernel_ms.read(ctx, "fwd.train") == pytest.approx(10.0)
    assert ctx.lines == ["[kernel_ms] custom calls named by neither "
                         "sigkernel_pde. nor signature.: "
                         "jvp_jit__gram_fused_impl__"]
    ctx = context(step({k: k for k in NAMED}))
    assert kernel_ms.read(ctx, "bwd.train") is None
    for name in ("jvp_jit__solve_fused_impl__", "jvp_jit__gram_fused_impl__",
                 "transpose_jvp_jit__solve_flat___",
                 "transpose_jvp_jit__grad_flat___"):
        assert name in ctx.lines[0]
    assert "pad" not in ctx.lines[0]


def test_the_older_readers_read_the_new_names_as_the_old():
    old, new = context(step({k: k for k in NAMED})), context(step())
    for read in (pde_fwd_roofline.read, pde_bwd_roofline.read,
                 engine_ms.read):
        assert read(new) == pytest.approx(read(old), rel=1e-12)
    assert engine_ms.read(new) == pytest.approx(1.0)


# One traced `mmd_train` step recorded on the chip with the kernels named by
# the program (TPU v5 lite, JAX 0.9.0, `run.py --seconds 0 --trace 1`),
# gzipped.
NAMED_TRACE = os.path.join(os.path.dirname(__file__), "data",
                           "mmd_train_step_named.xplane.pb.gz")


def test_recorded_chip_trace_reads_each_role():
    lines = []
    t = trace_reduce.load(NAMED_TRACE)
    ctx = Context(trace=t, log=lines.append)
    assert t.units == 1
    fwd, ckpt, bwd = (kernel_ms.read(ctx, f"{role}.train")
                      for role in ("fwd", "fwd_ckpt", "bwd"))
    assert fwd == pytest.approx(2462.550428, rel=1e-12)
    assert ckpt == pytest.approx(1824.983326, rel=1e-12)
    assert bwd == pytest.approx(3823.64396, rel=1e-12)
    assert lines == []
    # the roles and the engine make up the busy time; the older readers'
    # substrings find the same kernels
    assert fwd + ckpt + bwd + engine_ms.read(ctx) == pytest.approx(
        1e3 * t.busy()[0], rel=1e-12)
    assert 1e3 * t.seconds(pde_fwd_roofline.matches)[0] == pytest.approx(
        fwd + ckpt, rel=1e-12)
    assert 1e3 * t.seconds(lambda e: pde_fwd_roofline.matches(
        e, pde_bwd_roofline.KERNELS))[0] == pytest.approx(bwd, rel=1e-12)

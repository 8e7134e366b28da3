"""The trace reduction: busy and idle time, kernel and collective time."""

import gzip
import os

import pytest
from jax.profiler import ProfileData

from chipbench import trace_reduce
from chipbench.metrics import collective_ms, engine_ms, pde_bwd_roofline, \
    pde_fwd_roofline
from chipbench.trace_reduce import Event, Reduced, device_op

UNIT = trace_reduce.UNIT_SPAN


def op(text, start, end):
    return device_op(text, start, end)


FWD = ("%jvp_jit__solve_fused_impl__.3 = f32[1,8256]{1,0:T(1,128)S(1)} "
       "custom-call(f32[8256,128,4]{2,1,0:T(8,128)} %pad_select_fusion), "
       "custom_call_target=\"tpu_custom_call\"")
BWD = ("%transpose_jvp_jit__grad_flat___.2 = f32[16384,128,127]{2,1,0:T(8,"
       "128)} custom-call(f32[16384,128,127]{2,1,0:T(8,128)} %pad.11)")
RERUN = ("%transpose_jvp_jit__solve_flat___.3 = (f32[1,8256]{1,0:T(1,128)}, "
         "f32[8256,2,8,384]{3,2,1,0:T(8,128)}) custom-call(f32[8256,128,127]"
         "{2,1,0:T(8,128)} %pad.18)")
PAD = ("%pad.11 = f32[16384,128,127]{2,1,0:T(8,128)} pad(f32[16384,127,127]"
       "{2,1,0:T(8,128)} %bitcast.9, f32[]{:T(128)} %constant.279)")
GATHER = ("%all-gather-start.3 = (f32[32800]{0}, f32[131200]{0}) "
          "all-gather-start(f32[32800]{0} %fusion.2), dimensions={0}")


def test_op_text_gives_name_and_opcode():
    assert device_op(FWD, 0, 1).name == "jvp_jit__solve_fused_impl__.3"
    assert device_op(FWD, 0, 1).opcode == "custom-call"
    assert device_op(RERUN, 0, 1).opcode == "custom-call"
    assert device_op(PAD, 0, 1).opcode == "pad"
    assert device_op(GATHER, 0, 1).opcode == "all-gather-start"
    assert device_op("dot_general.1", 0, 1) == Event("dot_general.1", 0, 1)


def test_busy_union_and_window():
    ops = [op(FWD, 0, 40), op(PAD, 30, 50), op(BWD, 70, 90),
           op(RERUN, 95, 150)]
    host = [Event(UNIT, 10, 60), Event(UNIT, 60, 100),
            Event("PjitFunction(step)", 55, 65)]
    t = Reduced({"/device:TPU:0": ops}, host)
    # the window takes in the ops that the clock mapping puts outside the
    # host spans: [0, 150]
    assert t.units == 2 and t.window_s == pytest.approx(150e-9)
    # busy: [0, 50] and [70, 90] and [95, 150]
    assert t.busy() == [pytest.approx(125e-9)]
    # the forward that the backward reruns counts as forward
    assert t.seconds(pde_fwd_roofline.matches) == [pytest.approx(95e-9)]
    assert t.seconds(lambda e: pde_fwd_roofline.matches(
        e, pde_bwd_roofline.KERNELS)) == [pytest.approx(20e-9)]
    assert t.seconds(engine_ms.outside) == [pytest.approx(20e-9)]
    gaps = t.idle_gaps()
    assert gaps[0] == ["PjitFunction(step)", pytest.approx(20e-9)]
    assert gaps[1] == [UNIT, pytest.approx(5e-9)]


def test_collectives_and_device_means():
    d0 = [op(FWD.replace("solve_fused", "gram_fused"), 0, 80),
          op(GATHER, 80, 84),
          op(GATHER.replace("start", "done"), 84, 90)]
    d1 = [op(FWD.replace("solve_fused", "gram_fused"), 0, 60),
          op("%all-reduce.1 = f32[] all-reduce(f32[] %x)", 60, 62)]
    t = Reduced({"/device:TPU:0": d0, "/device:TPU:1": d1},
                [Event(UNIT, 0, 100)])
    assert t.seconds(collective_ms.matches) == [pytest.approx(10e-9),
                                                pytest.approx(2e-9)]
    assert t.busy() == [pytest.approx(90e-9), pytest.approx(62e-9)]
    top = t.top_ops(2)
    assert top[0] == ["jvp_jit__gram_fused_impl__", pytest.approx(70e-9)]


def test_a_trace_without_units_or_devices_is_refused():
    with pytest.raises(ValueError):
        Reduced({"/device:TPU:0": []}, [])
    with pytest.raises(ValueError):
        Reduced({}, [Event(UNIT, 0, 1)])


# A trace recorded on the chip: one traced `mmd_train` step (TPU v5 lite,
# JAX 0.9.0, `run.py --trace 1`), gzipped.
MMD_TRACE = os.path.join(os.path.dirname(__file__), "data",
                         "mmd_train_step.xplane.pb.gz")


def _raw_ops(path):
    """(text, duration ns) of the XLA Ops events, read without the
    reduction, to check it against."""
    with gzip.open(path, "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return [(e.name, e.duration_ns) for p in profile.planes
            if p.name == "/device:TPU:0" for line in p.lines
            if line.name == "XLA Ops" for e in line.events]


def test_recorded_chip_trace_reduces_as_expected():
    t = trace_reduce.load(MMD_TRACE)
    assert t.units == 1 and list(t.devices) == ["/device:TPU:0"]
    # the device's first op starts 0.565 ms before the host's unit span
    assert t.window_s == pytest.approx(8.144635117, rel=1e-12)
    assert t.busy() == [pytest.approx(8.142365831, rel=1e-12)]
    fwd = t.seconds(pde_fwd_roofline.matches)
    bwd = t.seconds(lambda e: pde_fwd_roofline.matches(
        e, pde_bwd_roofline.KERNELS))
    assert fwd == [pytest.approx(4.287367243, rel=1e-12)]
    assert bwd == [pytest.approx(3.823617052, rel=1e-12)]
    assert t.seconds(collective_ms.matches) == [0.0]     # one chip
    assert t.seconds(engine_ms.outside) == [pytest.approx(0.031381536,
                                                          rel=1e-9)]
    # the same sums taken from the raw events, all inside the window; the
    # kernels are the wrappers' custom calls
    raw = _raw_ops(MMD_TRACE)
    assert sum(d for _, d in raw) * 1e-9 == pytest.approx(
        fwd[0] + bwd[0] + 0.031381536, rel=1e-9)
    assert t.busy()[0] == pytest.approx(sum(d for _, d in raw) * 1e-9,
                                        rel=1e-12)
    assert sum(d for n, d in raw if "_grad_flat" in n and "custom-call"
               in n) * 1e-9 == pytest.approx(bwd[0], rel=1e-12)
    idle = 1 - t.busy()[0] / t.window_s
    assert idle == pytest.approx(2.786e-4, rel=1e-3)


# Two traced `gram2s_4chip` Grams on four chips, gzipped: the pair solves
# run inside a `while` (the row-block loop), and an all-gather collects
# each chip's pair values.
GRAM_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "gram2s_4chip_two_units.xplane.pb.gz")


def test_recorded_four_chip_trace_reduces_as_expected():
    t = trace_reduce.load(GRAM_TRACE)
    assert t.units == 2 and len(t.devices) == 4
    assert t.window_s == pytest.approx(7.542510666, rel=1e-12)
    busy = t.busy()
    assert busy[0] == pytest.approx(7.538273523, rel=1e-12)
    fwd = t.seconds(pde_fwd_roofline.matches)
    assert fwd[0] == pytest.approx(7.413540389, rel=1e-12)
    # the all-gather's two events on chip 0: 23,251 + 19,713 ns
    coll = t.seconds(collective_ms.matches)
    assert coll[0] == pytest.approx(42964e-9, rel=1e-12)
    assert max(coll) == pytest.approx(65948e-9, rel=1e-12)
    # the `while` that holds the solves keeps only its own 0.2 µs, so the
    # engine's time is the pads and copies around the solves
    assert t.seconds(lambda e: e.opcode == "while")[0] == pytest.approx(
        213e-9, rel=1e-9)
    eng = t.seconds(engine_ms.outside)
    assert eng[0] == pytest.approx(0.12469017, rel=1e-9)
    for b, f, c, e in zip(busy, fwd, coll, eng):
        assert f + c + e == pytest.approx(b, rel=1e-6)
    # the raw events overlap (the `while` spans its body): summing them
    # would count the solves twice
    raw = sum(d for _, d in _raw_ops(GRAM_TRACE)) * 1e-9
    assert raw == pytest.approx(15.071252724, rel=1e-9)
    assert raw > 1.9 * busy[0]
    assert t.top_ops(1)[0] == ["_solve_fused_impl",
                               pytest.approx(sum(fwd) / 4, rel=1e-9)]

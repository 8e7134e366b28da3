"""``mmd_train``'s comparison with the reference, driven through a whole
run on the CPU at a small size: a sound run is correct, and the control
(the program's own bfloat16-interior path) and each planted fault of a
training step are not."""

import pytest

from chipbench import faults
from chipbench.tests.helpers import run_small

SEED = 2**31 + 11


def test_sound_run_is_correct():
    res = run_small("mmd_train", SEED)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_compared_steps_do_not_depend_on_the_window():
    """A window of one step leaves the third compared step to be run after
    it; the readings are those of a window that held all three."""
    short = run_small("mmd_train", SEED, seconds=0.0)
    assert short["attempted"] == 1
    assert short["checks"] == run_small("mmd_train", SEED)["checks"]


def test_lower_precision_control_is_not_correct():
    res = run_small("mmd_train", SEED, interior_dtype="bfloat16")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["mmd_sgd_step"]))
def test_planted_fault_is_not_correct(fault):
    with faults.planted("mmd_sgd_step", fault):
        res = run_small("mmd_train", SEED)
    assert not res["correct"], res["checks"]

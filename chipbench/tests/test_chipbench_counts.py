"""Work counts from the shapes."""

import pytest

from chipbench import counts
from chipbench.units import gram_sharded, mmd_sgd_step


@pytest.mark.parametrize("lam", [(0, 0), (1, 1)])
def test_pde_count_is_refined_cells_times_flops_per_cell(lam):
    cells = counts.refined_cells(127, 127, *lam)
    assert cells == (127 << lam[0]) * (127 << lam[1])
    fwd = counts.pde_forward(10, cells, 0)
    bwd = counts.pde_backward(10, cells, 0, 0)
    assert fwd.flops == 10 * cells * 10
    assert bwd.flops == 10 * cells * 20


def test_mmd_and_gram_pair_counts():
    assert counts.mmd2_unbiased_pairs(128, 128) == (32640, 24512)
    assert counts.symmetric_gram_pairs(1024) == 524800


@pytest.mark.parametrize("unit,cfg", [
    (mmd_sgd_step, {"paths_per_side": 128, "length": 128, "channels": 3,
                    "time_aug": True, "dyadic_order": [1, 1]}),
    (gram_sharded, {"paths": 1024, "length": 128, "channels": 8,
                    "time_aug": False, "dyadic_order": [0, 0]}),
])
def test_count_does_not_change_with_backend(unit, cfg):
    works = [unit.work(dict(cfg, backend=b))
             for b in ("auto", "pallas", "pallas_fused", "antidiag",
                       "reference")]
    assert all(w == works[0] for w in works)


def test_cell_sizes_give_the_expected_work():
    cfg = {"paths_per_side": 128, "length": 128, "channels": 3,
           "time_aug": True, "dyadic_order": [1, 1]}
    w = mmd_sgd_step.work(cfg)
    assert w["pde_fwd"].flops == 32640 * 254 * 254 * 10
    assert w["pde_bwd"].flops == 24512 * 254 * 254 * 20
    # increments of both sides (128 x 127 x 4 f32 each) plus the results
    assert w["pde_fwd"].bytes == 2 * 128 * 127 * 4 * 4 + 32640 * 4
    g = gram_sharded.work({"paths": 1024, "length": 128, "channels": 8,
                           "time_aug": False, "dyadic_order": [0, 0]})
    assert g["pde_fwd"].flops == 524800 * 127 * 127 * 10


def test_roofline_names_its_bound():
    w = counts.Work(flops=6e9, bytes=1e6)
    assert counts.roofline_seconds(w, 6e12, 1e9) == (1e-3, "compute")
    assert counts.roofline_seconds(w, 6e12, 1e8)[1] == "memory"

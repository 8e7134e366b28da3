"""Peak table: TPUs are looked up by device_kind; an unknown TPU raises."""

import types

import pytest

from repro.bench import roofline


@pytest.fixture
def fake_device(monkeypatch):
    def use(platform, kind):
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(roofline.jax, "devices", lambda: [dev])
        monkeypatch.setattr(roofline, "_peaks_memo", None)
    yield use
    roofline._peaks_memo = None


def test_v5e_has_mxu_and_vpu_peaks_with_source(fake_device):
    fake_device("tpu", "TPU v5 lite")
    pk = roofline.peaks()
    assert pk["flops"] == 197e12 and pk["bandwidth"] == 819e9
    assert 0 < pk["vpu_flops"] < pk["flops"]
    assert "TPU v5e" in pk["source"]


def test_unknown_tpu_is_an_error(fake_device):
    fake_device("tpu", "TPU v99")
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.peaks()
    with pytest.raises(KeyError):
        roofline.attach({"name": "x", "kind": "check", "meta": {}})

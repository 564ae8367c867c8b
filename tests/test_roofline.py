"""Roofline/MFU harness (VERDICT r4 directive 1b)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.utils import roofline


def test_cost_of_matmul():
    a = jnp.ones((256, 128), jnp.float32)
    b = jnp.ones((128, 64), jnp.float32)
    c = roofline.cost_of(lambda x, y: x @ y, a, b)
    # 2*M*N*K FLOPs, allow cost-model slack either way
    want = 2 * 256 * 128 * 64
    assert c["flops"] == 0 or 0.5 * want <= c["flops"] <= 2 * want
    assert c["bytes"] >= 0


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_peaks_keyed_by_device_kind():
    v5e = roofline.peaks(_Dev("tpu", "TPU v5 lite"))
    assert v5e["flops"] == 197e12 and v5e["bytes_per_s"] == 819e9
    assert roofline.peaks(_Dev("cpu", "cpu")) is None
    assert roofline.peaks() is None            # the test rig is CPU


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks(_Dev("tpu", "TPU v9"))


def test_mfu_has_no_share_on_cpu():
    out = roofline.mfu(flops_per_call=1e12, bytes_per_call=1e9,
                       calls=10, seconds=1.0)
    assert out["achieved_tflops"] == 10.0
    assert out["mfu"] is None and out["hbm_util"] is None
    assert "bound" not in out


def test_mfu_fields(monkeypatch):
    monkeypatch.setattr(roofline, "peaks", lambda: {
        "flops": 100e12, "bytes_per_s": 800e9})
    out = roofline.mfu(flops_per_call=1e12, bytes_per_call=1e9,
                       calls=10, seconds=1.0)
    assert out["achieved_tflops"] == 10.0
    assert out["mfu"] == 0.1
    assert out["achieved_gbps"] == 10.0
    assert out["hbm_util"] == 0.0125
    assert out["bound"] == "compute"   # AI=1000 > 100e12/800e9=125

def test_report_never_raises():
    # a function the cost model may not fully analyze still yields a dict
    out = roofline.report(lambda x: jnp.sort(x), (jnp.ones(64),),
                          calls=1, seconds=0.5)
    assert isinstance(out, dict)

"""The arithmetic every architecture shares: peaks, the roofline and the
bytes of a store's element."""

import pytest

from chipbench import costs


def test_roofline_picks_the_binding_side():
    # 197 TFLOP of work against 1 byte: one second, compute-bound
    r = costs.roofline_seconds(197e12, 1.0, "TPU v5 lite")
    assert r == {"seconds": 1.0, "bound": "compute"}
    r = costs.roofline_seconds(1.0, 819e9, "TPU v5 lite")
    assert r == {"seconds": 1.0, "bound": "memory"}


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(costs.UnknownDevice):
        costs.peaks("cpu")


def test_unknown_dtype_is_an_error():
    assert (costs.dtype_bytes("float32"), costs.dtype_bytes("bfloat16")) == (4, 2)
    with pytest.raises(LookupError, match="float32"):
        costs.dtype_bytes("float8")

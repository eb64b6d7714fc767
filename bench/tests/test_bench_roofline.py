"""Operation counts, the peak table and the roofline share."""

import pytest

from yardstick.roofline import kernel_flops_bytes, peak, roofline_share


def test_flops_and_bytes():
    assert kernel_flops_bytes("matmul", 2048) == (2 * 2048**3, 3 * 2048**2 * 4)
    assert kernel_flops_bytes("matadd", 2048) == (2048**2, 3 * 2048**2 * 4)
    with pytest.raises(KeyError):
        kernel_flops_bytes("conv", 8)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peak("cpu")


def test_matmul_is_compute_bound_at_2048():
    t = 2 * 2048**3 / 197e12
    share, bound = roofline_share("matmul", 2048, 10, 10 * t * 2, "TPU v5 lite")
    assert bound == "compute"
    assert share == pytest.approx(50.0)


def test_matadd_is_memory_bound():
    t = 3 * 2048**2 * 4 / 819e9
    share, bound = roofline_share("matadd", 2048, 4, 4 * t / 0.8, "TPU v5 lite")
    assert bound == "memory"
    assert share == pytest.approx(80.0)

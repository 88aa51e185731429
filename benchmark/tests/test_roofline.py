import pytest

import roofline


def test_headline_block_is_bound_by_bytes():
    peak = roofline.peaks("TPU v5 lite")
    s, bound = roofline.block_least_s(131072, 16384, 1, peak)
    assert bound == "bytes"
    assert s == pytest.approx(0.328e-3, rel=0.01)  # 268 MB of bits at 819 GB/s
    # many rows turn the same block to the MXU's bound
    assert roofline.block_least_s(131072, 16384, 4096, peak)[1] == "ops"
    assert roofline.dispatch_least_s([(131072, 16384), (1024, 1024)], 1,
                                     peak) > s


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("_source")

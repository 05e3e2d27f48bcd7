import pytest

import peaks
import work

HEADLINE = (32, 64, 32)


def test_headline_figures():
    assert work.ops_per_packet(HEADLINE) == 8192
    assert work.bytes_per_packet(HEADLINE) == 8


def test_headline_is_compute_bound_on_v5e():
    v5e = peaks.peaks("TPU v5 lite")
    ops, nbytes = work.totals([HEADLINE], [1_000_000])
    t, bound = work.least_time(ops, nbytes, v5e)
    assert bound == "compute"
    assert t == pytest.approx(8192e6 / 393e12)


def test_mix_is_weighted_by_each_packets_tenant():
    shapes = [(32, 64, 32), (16, 32, 8), (128, 64, 32, 2)]
    counts = [10, 0, 3]
    ops, nbytes = work.totals(shapes, counts)
    assert ops == 10 * 8192 + 3 * 2 * (128 * 64 + 64 * 32 + 32 * 2)
    assert nbytes == 10 * 8 + 3 * (128 + 2) / 8


def test_counts_must_match_tenants():
    with pytest.raises(ValueError):
        work.totals([HEADLINE, HEADLINE], [1])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite").ops_int8 == 393e12

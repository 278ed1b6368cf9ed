"""The shape-to-bytes functions against numbers worked by hand for the
rows cell's request: 100,000 ids x 50 float32 columns."""

import pytest

from benchmark.lib import shapes


def test_gather_bytes_of_a_100k_by_50_request():
    # ids 100,000 x 4 B = 400,000; rows read 100,000 x 50 x 4 B =
    # 20,000,000; the same written out
    assert shapes.gather_bytes(100_000, 50, 4) == 40_400_000


def test_scatter_add_bytes_of_a_100k_by_50_request():
    # ids 400,000; deltas read 20,000,000; rows read 20,000,000 and
    # written back 20,000,000
    assert shapes.scatter_add_bytes(100_000, 50, 4) == 60_400_000


def test_roofline_share_against_819_gb_per_s():
    # 40.4 MB at 819 GB/s is 49.328 us; in 1.5 ms that is 3.2885 %
    share = shapes.roofline_share(40_400_000, 1.5e-3, 819e9)
    assert share == pytest.approx(3.2885, abs=1e-4)
    # at the bound itself the share is 100 %
    assert shapes.roofline_share(819e9, 1.0, 819e9) == pytest.approx(100.0)

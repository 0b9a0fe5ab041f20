"""A kernel's operations and bytes from its shapes, and its share of the
roofline from a time: arithmetic only, no chip."""

import json
import os

import pytest

from perfbench import kernel_costs
from perfbench import manifest as M


def peak():
    with open(os.path.join(M.ROOT, M.HERE, "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


def test_scatter_sorted_counts_one_stretch_an_item_and_the_table_once():
    ops, moved = kernel_costs.scatter_sorted(items=32768, table_rows=1 << 21, digit_planes=2)
    assert ops == 2 * 128 * 128 * 2 * 32768  # 2.1e9
    assert moved == 8 * 32768 + 4 * 2 * (1 << 21)  # 17 MB: the table dominates
    # twice the items, twice the operations; the table's bytes stay
    ops2, moved2 = kernel_costs.scatter_sorted(65536, 1 << 21, 2)
    assert ops2 == 2 * ops and moved2 - moved == 8 * 32768


@pytest.mark.parametrize("seconds,share", [(100e-6, 20.793), (20.793e-6, 100.0)])
def test_roofline_share_names_the_peak_that_bounds_it(seconds, share):
    ops, moved = kernel_costs.scatter_sorted(32768, 1 << 21, 2)
    got, bound = kernel_costs.roofline_share(ops, moved, seconds, peak())
    # 17 MB over 819 GB/s is 20.8 us; 2.1 GFLOP over 197 TFLOP/s is 10.9 us
    assert bound == "bytes" and got == pytest.approx(share, rel=1e-3)
    got, bound = kernel_costs.roofline_share(100 * ops, moved, 1e-3, peak())
    assert bound == "operations" and got == pytest.approx(100 * ops / 197e12 / 1e-3 * 100)

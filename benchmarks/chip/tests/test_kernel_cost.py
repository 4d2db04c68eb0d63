"""The alloc_scan count function against a hand count, and the peaks."""
import pytest

import kernel_cost
import peaks


def test_count_at_one_shape_by_hand():
    # 8 rows against 200 nodes, padded to 2 blocks of 128 = 256 lanes.
    assert kernel_cost.padded_nodes(200) == 256
    # Per row: 20 ops on each of 256 tile elements, 7 on each of 8 rows.
    assert kernel_cost.ops(8, 200) == 8 * (20 * 256 + 7 * 8) == 41408
    # Four f32 tiles once, two [8, 8] f32 tables, 13 row arrays, 2 totals.
    assert kernel_cost.hbm_bytes(8, 200) == 4096 + 512 + 416 + 8 == 5032


def test_bound_is_bandwidth_at_the_cell_shapes():
    chip = peaks.peaks("TPU v5 lite")
    for rows in (1, 8, 1024, 2048):
        seconds, bound = kernel_cost.least_time(rows, 5000, chip)
        assert bound == "hbm"
        assert seconds == kernel_cost.hbm_bytes(rows, 5000) / 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")

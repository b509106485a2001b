"""The sketch scan's byte count against a hand count."""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference, roofline  # noqa: E402


def test_upper_only_hand_count():
    # n = 8 coordinates onto m = 4 rows (h = 1): 0,4 -> row 0; 1,5 -> 1; ...
    maps = np.array([[0, 1, 2, 3, 0, 1, 2, 3]], np.int32)
    q_idx = np.array([[0, 4, 1, -1], [1, 5, -1, -1]])
    q_val = np.array([[1.0, 2.0, 3.0, 0.0], [1.0, 1.0, 0.0, 0.0]])
    C = 4096
    # distinct coordinates {0, 1, 4, 5}; rows {0, 1}; one side (U)
    want = 2 * C * 2 + 4 * C // 8 + C
    assert roofline.scan_bytes(q_idx, q_val, maps, C, 2, signed=False) == want


def test_signed_counts_each_side_once():
    maps = np.array([[0, 1, 2, 3, 0, 1, 2, 3]], np.int32)
    q_idx = np.array([[0, 4, 2], [0, 6, -1]])
    q_val = np.array([[1.0, -2.0, 3.0], [1.0, 5.0, 0.0]])
    C = 1024
    # (row, side): (0,U) from 0; (0,L) from 4; (2,U) from 2 and 6 -> 3 sides
    # coordinates {0, 2, 4, 6} -> 4 membership rows
    want = 3 * C * 2 + 4 * C // 8 + C
    assert roofline.scan_bytes(q_idx, q_val, maps, C, 2, signed=True) == want


def test_two_mappings_touch_both_rows():
    maps = np.array([[0, 1], [2, 3]], np.int32)
    q_idx = np.array([[0, -1]])
    q_val = np.array([[1.0, 0.0]])
    C = 64
    assert roofline.scan_bytes(q_idx, q_val, maps, C, 4,
                               signed=False) == 2 * C * 4 + C // 8 + C


def test_mapping_recipe_is_deterministic():
    a = reference.mappings(5, 100, 7, 2)
    assert a.shape == (2, 100) and a.min() >= 0 and a.max() < 7
    assert np.array_equal(a, reference.mappings(5, 100, 7, 2))

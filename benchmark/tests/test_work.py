"""The join's required bytes and operations for hand-worked shapes."""

import json
import os

import pytest

import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_small_join_by_hand():
    # reads: 1000 left ts (8 B) + 2000 right ts (8 B) + 2000 validity
    # bytes of the one right column = 26,000 B; writes one int32 index
    # per left row = 4,000 B; ops: 3000 merged rows x (1 compare + 1
    # select)
    assert work.asof_join(1000, 2000, 1) == {"bytes": 30_000, "ops": 6_000}


def test_hhar_join_by_hand():
    # 13,062,475 phone rows against 3,540,962 watch rows, four right
    # columns (event_ts, x, y, z): reads 13,062,475 x 8 + 3,540,962 x
    # (8 + 4) = 146,991,344 B, writes 13,062,475 x 4 x 4 = 208,999,600 B
    assert work.asof_join(13_062_475, 3_540_962, 4) == {
        "bytes": 355_990_944, "ops": 83_017_185}


def test_least_time_is_memory_bound_at_v5e_peaks():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    seconds, bound = work.least_seconds(work.asof_join(1000, 2000, 1), peaks)
    assert bound == "memory"
    assert seconds == pytest.approx(30_000 / 819e9)

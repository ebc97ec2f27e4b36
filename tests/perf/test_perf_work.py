"""The work functions against hand-computed bytes and operations, and the
peaks table."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf import work  # noqa: E402


def test_exact_scan_one_launch_one_query():
    ops, moved = work.exact_scan_work(1_000_000, 128, 10, 1, 1)
    assert ops == 2 * 1_000_000 * 128 == 256_000_000
    assert moved == 1_000_000 * 128 * 4 + 128 * 4 + 10 * 8 == 512_000_592


def test_exact_scan_coalesced_launches_read_the_column_once_each():
    ops, moved = work.exact_scan_work(1000, 8, 10, launches=3, queries=12)
    assert ops == 2 * 12 * 1000 * 8
    assert moved == 3 * 1000 * 8 * 4 + 12 * (8 * 4 + 10 * 8)


def test_ivfpq_scan_by_hand():
    # 1M rows in 128 lists -> 7,812.5 rows a list; 32 probes; m = 8
    ops, moved = work.ivfpq_scan_work(
        1_000_000, 128, nlist=128, m=8, ks=256, nprobe=32, pool=128,
        launches=1, queries=1)
    rows = 32 * 7812.5
    assert ops == 2 * 256 * 128 + rows * 8 + 2 * 128 * 128
    assert moved == 256 * 128 * 4 + 128 * 4 + rows * 8 + 128 * 128 * 4 + 128 * 8


def test_least_seconds_names_the_bounding_side():
    peaks = work.peaks_for("TPU v5 lite")
    assert peaks == {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    least, side = work.least_seconds(*work.exact_scan_work(
        1_000_000, 128, 10, 1, 1), peaks)
    assert side == "bandwidth"
    assert least == pytest.approx(512_000_592 / 819e9)   # 0.625 ms
    assert work.least_seconds(1e15, 1.0, peaks) == (1e15 / 197e12, "compute")


@pytest.mark.parametrize("kind", ["cpu", "TPU v9", "source", ""])
def test_an_unknown_device_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        work.peaks_for(kind)

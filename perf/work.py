"""What a launch has to do, from shapes alone, and the least time a chip
could take for it. Kept with the benchmark: a PR that swaps the Pallas
kernel for XLA, or fuses the top-k into the scan, is read by the same
yardstick. Nothing here comes from `telemetry/roofline.py`.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind [{device_kind}] "
                       f"in {PEAKS_FILE.name}")
    return table[device_kind]


def exact_scan_work(n: int, d: int, k: int, launches: int, queries: int,
                    stored_bytes: int = 4) -> tuple[float, float]:
    """(operations, bytes) of `launches` exact scans that served `queries`
    queries between them: every launch reads the whole stored column once,
    every query brings d floats in and takes k (score, id) pairs out, and
    scores every row with one multiply-add per dimension."""
    ops = 2.0 * queries * n * d
    moved = launches * n * d * stored_bytes + queries * (d * 4 + k * 8)
    return ops, float(moved)


def ivfpq_scan_work(n: int, d: int, nlist: int, m: int, ks: int, nprobe: int,
                    pool: int, launches: int, queries: int
                    ) -> tuple[float, float]:
    """(operations, bytes) of `launches` IVF-PQ launches serving `queries`
    queries: the look-up tables (every query against ks centroids in each
    of m subspaces: ks * d multiply-adds, the codebooks read once a
    launch), the codes of the probed lists (nprobe lists of n / nlist rows,
    m bytes and m additions a row) and the exact rescore of the pool."""
    rows = nprobe * (n / nlist)
    ops = queries * (2.0 * ks * d + rows * m + 2.0 * pool * d)
    moved = (launches * ks * d * 4
             + queries * (d * 4 + rows * m + pool * d * 4 + pool * 8))
    return ops, float(moved)


def least_seconds(ops: float, moved: float, peaks: dict) -> tuple[float, str]:
    """The roofline's floor for that work and which side sets it."""
    by_compute = ops / peaks["flops_per_s"]
    by_bandwidth = moved / peaks["bytes_per_s"]
    if by_compute >= by_bandwidth:
        return by_compute, "compute"
    return by_bandwidth, "bandwidth"

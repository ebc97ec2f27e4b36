"""Residency across chips: the fullest chip's resident bytes over the mean
of the cell's chips, from the ledger's chip-by-chip figures in the closing
snapshot of the traced part's capture (program counter). 1.0 is even. None
on a tree whose ledger keeps no such figures."""

from perf import hostspans


def skew(by_device: dict | None, chips: int) -> float | None:
    if not by_device or sum(by_device.values()) <= 0:
        return None
    return max(by_device.values()) / (sum(by_device.values()) / chips)


def read(run):
    capture = hostspans.capture_of(run)
    if capture is None:
        return None
    closing = capture["counters"]["close"] or {}
    return skew(closing.get("device_resident_by_device"), run.cell["chips"])

"""Residency: the device-residency ledger's resident bytes in the closing
snapshot of the traced part's capture (program counter)."""

from perf.hostspans import metric


def read(run):
    return metric(run, "device.resident_bytes")

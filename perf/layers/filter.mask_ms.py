"""Mesh program / per-shard ANN: mean time a filtered request spends turning
its filter into the eligibility its launch uses (`filter.mask`: the filter
executor, the mask's way to the device, `valid & mask`; program span).
None where the program opens no such span."""

from perf import hostspans


def read(run):
    capture = hostspans.capture_of(run)
    if capture is None:
        return None
    return hostspans.mean_duration_ms(capture, "filter.mask")

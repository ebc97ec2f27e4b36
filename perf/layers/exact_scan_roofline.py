"""Kernels, exact path: the least time the chip could take for the
launches of the traced span (perf/work.py, perf/peaks.json) over ALL the
time the device was busy in it, whatever implements the scan."""

from perf.roofline import share


def read(run):
    return share(run, "exact_scan")

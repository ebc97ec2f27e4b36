"""Kernels, IVF-PQ path: the least time the chip could take for the LUT
build, the probed lists' codes and the pool's rescore of the traced span's
launches over ALL the time the device was busy in it."""

from perf.roofline import share


def read(run):
    return share(run, "ivfpq_scan")

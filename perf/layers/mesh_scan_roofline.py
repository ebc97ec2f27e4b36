"""Kernels, exact path across chips: the least time ONE chip could take for
its share of the traced launches (its docs / shards rows of the column,
every query of every launch: perf/work.py, perf/peaks.json) over the mean
time a chip was busy (`run.trace` averages busy and window over the device
planes), whatever implements the scan. The bytes are a chip's own, so a
scan that reads nothing but its shard cannot pass 100%."""

import copy

from perf.roofline import share


def read(run):
    shards = run.config["index_body"]["settings"]["number_of_shards"]
    if shards < 2:
        return None
    chip = copy.copy(run)
    chip.docs = run.docs / shards
    return share(chip, "exact_scan")

"""Search service: mean time a hybrid request spends in the phase-results
processor (`hybrid.fuse`: min-max normalisation of each sub-query's pool,
the weighted combination, the re-ranking, on the host; program span), over
the capture's whole requests. None where the program opens no such span."""

from perf import hostspans


def read(run):
    capture = hostspans.capture_of(run)
    if capture is None:
        return None
    return hostspans.mean_duration_ms(capture, "hybrid.fuse")

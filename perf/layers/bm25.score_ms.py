"""Mesh program / per-shard ANN: mean time one shard's query phase spends
scoring a full-text query (`bm25.score`: the terms' look-ups, the BM25
launches of every segment, the shard's top-k on the host; program span),
over the capture's whole requests. None where the program opens no such
span."""

from perf import hostspans


def read(run):
    capture = hostspans.capture_of(run)
    if capture is None:
        return None
    return hostspans.mean_duration_ms(capture, "bm25.score")

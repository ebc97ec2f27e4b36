"""Mesh program / per-shard ANN: mean time a nested kNN request spends in
its query phase over a multi-vector field (`knn.nested`: on the mesh road
the one launch for every shard, from the batcher's queue to the documents
it returned; on the per-shard road one shard's launches and top-k; program
span), over the capture's whole requests. None where the program opens no
such span."""

from perf import hostspans


def read(run):
    capture = hostspans.capture_of(run)
    if capture is None:
        return None
    return hostspans.mean_duration_ms(capture, "knn.nested")

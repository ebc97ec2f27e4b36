"""What served NESTED kNN requests (a `nested` `knn` over a multi-vector
field: documents of many chunk vectors, each document scored by its best
chunk) have to do at least, from shapes and the program's own count of
rows: the work function of `nested_scan_roofline`. A floor that no
implementation can beat, so that the share reads the same whatever scans
the rows and reduces them to documents: a launch reads the stored rows
once, however many requests it serves; a request brings its query in,
takes k (score, id) pairs out and scores every row with one multiply-add a
dimension. The reduction to documents, padding rows and the final cut
count as no work at all."""

from __future__ import annotations


def nested_scan_work(rows: float, d: int, k: int, launches: float,
                     requests: float, stored_bytes: int = 4
                     ) -> tuple[float, float]:
    """(operations, bytes) of `launches` launches over `rows` rows of `d`
    dimensions that served `requests` requests between them."""
    ops = 2.0 * requests * rows * d
    moved = launches * rows * d * stored_bytes + requests * (d * 4 + k * 8)
    return ops, float(moved)

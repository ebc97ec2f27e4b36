"""Mesh program / per-shard ANN, a nested cell: the share of device-busy
time that is NOT the scan kernel, over all device planes of the traced
span. The same quantity as `mesh.merge_share` (1 - union of the ops named
`knn_fused` / union of all ops, from the reduced trace `run.py` leaves), so
that reader's code reads it; what the rest IS differs: here whatever
reduces the rows to documents outside the scan's own pool (a segment max,
a gather), relayouts of the rows and the final cut. None where no op of
the scan kernel's name ran."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perf_layers_mesh_merge_share",
    Path(__file__).with_name("mesh.merge_share.py"))
_mesh = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mesh)

read = _mesh.read

"""Mesh program / per-shard ANN, a hybrid cell: the share of device-busy
time that is NOT the vector scan's kernel, over all device planes of the
traced span. The same quantity as `mesh.merge_share` and
`filter.device_share` (1 - union of the ops named `knn_fused` / union of
all ops, from the reduced trace `run.py` leaves), so that reader's code
reads it; what the rest IS differs: here the lexical sub-query (BM25's
gathers and scatter-adds, its top-k) and whatever else a hybrid request
puts on the device. None where no op of the scan kernel's name ran."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perf_layers_mesh_merge_share",
    Path(__file__).with_name("mesh.merge_share.py"))
_mesh = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mesh)

read = _mesh.read

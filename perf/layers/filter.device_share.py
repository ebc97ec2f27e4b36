"""Mesh program / per-shard ANN, a filtered cell: the share of device-busy
time that is NOT the scan kernel, over all device planes of the traced span.
The same quantity as `mesh.merge_share` (1 - union of the ops named
`knn_fused` / union of all ops, from the reduced trace `run.py` leaves), so
that reader's code reads it; what the rest IS differs: there the cross-chip
merge, here filter evaluation, the mask's traffic and relayouts of the
column. None where no op of the scan kernel's name ran."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perf_layers_mesh_merge_share",
    Path(__file__).with_name("mesh.merge_share.py"))
_mesh = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mesh)

not_the_scan_share = _mesh.merge_share
read = _mesh.read

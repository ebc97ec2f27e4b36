"""Device-resident segment bundles: padded jnp arrays in HBM.

The "refresh publishes immutable arrays" half of the segment story
(SURVEY.md §7 design stance): a HostSegment is sealed once, then `to_device`
pads every column to the segment's bucketed n_pad and jax.device_put's the
bundle. Readers (query phase) only ever see these immutable arrays — the
segment-replication model (indices/replication/ in the reference) falls out
naturally: replicas fetch the same immutable bundles instead of re-indexing.

Padding invariants relied on by the ops kernels:
- doc column index in [0, n_pad); docs >= n_docs are padding (live=False)
- postings arrays padded with zeros (never addressed: window mask guards)
- keyword CSR padded with ord=-2, doc=0 (ord -2 matches no query ordinal)
- a multi-vector field's rows pad to their own bucketed r_pad, padding
  rows with parent -1 and valid False
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from opensearch_tpu.index.segment import (
    HostSegment,
    pad_size,
    split_i64,
)
from opensearch_tpu.telemetry.device_ledger import (
    KIND_COLUMN,
    array_nbytes,
    default_ledger,
)

# IVF-PQ publish-time build accounting (surfaced via the knn_batch stats
# section's `ann.index_builds`): builds happen on the refresh/merge path,
# which can run concurrently with stats readers
_ann_build_lock = threading.Lock()
_ann_build_stats = {"builds": 0, "build_wall_ns": 0, "last_generation": 0}


def ann_build_stats() -> dict:
    with _ann_build_lock:
        return dict(_ann_build_stats)


def _pad1(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    if a.shape[0] >= n:
        return a[:n]
    out = np.full((n, *a.shape[1:]), fill, dtype=a.dtype)
    out[: a.shape[0]] = a
    return out


@dataclass
class DeviceTextField:
    postings_docs: jnp.ndarray    # int32 [P_pad]
    postings_tfs: jnp.ndarray     # float32 [P_pad]
    # each posting's document length, doc_len[postings_docs]: the BM25
    # launch reads it with the posting instead of gathering doc_len.
    # Derived at upload, never persisted
    postings_dl: jnp.ndarray      # float32 [P_pad]
    doc_len: jnp.ndarray          # float32 [n_pad]
    # the (row width, counted) variants of the BM25 launch whose every rung
    # of the ladder has been compiled for this field (search/executor.py
    # `_compile_ladder`)
    ladders: set = dataclasses.field(default_factory=set)


@dataclass
class DeviceKeywordField:
    first_ord: jnp.ndarray        # int32 [n_pad], -1 missing
    mv_ords: jnp.ndarray          # int32 [E_pad], pad = -2
    mv_docs: jnp.ndarray          # int32 [E_pad], pad = 0


@dataclass
class DeviceNumericField:
    kind: str                     # "int" | "float"
    hi: jnp.ndarray | None        # int32 [n_pad] (int kind)
    lo: jnp.ndarray | None
    values: jnp.ndarray | None    # float32 [n_pad] (float kind)
    present: jnp.ndarray          # bool [n_pad]


@dataclass
class DeviceVectorField:
    vectors: jnp.ndarray          # float32 [n_pad, dims]
    norms_sq: jnp.ndarray         # float32 [n_pad]
    present: jnp.ndarray          # bool [n_pad]
    dims: int
    similarity: str
    # IVF-PQ ANN structure (ops/ivfpq.IVFPQIndex) built at publish time when
    # the mapper asked for method ivf_pq and the segment is big enough — the
    # per-segment index-structure model of the k-NN plugin's codecs.
    ann: object | None = None
    nprobe_default: int = 8


@dataclass
class DeviceNestedVectorField:
    """A multi-vector field on the device: its rows, each row's document,
    and which rows may answer (`valid`: the row's document is live), made
    again whenever the segment's live bitmap is republished, so a deleted
    document's rows score as dead."""

    vectors: jnp.ndarray          # float32 [r_pad, dims]
    norms_sq: jnp.ndarray         # float32 [r_pad]
    parent: jnp.ndarray           # int32 [r_pad], padding rows -1
    valid: jnp.ndarray            # bool [r_pad]
    rows: int                     # rows before padding
    dims: int
    similarity: str
    parent_host: np.ndarray       # int32 [rows]: what `valid` is made from


def _nested_valid(parent: np.ndarray, live: np.ndarray,
                  r_pad: int) -> np.ndarray:
    """bool [r_pad]: the rows whose document is live (padding: False)."""
    valid = np.zeros(r_pad, dtype=bool)
    valid[: len(parent)] = live[parent]
    return valid


@dataclass
class DeviceSegment:
    name: str
    n_docs: int
    n_pad: int
    live: jnp.ndarray             # bool [n_pad] (padding rows are False)
    text_fields: dict[str, DeviceTextField]
    keyword_fields: dict[str, DeviceKeywordField]
    numeric_fields: dict[str, DeviceNumericField]
    vector_fields: dict[str, DeviceVectorField]
    # residency-ledger handles for this segment's device arrays, keyed by
    # logical part ("<field>", "_live", "ivfpq:<field>"): the engine frees
    # them when it retires the segment (merge, replicated-install, close)
    allocations: dict | None = None
    # the chip every column above was put on (None: the default device)
    device: object | None = None
    nested_vector_fields: dict[str, DeviceNestedVectorField] | None = None

    def with_live(self, live_host: np.ndarray) -> "DeviceSegment":
        """Republishes the deletes bitmap (refresh after deletes)."""
        live = np.zeros(self.n_pad, dtype=bool)
        live[: self.n_docs] = live_host[: self.n_docs]
        live_dev = jax.device_put(live, self.device)
        # the republished bitmap supersedes the old one on device: swap the
        # ledger allocation so residency tracks the PUBLISHED set (column
        # allocations move to the new segment object unchanged)
        allocs = dict(self.allocations or {})
        old_live = allocs.pop("_live", None)
        if old_live is not None:
            old_live.free(reason="live-republished")
        allocs["_live"] = default_ledger.register(
            KIND_COLUMN, array_nbytes(live_dev), field="_live")
        # a multi-vector field's rows answer only for live documents
        nested = {}
        for fname, nvf in (self.nested_vector_fields or {}).items():
            nested[fname] = dataclasses.replace(nvf, valid=jax.device_put(
                _nested_valid(nvf.parent_host, live, nvf.valid.shape[0]),
                self.device))
            old_valid = allocs.pop(f"_live:{fname}", None)
            if old_valid is not None:
                old_valid.free(reason="live-republished")
            allocs[f"_live:{fname}"] = default_ledger.register(
                KIND_COLUMN, array_nbytes(nested[fname].valid),
                field=f"_live:{fname}")
        return DeviceSegment(
            name=self.name,
            n_docs=self.n_docs,
            n_pad=self.n_pad,
            live=live_dev,
            text_fields=self.text_fields,
            keyword_fields=self.keyword_fields,
            numeric_fields=self.numeric_fields,
            vector_fields=self.vector_fields,
            allocations=allocs,
            device=self.device,
            nested_vector_fields=nested,
        )

    def free_allocations(self, reason: str = "retired") -> None:
        """Release this segment's residency-ledger entries (the engine's
        retirement hook; idempotent)."""
        for alloc in (self.allocations or {}).values():
            alloc.free(reason=reason)


def _maybe_build_ann(vf, device, field: str | None = None):
    """Build an IVF-PQ index for a sealed vector column when asked for.

    Returns (ann_or_None, nprobe_default). ANN serves l2/cosine; dot_product
    stays exact (IVF residual geometry doesn't carry MIPS) — matching the
    k-NN plugin, where engine support varies per space type.
    """
    method = vf.method or {}
    name = str(method.get("name", "")).lower().replace("-", "_")
    if name not in ("ivf_pq", "ivfpq", "ivf"):
        return None, 8
    if vf.similarity not in ("l2_norm", "l2", "cosine", "cosinesimil"):
        return None, 8
    params = method.get("parameters") or {}
    n_present = int(vf.present.sum())
    from opensearch_tpu.ops import ivfpq

    min_train = int(params.get("min_train", ivfpq.MIN_TRAIN_DOCS))
    if n_present < min_train:
        return None, 8
    dims = vf.dims
    m = int(params.get("m", params.get("code_size", ivfpq.DEFAULT_M)))
    while dims % m != 0 and m > 1:
        m -= 1
    doc_ids = np.nonzero(vf.present)[0].astype(np.int32)
    t0 = time.perf_counter_ns()
    from opensearch_tpu.telemetry.device_ledger import upload_scope

    # field attribution for the slab's ledger allocation (ivfpq.build
    # registers it; index/shard/generation come from the engine's scope)
    with upload_scope(field=field):
        ann = ivfpq.build(
            vf.vectors[doc_ids],
            doc_ids,
            nlist=int(params.get("nlist", ivfpq.DEFAULT_NLIST)),
            m=m,
            ks=int(params.get("ks", ivfpq.DEFAULT_KS)),
            iters=int(params.get("iters", 10)),
            normalized=vf.similarity in ("cosine", "cosinesimil"),
            device=device,
        )
    with _ann_build_lock:
        _ann_build_stats["builds"] += 1
        _ann_build_stats["build_wall_ns"] += time.perf_counter_ns() - t0
        # the newest generation published by THIS process: serving batch
        # keys carry it, so a stats reader can line launches up with builds
        _ann_build_stats["last_generation"] = ann.build_generation
    return ann, int(params.get("nprobe", ivfpq.DEFAULT_NPROBE))


def to_device(seg: HostSegment, device=None) -> DeviceSegment:
    """Publish `seg`'s columns on `device` (the chip of the segment's
    shard, `parallel.mesh.shard_device`; None: the default device). Each
    column goes from host memory to that chip and touches no other."""
    n_pad = pad_size(seg.n_docs)
    put = lambda a: jax.device_put(a, device)
    # residency accounting: one ledger allocation per published column
    # (bytes == the device arrays' summed .nbytes); index/shard/generation
    # and device attribution ride the engine's upload_scope
    allocs: dict[str, object] = {}

    def track(fname: str, *arrays) -> None:
        allocs[fname] = default_ledger.register(
            KIND_COLUMN, array_nbytes(*arrays), field=fname)

    live = np.zeros(n_pad, dtype=bool)
    live[: seg.n_docs] = seg.live

    text_fields: dict[str, DeviceTextField] = {}
    for fname, tf in seg.text_fields.items():
        p_pad = pad_size(max(len(tf.postings_docs), 1))
        text_fields[fname] = dtf = DeviceTextField(
            postings_docs=put(_pad1(tf.postings_docs, p_pad)),
            postings_tfs=put(_pad1(tf.postings_tfs, p_pad)),
            postings_dl=put(_pad1(tf.doc_len[tf.postings_docs], p_pad)),
            doc_len=put(_pad1(tf.doc_len, n_pad)),
        )
        track(fname, dtf.postings_docs, dtf.postings_tfs, dtf.postings_dl,
              dtf.doc_len)

    keyword_fields: dict[str, DeviceKeywordField] = {}
    for fname, kf in seg.keyword_fields.items():
        e_pad = pad_size(max(len(kf.mv_ords), 1))
        keyword_fields[fname] = dkf = DeviceKeywordField(
            first_ord=put(_pad1(kf.first_ord, n_pad, fill=-1)),
            mv_ords=put(_pad1(kf.mv_ords, e_pad, fill=-2)),
            mv_docs=put(_pad1(kf.mv_docs, e_pad, fill=0)),
        )
        track(fname, dkf.first_ord, dkf.mv_ords, dkf.mv_docs)

    numeric_fields: dict[str, DeviceNumericField] = {}
    for fname, nf in seg.numeric_fields.items():
        present = put(_pad1(nf.present, n_pad, fill=False))
        if nf.kind == "int":
            hi, lo = split_i64(nf.values_i64)
            numeric_fields[fname] = dnf = DeviceNumericField(
                kind="int",
                hi=put(_pad1(hi, n_pad)),
                lo=put(_pad1(lo, n_pad)),
                values=None,
                present=present,
            )
        else:
            numeric_fields[fname] = dnf = DeviceNumericField(
                kind="float",
                hi=None,
                lo=None,
                values=put(_pad1(nf.values_f64.astype(np.float32), n_pad)),
                present=present,
            )
        track(fname, dnf.hi, dnf.lo, dnf.values, dnf.present)

    vector_fields: dict[str, DeviceVectorField] = {}
    for fname, vf in seg.vector_fields.items():
        vecs = _pad1(vf.vectors, n_pad)
        ann, nprobe_default = _maybe_build_ann(vf, device, field=fname)
        vector_fields[fname] = dvf = DeviceVectorField(
            vectors=put(vecs),
            norms_sq=put((vecs.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)),
            present=put(_pad1(vf.present, n_pad, fill=False)),
            dims=vf.dims,
            similarity=vf.similarity,
            ann=ann,
            nprobe_default=nprobe_default,
        )
        track(fname, dvf.vectors, dvf.norms_sq, dvf.present)
        if ann is not None and getattr(ann, "allocation", None) is not None:
            allocs[f"ivfpq:{fname}"] = ann.allocation

    nested_vector_fields: dict[str, DeviceNestedVectorField] = {}
    for fname, vf in seg.nested_vector_fields.items():
        rows = vf.vectors.shape[0]
        r_pad = pad_size(rows)
        vecs = _pad1(vf.vectors, r_pad)
        nested_vector_fields[fname] = nvf = DeviceNestedVectorField(
            vectors=put(vecs),
            norms_sq=put((vecs.astype(np.float64) ** 2).sum(axis=1)
                         .astype(np.float32)),
            parent=put(_pad1(vf.parent, r_pad, fill=-1)),
            valid=put(_nested_valid(vf.parent, live, r_pad)),
            rows=rows,
            dims=vf.dims,
            similarity=vf.similarity,
            parent_host=vf.parent,
        )
        track(fname, nvf.vectors, nvf.norms_sq, nvf.parent)
        track(f"_live:{fname}", nvf.valid)

    live_dev = put(live)
    allocs["_live"] = default_ledger.register(
        KIND_COLUMN, array_nbytes(live_dev), field="_live")
    return DeviceSegment(
        name=seg.name,
        n_docs=seg.n_docs,
        n_pad=n_pad,
        live=live_dev,
        text_fields=text_fields,
        keyword_fields=keyword_fields,
        numeric_fields=numeric_fields,
        vector_fields=vector_fields,
        allocations=allocs,
        device=device,
        nested_vector_fields=nested_vector_fields,
    )

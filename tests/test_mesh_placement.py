"""One index of four shards over four devices (PR 28): served answers equal
numpy brute force, every device holds its own shard's bytes and nothing
else, and the one-shard index runs the same code unchanged.

Small and seeded: 4 shards x ~300 rows x 16-d, integer-valued vectors (so
float32 distances are exact and a planted tie is a tie). The checks need
four devices: where this process has fewer (no `tests/conftest.py`), each
runs in a child with XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

DIMS, DOCS, K = 16, 1200, 10
TIE = [3.0] * DIMS                      # one vector under several ids
TIE_IDS = [f"tie{i}" for i in range(8)]
MAPPING = {"properties": {
    "v": {"type": "knn_vector", "dimension": DIMS, "space_type": "l2"},
    "tag": {"type": "keyword"}}}


def _corpus() -> dict:
    rng = np.random.default_rng(20261001)
    rows = {f"d{i}": rng.integers(0, 32, DIMS).astype(np.float32)
            for i in range(DOCS - len(TIE_IDS))}
    rows.update({i: np.asarray(TIE, np.float32) for i in TIE_IDS})
    return rows


def _node(path: Path, shards: int):
    from opensearch_tpu.node import TpuNode
    from opensearch_tpu.search import distributed_serving

    distributed_serving.clear_caches()
    node = TpuNode(path / f"data{shards}")
    node.create_index("mesh4", {
        "settings": {"number_of_shards": shards, "number_of_replicas": 0},
        "mappings": MAPPING})
    node.bulk([("index", {"_index": "mesh4", "_id": doc_id},
                {"v": vec.tolist(), "tag": "a" if n % 3 == 0 else "b"})
               for n, (doc_id, vec) in enumerate(_corpus().items())],
              refresh=True)
    return node


def _where(node) -> dict:
    """doc id -> (shard, segment, doc): the tie-break's own coordinates."""
    out = {}
    for s, shard in sorted(node.indices["mesh4"].shards.items()):
        for g, (host, _dev) in enumerate(
                shard.engine.acquire_searcher().segments):
            out.update({doc_id: (s, g, d)
                        for d, doc_id in enumerate(host.doc_ids)})
    return out


def _brute_force(node, query, keep=lambda doc_id: True) -> list:
    """[(id, score)] of the K nearest among the rows `keep` admits, in the
    served order (-score, shard, segment, doc); float64 distances."""
    rows = {i: v for i, v in _corpus().items() if keep(i)}
    where = _where(node)
    q = np.asarray(query, np.float64)
    d2 = {i: float(((v.astype(np.float64) - q) ** 2).sum())
          for i, v in rows.items()}
    order = sorted(rows, key=lambda i: (d2[i], *where[i]))[:K]
    return [(i, 1.0 / (1.0 + d2[i])) for i in order]


def _body(query, **knn) -> dict:
    return {"size": K, "query": {"knn": {"v": {
        "vector": [float(x) for x in query], "k": K, **knn}}}}


def _same(resp, want) -> None:
    assert resp["_shards"]["failed"] == 0
    hits = resp["hits"]["hits"]
    assert [h["_id"] for h in hits] == [i for i, _ in want]
    np.testing.assert_allclose([h["_score"] for h in hits],
                               [s for _, s in want], rtol=2e-6)


def _queries(n: int) -> list:
    rng = np.random.default_rng(7)
    return [rng.integers(0, 32, DIMS).astype(np.float32) for _ in range(n)]


def _at_once(node, bodies: list) -> list:
    out, errors = [None] * len(bodies), []
    gate = threading.Barrier(len(bodies))

    def one(i):
        gate.wait()
        try:
            out[i] = node.search("mesh4", bodies[i])
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    return out


def _program_keys() -> set:
    from opensearch_tpu.search import distributed_serving

    return set(distributed_serving._PROGRAM_CACHE)


# -- the checks (each runs here, or in a child with four devices) -----------


def check_answers_equal_brute_force(path: Path) -> None:
    """(a) B = 1 and a coalesced B = 5 (padded to 8), with an exact tie
    across shards resolved by (-score, shard, segment, doc)."""
    from opensearch_tpu.search import distributed_serving

    node = _node(path, 4)
    try:
        where = _where(node)
        assert len({where[i][0] for i in TIE_IDS}) >= 2, \
            "the planted tie has to span shards"
        tie_query = np.asarray(TIE, np.float32) + 1.0
        for q in [tie_query, *_queries(3)]:
            _same(node.search("mesh4", _body(q)), _brute_force(node, q))
        tied = node.search("mesh4", _body(tie_query))["hits"]["hits"]
        assert [h["_id"] for h in tied[:len(TIE_IDS)]] == \
            sorted(TIE_IDS, key=where.get)
        assert len({h["_score"] for h in tied[:len(TIE_IDS)]}) == 1

        batcher = node.knn_batcher
        batcher.configure(enabled=True, max_batch_size=5, max_wait_ms=5_000)
        batcher.reset()
        batched = distributed_serving.stats["batched_queries"]
        try:
            qs = [tie_query, *_queries(4)]
            got = _at_once(node, [_body(q) for q in qs])
        finally:
            batcher.configure(enabled=True, max_batch_size=32, max_wait_ms=2)
        stats = batcher.snapshot_stats()
        assert (stats["dispatches"], stats["merged_queries"]) == (1, 5)
        for q, resp in zip(qs, got):
            _same(resp, _brute_force(node, q))
        # (devices, shards, ..., b_pad at [7]): B = 1 and B = 5 -> 8
        assert {(k[0], k[1], k[7]) for k in _program_keys()} == \
            {(4, 4, 1), (4, 4, 8)}
        assert distributed_serving.stats["batched_queries"] == batched + 5
    finally:
        node.close()


def check_filtered_query_equals_brute_force(path: Path) -> None:
    from opensearch_tpu.search import distributed_serving

    node = _node(path, 4)
    try:
        tagged = {i for n, i in enumerate(_corpus()) if n % 3 == 0}
        before = distributed_serving.stats["filtered"]
        for q in _queries(2):
            resp = node.search("mesh4", _body(
                q, filter={"term": {"tag": "a"}}))
            _same(resp, _brute_force(node, q, keep=tagged.__contains__))
        assert distributed_serving.stats["filtered"] == before + 2
    finally:
        node.close()


def check_every_device_holds_its_own_shard(path: Path) -> None:
    """(b) after the build: per-chip ledger figures = `addressable_shards`
    figures, segments on their shard's chip alone, nothing staged."""
    import jax

    from opensearch_tpu.parallel.mesh import shard_device
    from opensearch_tpu.search import distributed_serving
    from opensearch_tpu.telemetry.device_ledger import (
        default_ledger,
        device_bytes,
    )

    node = _node(path, 4)
    try:
        node.search("mesh4", _body(_queries(1)[0]))
        devices = [str(d) for d in jax.devices()[:4]]
        assert [str(shard_device(s, 4)) for s in range(4)] == devices

        held = dict.fromkeys(devices, 0)    # from the arrays themselves
        for s, shard in sorted(node.indices["mesh4"].shards.items()):
            for _host, dev in shard.engine.acquire_searcher().segments:
                arrays = [dev.live]
                for vf in dev.vector_fields.values():
                    arrays += [vf.vectors, vf.norms_sq, vf.present]
                for kf in dev.keyword_fields.values():
                    arrays += [kf.first_ord, kf.mv_ords, kf.mv_docs]
                for a in arrays:
                    assert {str(d) for d in a.devices()} == {devices[s]}
                    held[devices[s]] += int(a.nbytes)
        (bundle,) = distributed_serving.registry._bundles.values()
        per_chip = device_bytes(bundle.vectors, bundle.norms_sq, bundle.valid)
        share = bundle.n_flat * (DIMS * 4 + 4 + 1)
        assert per_chip == dict.fromkeys(devices, share)
        for dev, nbytes in per_chip.items():
            held[dev] += nbytes

        booked = dict.fromkeys(devices, 0)  # from the ledger's rows
        for row in default_ledger.structures(index="mesh4"):
            for dev, nbytes in row.get(
                    "by_device", {row["device"]: row["bytes"]}).items():
                booked[dev] += nbytes
        assert booked == held
        totals = default_ledger.device_totals()
        assert all(totals[d] >= booked[d] for d in devices)
        assert "mesh[4]" not in totals
        mean = sum(booked.values()) / 4
        assert max(booked.values()) <= 1.1 * mean   # equal to within padding

        (span,) = [s for s in node.telemetry.tracer.finished_spans()
                   if s.name == "mesh.bundle_build"]
        assert span.attributes == {
            "devices": 4, "shards": 4, "bytes_per_device": share,
            "staging_bytes": 0}
    finally:
        node.close()


def check_one_shard_is_unchanged(path: Path) -> None:
    """(c) the same code on a mesh of one: answers, program key, device."""
    import jax

    from opensearch_tpu.search import distributed_serving

    node = _node(path, 1)
    try:
        for q in _queries(3):
            _same(node.search("mesh4", _body(q)), _brute_force(node, q))
        (key,) = _program_keys()
        # (n_devices, s, n_flat, dims, k_shard, k_final, similarity, b_pad)
        assert key[:6] == (1, 1, 2048, DIMS, K, K) and key[7] == 1
        (bundle,) = distributed_serving.registry._bundles.values()
        first = jax.devices()[0]
        assert bundle.vectors.devices() == {first}
        (shard,) = node.indices["mesh4"].shards.values()
        for _host, dev in shard.engine.acquire_searcher().segments:
            assert dev.live.devices() == {first}
    finally:
        node.close()


CHECKS = [check_answers_equal_brute_force,
          check_filtered_query_equals_brute_force,
          check_every_device_holds_its_own_shard,
          check_one_shard_is_unchanged]


@pytest.mark.parametrize("check", CHECKS, ids=lambda c: c.__name__[6:])
def test_four_shards_on_four_devices(check, tmp_path):
    import jax

    if len(jax.devices()) >= 4:
        check(tmp_path)
        return
    env = {k: v for k, v in os.environ.items() if k != "JAX_NUM_CPU_DEVICES"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, __file__, check.__name__, str(tmp_path)], env=env,
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-4000:]


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    globals()[sys.argv[1]](Path(sys.argv[2]))

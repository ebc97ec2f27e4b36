"""Native C++ runtime: translog writer + varint codec.

Reference context: the WAL append path (Translog.java:606) and postings
codecs are the reference's native-speed loops; ours live in
native/tlog_codec.cpp behind ctypes with Python fallbacks (SURVEY.md §2
"Native equivalents" column).
"""

import json
import struct
import zlib

import numpy as np
import pytest

from opensearch_tpu import native
from opensearch_tpu.index.translog import Checkpoint, Translog


class TestVarintCodec:
    def test_roundtrip_ascending(self):
        docs = np.sort(np.random.default_rng(0).integers(0, 10_000, 5000)
                       ).astype(np.int32)
        enc = native.varint_encode(docs)
        # ascending deltas ~small: must beat raw int32
        assert len(enc) < docs.nbytes
        out = native.varint_decode(enc, len(docs))
        assert np.array_equal(out, docs)

    def test_roundtrip_with_negative_deltas(self):
        # term-boundary resets: values drop back down (CSR postings shape)
        docs = np.asarray([5, 9, 1000, 3, 4, 7, 0, 2**31 - 1, 0], np.int32)
        out = native.varint_decode(native.varint_encode(docs), len(docs))
        assert np.array_equal(out, docs)

    def test_empty(self):
        assert native.varint_encode(np.zeros(0, np.int32)) == b""
        assert native.varint_decode(b"").size == 0

    def test_python_fallback_matches_native(self, monkeypatch):
        docs = np.asarray([10, 3, 500, 499, 1_000_000], np.int32)
        enc_native = native.varint_encode(docs)
        monkeypatch.setattr(native, "_load", lambda: None)
        enc_py = native.varint_encode(docs)
        assert enc_py == enc_native
        out_py = native.varint_decode(enc_native)
        assert np.array_equal(out_py, docs)


def test_library_is_named_by_its_source_content(tmp_path, monkeypatch):
    """A binary carried over in a copied tree (mtimes and all) must never
    be loaded for a different source: the file name follows the content."""
    built_for = native._lib_path().name
    edited = tmp_path / "tlog_codec.cpp"
    edited.write_bytes(native._SRC.read_bytes() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", edited)
    assert native._lib_path().name != built_for


class TestNativeTlog:
    @pytest.mark.skipif(not native.native_available(),
                        reason="no C++ toolchain")
    def test_crc_matches_zlib(self):
        lib = native._load()
        for payload in (b"", b"x", b"hello world" * 100):
            assert lib.osn_crc32(payload, len(payload)) == zlib.crc32(payload)

    @pytest.mark.skipif(not native.native_available(),
                        reason="no C++ toolchain")
    def test_writer_format_readable_by_python(self, tmp_path):
        path = tmp_path / "gen.tlog"
        w = native.NativeTlogWriter(path, 0)
        payloads = [json.dumps({"op": "index", "id": str(i)}).encode()
                    for i in range(100)]
        locations = [w.append(p) for p in payloads]
        w.sync()
        assert w.tell() == sum(len(p) + 8 for p in payloads)
        w.close()
        data = path.read_bytes()
        header = struct.Struct("<II")
        pos = 0
        for i, expected in enumerate(payloads):
            assert locations[i] == pos
            length, crc = header.unpack_from(data, pos)
            pos += header.size
            payload = data[pos: pos + length]
            assert payload == expected and zlib.crc32(payload) == crc
            pos += length
        assert pos == len(data)

    @pytest.mark.skipif(not native.native_available(),
                        reason="no C++ toolchain")
    def test_open_truncates_garbage(self, tmp_path):
        path = tmp_path / "gen.tlog"
        path.write_bytes(b"good" + b"GARBAGE")
        w = native.NativeTlogWriter(path, 4)
        w.append(b"x")
        w.sync()
        w.close()
        assert path.read_bytes()[:4] == b"good"
        assert b"GARBAGE" not in path.read_bytes()


class TestTranslogIntegration:
    def test_roundtrip_through_engine_format(self, tmp_path):
        tlog = Translog(tmp_path / "t")
        ops = [{"op": "index", "id": str(i), "seq_no": i, "version": 1,
                "source": {"n": i}} for i in range(50)]
        for op in ops:
            tlog.add(op)
        tlog.sync()
        tlog.close()
        # fresh instance recovers every op
        tlog2 = Translog(tmp_path / "t")
        recovered = list(tlog2.read_ops())
        assert recovered == ops
        assert tlog2.checkpoint.max_seq_no == 49
        tlog2.close()

    def test_roll_generation_native(self, tmp_path):
        tlog = Translog(tmp_path / "t")
        tlog.add({"op": "index", "id": "a", "seq_no": 0, "version": 1})
        tlog.roll_generation()
        tlog.add({"op": "index", "id": "b", "seq_no": 1, "version": 1})
        tlog.sync()
        assert tlog.current_generation == 2
        assert [o["id"] for o in tlog.read_ops()] == ["a", "b"]
        tlog.close()

    def test_unsynced_tail_discarded_on_recovery(self, tmp_path):
        tlog = Translog(tmp_path / "t")
        tlog.add({"op": "index", "id": "synced", "seq_no": 0, "version": 1})
        tlog.sync()
        tlog.add({"op": "index", "id": "unsynced", "seq_no": 1, "version": 1})
        # crash: no sync; writer buffer may or may not have hit the file
        tlog._close_writer()
        tlog2 = Translog(tmp_path / "t")
        ids = [o["id"] for o in tlog2.read_ops()]
        assert ids == ["synced"]
        tlog2.close()


class TestSegmentVarintPersistence:
    def test_segment_roundtrip_uses_varint(self, tmp_path):
        from opensearch_tpu.index.analysis import AnalysisRegistry
        from opensearch_tpu.index.mapper import MapperService
        from opensearch_tpu.index.segment import (
            SegmentBuilder, load_segment, save_segment,
        )

        ms = MapperService({"properties": {"t": {"type": "text"}}},
                           AnalysisRegistry.from_index_settings(None))
        b = SegmentBuilder(ms, "s0")
        for i in range(40):
            b.add(ms.parse_document(str(i), {"t": f"word{i % 7} common"}),
                  seq_no=i)
        seg = b.build()
        save_segment(seg, tmp_path)
        loaded = load_segment(tmp_path, "s0")
        tf0, tf1 = seg.text_fields["t"], loaded.text_fields["t"]
        assert np.array_equal(tf0.postings_docs, tf1.postings_docs)
        assert np.array_equal(tf0.term_offsets, tf1.term_offsets)
        # the stored representation really is the varint format
        arrays = np.load(tmp_path / "s0.npz")
        assert "text:t:docs_vint" in arrays

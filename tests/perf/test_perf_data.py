"""The yardstick's data and reference: seeded, and right."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf.data import (Mixture, Reference, bulk_bodies,  # noqa: E402
                       round_bf16)

BIG_SEED = 2**31 + 12345          # more than 32 signed bits hold


def test_same_seed_same_inputs_and_rows_do_not_depend_on_the_size():
    a, b = Mixture(BIG_SEED, 16), Mixture(BIG_SEED, 16)
    assert np.array_equal(a.corpus(1000), b.corpus(1000))
    assert np.array_equal(a.corpus(70_000)[:1000], a.corpus(1000))
    assert np.array_equal(a.queries(9, 0, 8), b.queries(9, 0, 8))
    assert not np.array_equal(a.queries(9, 0, 8), a.queries(9, 1, 8))
    assert not np.array_equal(a.queries(9, 0, 8), a.queries(BIG_SEED, 0, 8))
    assert not np.array_equal(a.corpus(100), Mixture(BIG_SEED + 1, 16).corpus(100))


def test_corpus_is_integer_valued_in_byte_range_queries_are_not():
    m = Mixture(7, 32)
    c, q = m.corpus(5000), m.queries(1, 0, 64)
    assert c.dtype == np.float32 and np.array_equal(c, np.rint(c))
    assert c.min() >= 0 and c.max() <= 255
    assert not np.array_equal(q, np.rint(q))


def test_bulk_bodies_parse_back_to_the_rows():
    c = Mixture(3, 12).corpus(25)
    c[0, :3] = (0, 7, 255)
    seen = []
    for lo, n, body in bulk_bodies(c, "v", 10):
        lines = body.decode().splitlines()
        assert len(lines) == 2 * n and body.endswith(b"\n")
        for i in range(n):
            assert json.loads(lines[2 * i]) == {"index": {"_id": str(lo + i)}}
            seen.append(json.loads(lines[2 * i + 1])["v"])
    assert np.array_equal(np.asarray(seen, np.float32), c)


def test_bulk_bodies_refuse_what_they_cannot_lay_out():
    with pytest.raises(ValueError):
        list(bulk_bodies(np.full((2, 4), 1000.0, np.float32), "v", 10))


def test_round_bf16_ties_to_even_and_keeps_small_integers():
    ints = np.arange(0, 257, dtype=np.float32)
    assert np.array_equal(round_bf16(ints), ints)
    x = np.asarray([1.0 + 2.0 ** -8, 1.0 + 3 * 2.0 ** -8, 257.0], np.float32)
    assert round_bf16(x).tolist() == [1.0, 1.0 + 2.0 ** -6, 256.0]


@pytest.mark.parametrize("n", [4096, 5000, 20_000])   # whole, ragged, chunks
def test_reference_topk_equals_naive_brute_force(n):
    m = Mixture(11, 24)
    c, q = m.corpus(n), m.queries(1, 0, 20)
    ref = Reference(c)
    ids, d2 = ref.topk(q, 10)
    for i in range(len(q)):
        diff = c.astype(np.float64) - q[i].astype(np.float64)
        full = np.einsum("nd,nd->n", diff, diff)
        order = np.lexsort((np.arange(n), full))[:10]
        assert ids[i].tolist() == order.tolist()
        assert np.allclose(d2[i], full[order], rtol=0, atol=1e-9)


def test_the_lower_precision_control_scores_differently():
    m = Mixture(11, 128)
    c, q = m.corpus(4096), m.queries(1, 0, 32)
    ref = Reference(c)
    ids, scores = ref.topk_lower_precision(q, 10)
    assert np.all(np.diff(scores, axis=1) <= 0)
    gaps = []
    for i in range(len(q)):
        want = 1.0 / (1.0 + ref.d2(q[i], ids[i]))
        gaps.append(np.max(np.abs(scores[i] - want) / want))
    # one bfloat16 pass is orders of magnitude outside float32 rounding
    assert max(gaps) > 1e-3


def test_the_reference_rests_while_its_gate_is_closed_and_answers_the_same():
    import threading

    m = Mixture(5, 16)
    ref = Reference(m.corpus(3000))
    q = m.queries(2, 0, 40)
    want_ids, want_d2 = ref.topk(q, 10)
    gate, got = threading.Event(), {}
    worker = threading.Thread(
        target=lambda: got.update(out=ref.topk(q, 10, gate=gate)), daemon=True)
    worker.start()
    worker.join(timeout=0.5)
    assert worker.is_alive() and not got      # closed: not one chunk is done
    gate.set()
    worker.join(timeout=60)
    assert not worker.is_alive()
    assert np.array_equal(got["out"][0], want_ids)
    assert np.array_equal(got["out"][1], want_d2)

"""The comparison that decides `correct`, on replies made by hand."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perf import judge  # noqa: E402
from perf.data import Mixture, Reference  # noqa: E402
from perf.traffic import Window  # noqa: E402

LIMITS = {"failed": {"max": 0}, "malformed": {"max": 0},
          "score_gap": {"max": 1e-3}, "rank_gap": {"max": 1e-3},
          "recall_at_10": {"min": 0.95}}


@pytest.fixture(scope="module")
def world():
    m = Mixture(5, 32)
    c, q = m.corpus(3000), m.queries(1, 0, 6)
    ref = Reference(c)
    ids, d2 = ref.topk(q, 10)
    return c, q, ref, ids, d2


def window_of(replies) -> Window:
    w = Window(seconds=1.0)
    for i, (status, payload) in enumerate(replies):
        w.query.append(i)
        w.t_from.append(0.0)
        w.t_done.append(0.1)
        w.late_s.append(0.0)
        w.status.append(status)
        w.payload.append(payload)
    return w


def sound(world, i):
    _, _, _, ids, d2 = world
    return ids[i].copy(), 1.0 / (1.0 + d2[i])


def verdict(world, replies):
    _, q, ref, ids, d2 = world
    got = judge.judge_window(window_of(replies), q, ref, ids, d2, 10, 1)
    return judge.compare(got["numbers"], LIMITS), got


def test_sound_replies_are_correct(world):
    replies = [(200, judge.reply_bytes(*sound(world, i), 1)) for i in range(6)]
    (correct, checks), got = verdict(world, replies)
    assert correct and got["ok"].all()
    assert got["numbers"]["recall_at_10"] == 1.0
    assert got["numbers"]["rank_gap"] == 0.0
    assert got["numbers"]["score_gap"] < 1e-12
    assert set(checks) == set(LIMITS)
    assert all(set(c) == {"value", "limit", "ok"} for c in checks.values())


def far_document(world, i):
    _, q, ref, ids, _ = world
    return int(np.argmax(ref.d2(q[i], np.arange(ref.corpus.shape[0]))))


def alter_id(world):
    ids, scores = sound(world, 0)
    ids[-1] = far_document(world, 0)
    return 200, judge.reply_bytes(ids, scores, 1)


def alter_score(world):
    ids, scores = sound(world, 0)
    return 200, judge.reply_bytes(ids, scores * 1.01, 1)


def http_500(world):
    return 500, b'{"error": "boom"}'


def never_answered(world):
    return 0, b""


def shard_failed(world):
    body = json.loads(judge.reply_bytes(*sound(world, 0), 1))
    body["_shards"] = {"total": 1, "successful": 0, "failed": 1}
    return 200, json.dumps(body).encode()


def nine_hits(world):
    ids, scores = sound(world, 0)
    return 200, judge.reply_bytes(ids[:9], scores[:9], 1)


def duplicate_hit(world):
    ids, scores = sound(world, 0)
    ids[1] = ids[0]
    return 200, judge.reply_bytes(ids, scores, 1)


def out_of_order(world):
    ids, scores = sound(world, 0)
    return 200, judge.reply_bytes(ids[::-1], scores[::-1], 1)


def unknown_document(world):
    ids, scores = sound(world, 0)
    ids[0] = 10**9
    return 200, judge.reply_bytes(ids, scores, 1)


def not_json(world):
    return 200, b"<html>"


@pytest.mark.parametrize("fault, number", [
    (alter_id, "rank_gap"), (alter_id, "score_gap"),
    (alter_score, "score_gap"), (http_500, "failed"),
    (never_answered, "failed"), (shard_failed, "failed"),
    (nine_hits, "malformed"), (duplicate_hit, "malformed"),
    (out_of_order, "malformed"), (unknown_document, "malformed"),
    (not_json, "malformed")])
def test_one_bad_reply_among_sound_ones_is_not_correct(world, fault, number):
    replies = [fault(world)] + [
        (200, judge.reply_bytes(*sound(world, i), 1)) for i in range(1, 6)]
    (correct, checks), got = verdict(world, replies)
    assert not correct
    assert not checks[number]["ok"]


def test_recall_below_the_floor_fails_where_scores_are_right(world):
    # an ANN answer: right scores, but half the neighbours missed
    _, q, ref, ids, _ = world
    replies = []
    for i in range(6):
        pool = np.argsort(ref.d2(q[i], np.arange(ref.corpus.shape[0])))
        got = np.concatenate([pool[:5], pool[10:15]])
        replies.append((200, judge.reply_bytes(
            got, 1.0 / (1.0 + ref.d2(q[i], got)), 1)))
    limits = {k: LIMITS[k] for k in ("score_gap", "recall_at_10")}
    out = judge.judge_window(window_of(replies), q, ref, ids,
                             world[4], 10, 1)
    correct, checks = judge.compare(out["numbers"], limits)
    assert out["numbers"]["recall_at_10"] == 0.5
    assert checks["score_gap"]["ok"] and not checks["recall_at_10"]["ok"]
    assert not correct


def test_the_lower_precision_control_fails_the_comparison(world):
    c, q, ref, _, _ = world
    m = Mixture(5, 128)
    c, q = m.corpus(3000), m.queries(1, 0, 16)
    ref = Reference(c)
    ids, d2 = ref.topk(q, 10)
    low_ids, low_scores = ref.topk_lower_precision(q, 10)
    w = window_of([(200, judge.reply_bytes(low_ids[i], low_scores[i], 1))
                   for i in range(16)])
    out = judge.judge_window(w, q, ref, ids, d2, 10, 1)
    correct, checks = judge.compare(out["numbers"], LIMITS)
    assert not correct and not checks["score_gap"]["ok"]

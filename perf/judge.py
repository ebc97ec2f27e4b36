"""The comparison that decides `correct`: every reply the window produced,
held to the plain reference over the same rows.

Numbers (each printed beside its limit; the limits are the configuration's,
under "limits" in its file):

  failed       requests never answered, answered with another status than
               200, or answered with `_shards.failed` > 0 / fewer shards
  malformed    replies that are not `size` distinct, known documents in
               descending score order
  count_gap    |`_count` - documents acknowledged by `_bulk`| (set-up)
  score_gap    widest |served _score - reference score of that document|
               relative to the reference score (1 / (1 + d2), float64)
  rank_gap     widest share by which a served document's reference score
               lies below the reference's k-th best (0 where every served
               document is among the reference's k best or ties with them)
  recall_at_10 mean over the replies of |served ids ∩ reference ids| / k
"""

from __future__ import annotations

import json

import numpy as np

from perf.data import Reference


def reply_bytes(ids, scores, shards: int) -> bytes:
    """A `_search` reply in the served shape (for the control, which puts
    the lower-precision reference in the program's place)."""
    return json.dumps({
        "_shards": {"total": shards, "successful": shards, "failed": 0},
        "hits": {"total": {"value": len(ids), "relation": "eq"},
                 "hits": [{"_id": str(int(i)), "_score": float(s)}
                          for i, s in zip(ids, scores)]}}).encode()


def judge_window(window, queries: np.ndarray, ref: Reference,
                 ref_ids: np.ndarray, ref_d2: np.ndarray, size: int,
                 shards: int) -> dict:
    """Per-request arrays (`ok`, `recall`) and the compared numbers."""
    n_docs = ref.corpus.shape[0]
    n = len(window)
    ok = np.zeros(n, bool)
    recall = np.full(n, np.nan)
    failed = malformed = 0
    score_gap = rank_gap = 0.0
    for i in range(n):
        if window.status[i] != 200:
            failed += 1
            continue
        try:
            resp = json.loads(window.payload[i])
            sh = resp["_shards"]
            hits = resp["hits"]["hits"]
            ids = np.asarray([int(h["_id"]) for h in hits], np.int64)
            scores = np.asarray([h["_score"] for h in hits], np.float64)
        except (ValueError, KeyError, TypeError):
            malformed += 1
            continue
        if sh["failed"] != 0 or sh["successful"] != shards \
                or sh["total"] != shards:
            failed += 1
            continue
        if (len(ids) != size or len(set(ids.tolist())) != size
                or ids.min() < 0 or ids.max() >= n_docs
                or not np.all(np.diff(scores) <= 0)):
            malformed += 1
            continue
        ok[i] = True
        q = window.query[i]
        want = 1.0 / (1.0 + ref.d2(queries[q], ids))
        score_gap = max(score_gap, float(np.max(np.abs(scores - want) / want)))
        kth = 1.0 / (1.0 + ref_d2[q, size - 1])
        rank_gap = max(rank_gap, float(np.max((kth - want) / kth)))
        recall[i] = len(set(ids.tolist())
                        & set(ref_ids[q, :size].tolist())) / size
    answered = recall[ok]
    return {
        "ok": ok, "recall": recall,
        "numbers": {
            "failed": failed, "malformed": malformed,
            "score_gap": score_gap, "rank_gap": max(rank_gap, 0.0),
            "recall_at_10": float(answered.mean()) if len(answered) else 0.0,
        },
    }


def compare(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers the
    configuration gives a limit to: {"max": x} or {"min": x}."""
    checks, correct = {}, True
    for name, lim in limits.items():
        value = numbers[name]
        if "max" in lim:
            good, text = value <= lim["max"], f"<= {lim['max']:g}"
        else:
            good, text = value >= lim["min"], f">= {lim['min']:g}"
        correct = correct and bool(good)
        checks[name] = {"value": value, "limit": text, "ok": bool(good)}
    return correct, checks

"""Doc-values filter primitives: boolean masks over the dense doc column.

The analog of Lucene filter clauses / points-range queries executing against
doc values (reference: index/query/* compiled through QueryShardContext into
Lucene queries). Every filter compiles to a [n_pad] bool mask; bool-query
composition is elementwise &, |, &~ on the VPU. Numeric ranges and a text
term's postings window are computed on the device from resident columns. A
keyword field's ordinals are not: `keyword_mask_from_postings` (numpy)
makes their mask on the host from the posting lists of the ordinals a query
names, so its work follows those lists and not the field's E pairs; the
caller uploads the mask.

int64 columns arrive as two int32 words (see segment.split_i64): range
comparison is lexicographic (hi, lo) with lo pre-offset so signed compare
behaves as unsigned — exact int64 semantics without x64 mode.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def i64_ge(hi: jnp.ndarray, lo: jnp.ndarray, qhi: jnp.ndarray, qlo: jnp.ndarray) -> jnp.ndarray:
    return (hi > qhi) | ((hi == qhi) & (lo >= qlo))


def i64_le(hi: jnp.ndarray, lo: jnp.ndarray, qhi: jnp.ndarray, qlo: jnp.ndarray) -> jnp.ndarray:
    return (hi < qhi) | ((hi == qhi) & (lo <= qlo))


def range_mask_i64(
    hi: jnp.ndarray,          # int32 [n_pad] high words
    lo: jnp.ndarray,          # int32 [n_pad] offset-encoded low words
    present: jnp.ndarray,     # bool [n_pad]
    gte_hi: jnp.ndarray, gte_lo: jnp.ndarray,   # scalar int32 lower bound words
    lte_hi: jnp.ndarray, lte_lo: jnp.ndarray,   # scalar int32 upper bound words
) -> jnp.ndarray:
    """Closed-interval int64 range; callers encode open/absent bounds as
    int64 min/max sentinels (gt x == gte x+1, lt x == lte x-1)."""
    return present & i64_ge(hi, lo, gte_hi, gte_lo) & i64_le(hi, lo, lte_hi, lte_lo)


def range_mask_f32(
    values: jnp.ndarray, present: jnp.ndarray,
    gte: jnp.ndarray, lte: jnp.ndarray,
    gt_open: jnp.ndarray, lt_open: jnp.ndarray,  # bool scalars: strict bounds
) -> jnp.ndarray:
    lower = jnp.where(gt_open, values > gte, values >= gte)
    upper = jnp.where(lt_open, values < lte, values <= lte)
    return present & lower & upper


def keyword_mask_from_postings(kf, ords, n_pad: int) -> tuple[np.ndarray, int]:
    """Host mask of the docs holding any of `ords` in one keyword field.

    `kf` is a HostKeywordField whose ordinal-major view is built
    (`build_postings`); `ords` is any collection of ordinals, a `range` for
    a range on ordinals; ordinals the segment does not hold (negative) are
    skipped. Each run of consecutive ordinals is ONE slice of `ord_docs`.
    Returns (bool [n_pad], posting entries scattered)."""
    mask = np.zeros(n_pad, bool)
    if isinstance(ords, range) and ords.step == 1:
        runs = [(ords.start, ords.stop)] if len(ords) else []
    else:
        held = np.unique(np.asarray(list(ords), np.int64))
        held = held[held >= 0]
        cuts = np.flatnonzero(np.diff(held) != 1) + 1
        runs = [(int(run[0]), int(run[-1]) + 1)
                for run in np.split(held, cuts) if len(run)]
    postings = 0
    for lo, hi in runs:
        a, b = int(kf.ord_offsets[lo]), int(kf.ord_offsets[hi])
        mask[kf.ord_docs[a:b]] = True
        postings += b - a
    return mask, postings


def exists_mask(present: jnp.ndarray) -> jnp.ndarray:
    return present


def docs_mask_from_postings(
    postings_docs: jnp.ndarray,
    offset: jnp.ndarray, length: jnp.ndarray,   # int32 scalars
    n_pad: int,
    window: int,
) -> jnp.ndarray:
    """Mask of docs containing one text term (term filter on a text field)."""
    win = jnp.arange(window, dtype=jnp.int32)
    valid = win < length
    idx = jnp.where(valid, offset + win, 0)
    docs = jnp.where(valid, postings_docs[idx], 0)
    mask = jnp.zeros(n_pad, jnp.int32).at[docs].max(valid.astype(jnp.int32))
    return mask.astype(bool)

"""Exactness of the blockwise top-k (block-max pruning) vs lexsort.

The monolithic lax.top_k over [B, 1M] was the perf hot spot;
blockwise_topk must be bit-exact under the (score desc, doc id asc) order.
"""

import numpy as np
import pytest

from opensearch_tpu.ops.topk import blockwise_topk, segment_top_k


def _ref(scores, k):
    n = scores.shape[-1]
    return np.stack([
        np.lexsort((np.arange(n), -row))[:k] for row in scores
    ])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape,bs", [
    ((4, 10_000), 512), ((7, 8_192), 1024), ((3, 100_000), 4096),
])
def test_exact_vs_lexsort(seed, shape, bs):
    rng = np.random.default_rng(seed)
    s = rng.standard_normal(shape).astype(np.float32)
    k = 10
    vals, ids = blockwise_topk(np.asarray(s), k, block_size=bs)
    ids = np.asarray(ids)
    expect = _ref(s, k)
    np.testing.assert_array_equal(ids, expect)
    np.testing.assert_allclose(
        np.asarray(vals), np.take_along_axis(s, expect, 1), rtol=0
    )


def test_tie_break_doc_id_ascending():
    # many identical scores across different blocks: ids must come back in
    # ascending order (the OpenSearch tie-break contract). n chosen large
    # enough to take the blockwise path, not the lax.top_k fallback.
    n = 65_536
    s = np.zeros((2, n), np.float32)
    s[0, [7, 20_000, 35_000]] = 5.0    # ties at 5.0
    s[1, :] = 1.0                      # all tied
    vals, ids = blockwise_topk(s, 5, block_size=256)
    ids = np.asarray(ids)
    assert ids[0, :3].tolist() == [7, 20_000, 35_000]
    assert ids[1].tolist() == [0, 1, 2, 3, 4]


def test_tie_break_across_blocks_with_unordered_block_maxima():
    # adversarial case from review: the tied docs live in blocks whose
    # block-MAX rank order differs from block-id order; the candidate
    # layout must still resolve the tie by lower doc id
    n = 65_536
    s = np.zeros((1, n), np.float32)
    s[0, 300] = 5.0          # early block, low max
    s[0, 40_000] = 9.0       # late block, high max
    s[0, 40_100] = 5.0       # tie with doc 300, same late block
    vals, ids = blockwise_topk(s, 2, block_size=256)
    assert np.asarray(ids)[0].tolist() == [40_000, 300]


def test_k_larger_than_blocks():
    rng = np.random.default_rng(3)
    s = rng.standard_normal((2, 1000)).astype(np.float32)
    vals, ids = blockwise_topk(s, 12, block_size=512)  # nb=2 <= k
    np.testing.assert_array_equal(np.asarray(ids), _ref(s, 12))


def test_padding_path():
    rng = np.random.default_rng(4)
    s = rng.standard_normal((2, 5000)).astype(np.float32)  # 5000 % 512 != 0
    vals, ids = blockwise_topk(s, 10, block_size=512)
    np.testing.assert_array_equal(np.asarray(ids), _ref(s, 10))


def test_neg_inf_masked_rows():
    s = np.full((1, 2048), -np.inf, np.float32)
    s[0, 100] = 1.0
    vals, ids = blockwise_topk(s, 10, block_size=256)
    assert np.asarray(ids)[0, 0] == 100
    assert np.asarray(vals)[0, 0] == 1.0


def test_segment_top_k_blockwise_route():
    rng = np.random.default_rng(5)
    s = rng.standard_normal(40_000).astype(np.float32)  # 1-D, above threshold
    vals, ids = segment_top_k(np.asarray(s), 10)
    expect = np.lexsort((np.arange(40_000), -s))[:10]
    np.testing.assert_array_equal(np.asarray(ids), expect)

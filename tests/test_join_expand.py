"""``kernels.expand_join_pairs`` against a numpy ``np.repeat`` of the
match ranges, on both of its methods: ``starts`` (a scatter of the
matched probe rows at their run starts and a running max) where the pairs
are not few against the probe rows, ``search`` (a binary search a pair)
where they are. The
method is forced by the shape alone and read back from its ``expand``
build event. On every live pair the probe and build indices are bit-equal
to the reference; every padding pair is masked off and indexes in range."""

import numpy as np
import pytest

from spark_tpu import metrics
from spark_tpu.physical import kernels as K

# (probe rows, pair capacity): ``search`` takes a probe of at least
# K.EXPAND_SEARCH_RATIO (32) times the pairs
SHAPES = {
    "starts/probe_shorter": (37, 480),
    "starts/probe_longer": (300, 96),
    "search/probe_longer": (4800, 96),
}
BUILD = 1000


def _matched(pattern, n, rng):
    """Which of the n probe rows have a match."""
    rows = np.arange(n)
    return {
        "zeros_at_head": rows >= n // 3,
        "zeros_at_tail": rows < n - n // 3,
        "zero_runs": (rows // 5) % 2 == 1,
        "scattered": rng.random(n) < 0.3,
        "one_row": rows == n // 2,
        "all_zero": np.zeros(n, bool),
    }[pattern]


def _counts(pattern, n, pairs, rng):
    """Counts over the matched rows that sum to ``pairs``, each at least 1."""
    rows = np.flatnonzero(_matched(pattern, n, rng))
    counts = np.zeros(n, np.int64)
    if pairs:
        rows = rows[:pairs]
        counts[rows] = 1 + rng.multinomial(
            pairs - len(rows), np.full(len(rows), 1 / len(rows)))
    return counts


CASES = [(shape, pattern, fill)
         for shape in SHAPES
         for pattern in ("zeros_at_head", "zeros_at_tail", "zero_runs",
                         "scattered", "one_row", "all_zero")
         for fill in ("full", "slack")
         if (pattern, fill) != ("all_zero", "full")]


@pytest.mark.parametrize("shape,pattern,fill", CASES)
def test_expansion_equals_a_repeat_of_the_ranges(shape, pattern, fill):
    n, total = SHAPES[shape]
    rng = np.random.default_rng(len(CASES) + CASES.index(
        (shape, pattern, fill)))
    live = {"full": total, "slack": total - total // 4}[fill]
    counts = _counts(pattern, n, 0 if pattern == "all_zero" else live, rng)
    live = int(counts.sum())
    lo = np.where(counts > 0, rng.integers(0, BUILD - counts.max(initial=0),
                                           n), 0)
    perm = rng.permutation(BUILD).astype(np.int32)

    before = metrics.recent(1)
    seen = before[-1]["n"] if before else -1
    p, b, mask = (np.asarray(x) for x in K.expand_join_pairs(
        K.JoinRanges(perm, lo, lo + counts), total))
    (event,) = [e for e in metrics.recent(16)
                if e["n"] > seen and e["kind"] == "expand"]
    assert (event["method"], event["probe_rows"], event["pairs"]) == (
        shape.split("/")[0], n, total)

    offsets = np.cumsum(counts) - counts
    want_p = np.repeat(np.arange(n), counts)
    want_b = perm[np.repeat(lo - offsets, counts) + np.arange(live)]
    assert p.shape == b.shape == mask.shape == (total,)
    np.testing.assert_array_equal(p[:live], want_p)
    np.testing.assert_array_equal(b[:live], want_b)
    assert mask[:live].all() and not mask[live:].any()
    assert ((p >= 0) & (p < n)).all()
    assert ((b >= 0) & (b < BUILD)).all()

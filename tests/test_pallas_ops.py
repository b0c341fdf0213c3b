"""Pallas kernel parity (spark_tpu/ops/pallas_agg.py) — interpret mode
on CPU against a numpy oracle; the same kernel runs compiled on TPU."""

import numpy as np
import pytest

from spark_tpu.ops import pallas_available, pallas_seg_sum


@pytest.mark.parametrize("n,k", [(100, 4), (8192, 16), (20000, 128),
                                 (5, 2)])
def test_seg_sum_matches_numpy(rng, n, k):
    data = rng.normal(size=n).astype(np.float32)
    seg = rng.integers(0, k, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    got = np.asarray(pallas_seg_sum(data, seg, mask, k, interpret=True))
    want = np.zeros(k, np.float32)
    np.add.at(want, seg[mask], data[mask])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_seg_sum_all_masked(rng):
    data = rng.normal(size=300).astype(np.float32)
    seg = np.zeros(300, np.int32)
    got = np.asarray(pallas_seg_sum(
        data, seg, np.zeros(300, bool), 3, interpret=True))
    assert (got == 0).all()


def test_seg_sum_counts(rng):
    """count = sum of the mask itself (how the engine derives counts)."""
    n, k = 4096, 7
    seg = rng.integers(0, k, n).astype(np.int32)
    mask = rng.random(n) < 0.5
    got = np.asarray(pallas_seg_sum(
        mask.astype(np.float32), seg, mask, k, interpret=True))
    want = np.bincount(seg[mask], minlength=k).astype(np.float32)
    np.testing.assert_array_equal(got, want)


def test_availability_gate():
    assert not pallas_available(np.float64, 16, platform="tpu")
    assert not pallas_available(np.float32, 1, platform="tpu")
    assert not pallas_available(np.float32, 100000, platform="tpu")
    assert pallas_available(np.float32, 16, platform="tpu")
    assert not pallas_available(np.float32, 16, platform="cpu")


def _pallas_events():
    from spark_tpu import metrics

    return [e for e in metrics.recent(4096) if e["kind"] == "pallas"]


def test_engine_seg_kernels_take_pallas_path(rng, monkeypatch):
    """seg_count/seg_min/seg_max route 64 < K <= 1024 unsorted
    aggregations through the Pallas kernel (SPARK_TPU_PALLAS=interpret:
    the explicit test switch) and agree with the scatter path; float
    seg_sum keeps the row-ordered scatter-add either way."""
    import jax.numpy as jnp

    from spark_tpu.physical.kernels import (seg_count, seg_max, seg_min,
                                            seg_sum)

    n, k = 6000, 100
    data = jnp.asarray(rng.normal(size=n).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, k, n))
    mask = jnp.asarray(rng.random(n) < 0.7)

    base_sum = np.asarray(seg_sum(data, seg, mask, k))
    base_cnt = np.asarray(seg_count(seg, mask, k))
    base_min = np.asarray(seg_min(data, seg, mask, k))
    base_max = np.asarray(seg_max(data, seg, mask, k))
    assert not _pallas_events(), "Pallas ran without being asked to"
    monkeypatch.setenv("SPARK_TPU_PALLAS", "interpret")
    got_sum = np.asarray(seg_sum(data, seg, mask, k))
    got_cnt = np.asarray(seg_count(seg, mask, k))
    np.testing.assert_array_equal(seg_min(data, seg, mask, k), base_min)
    np.testing.assert_array_equal(seg_max(data, seg, mask, k), base_max)
    ran = _pallas_events()
    assert sorted(e["op"] for e in ran) == ["count", "max", "min"]
    assert all(e["interpret"] for e in ran)
    np.testing.assert_allclose(got_sum, base_sum, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got_cnt, base_cnt)
    assert got_cnt.dtype == np.int64

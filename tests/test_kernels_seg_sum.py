"""kernels.seg_sum: the rung a sum takes and the bits it returns.

Exact sums (int64 / scaled decimals) follow the integer ladder as int64
at every K — with 1 < K <= 64 the dense masked reduction, never a
scatter-add (PR 27: q1's 8.6 s on the v5e were fifteen emulated-f64
scatter-adds), past that one cumsum over sorted ids or one int64
scatter-add (PR 36: three f64-limb scatter-adds were 646 of Q15's
1,118 ms at SF10); only user floats need row order. The masked rung
(sum, count, min, max) reads its column once for every G slots, not once
a slot (PR 29: 36 passes were 24 of q1's 40 ms at SF10). The StableHLO
tripwires and the trace-time ``seg_sum`` event stop either regressing
unseen.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_tpu import metrics
from spark_tpu.physical import kernels as K

N = 3000
KS = [2, 6, 64, 65, 200]


def _columns(rng):
    """Values near +-2^62 whose totals wrap, an all-negative column,
    one of money-sized values."""
    wild = rng.integers(-(1 << 62), 1 << 62, N, dtype=np.int64)
    wild[:8] = [(1 << 62) - 1, -(1 << 62), (1 << 42) - 1, 1 << 42,
                -1, (1 << 21) - 1, -(1 << 21), 0]
    return {"wraps": wild,
            "negative": -rng.integers(1, 1 << 61, N, dtype=np.int64),
            "money": rng.integers(0, 10 ** 11, N, dtype=np.int64)}


def _seg(rng, k, sorted_seg):
    # slot k - 1 stays empty: an empty segment sums to zero on every rung
    seg = rng.integers(0, max(k - 1, 1), N, dtype=np.int64)
    return np.sort(seg) if sorted_seg else seg


def _mask(rng, kind):
    return {"all": np.ones(N, bool), "none": np.zeros(N, bool),
            "p70": rng.random(N) < 0.7}[kind]


OPS = {  # op -> (kernel, numpy's ufunc, what an empty slot reads)
    "sum": (K.seg_sum, np.add, lambda dt: 0),
    "count": (lambda d, s, m, k, sorted_seg: K.seg_count(s, m, k, sorted_seg),
              np.add, lambda dt: 0),
    "min": (K.seg_min, np.minimum, lambda dt: K._pos_sentinel(dt)),
    "max": (K.seg_max, np.maximum, lambda dt: K._neg_sentinel(dt)),
}


def _jitted(k, sorted_seg, op="sum"):
    return jax.jit(lambda d, s, m: OPS[op][0](d, s, m, k, sorted_seg))


def _op_reference(op, data, seg, mask, k):
    _, ufunc, empty = OPS[op]
    if op == "count":
        data = np.ones(len(seg), np.int64)
    ref = np.full(k, empty(data.dtype), data.dtype)
    ufunc.at(ref, seg[mask], data[mask])  # add wraps like the device's
    return ref


@pytest.mark.parametrize("mask_kind", ["all", "none", "p70"])
@pytest.mark.parametrize("sorted_seg", [False, True])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("op", list(OPS))
def test_int64_reduction_is_bit_equal_to_numpy(rng, op, k, sorted_seg,
                                               mask_kind):
    seg, mask = _seg(rng, k, sorted_seg), _mask(rng, mask_kind)
    fn = _jitted(k, sorted_seg, op)
    for name, data in _columns(rng).items():
        got = np.asarray(fn(jnp.asarray(data), jnp.asarray(seg),
                            jnp.asarray(mask)))
        assert got.dtype == np.int64
        # an empty slot's MIN / MAX is NULL by its count: on the sorted
        # rungs (K > 64) its payload is whatever row the clip lands on
        live = (np.bincount(seg[mask], minlength=k) > 0
                if op in ("min", "max") else slice(None))
        ref = _op_reference(op, data, seg, mask, k)
        assert np.array_equal(got[live], ref[live]), name


@pytest.mark.parametrize("mask_kind", ["all", "none", "p70"])
@pytest.mark.parametrize("k", [2, 6, 64])
@pytest.mark.parametrize("op", ["min", "max"])
def test_f32_min_max_are_bit_equal_to_numpy(rng, op, k, mask_kind):
    """Every dtype takes the masked rung for MIN / MAX: user floats too
    (an order of combination changes no minimum)."""
    seg, mask = _seg(rng, k, False), _mask(rng, mask_kind)
    data = rng.standard_normal(N).astype(np.float32)
    got = np.asarray(_jitted(k, False, op)(
        jnp.asarray(data), jnp.asarray(seg), jnp.asarray(mask)))
    assert got.dtype == np.float32
    assert got.tobytes() == _op_reference(op, data, seg, mask, k).tobytes()


@pytest.mark.parametrize("sorted_seg", [False, True])
@pytest.mark.parametrize("k", [6, 64, 200])
def test_static_and_compacted_layouts_give_the_same_bits(rng, k, sorted_seg):
    """PR 16's invariant, for decimals on the rung they take now: the
    same live rows at another capacity, dead slots holding garbage."""
    data, seg = _columns(rng)["wraps"], _seg(rng, k, sorted_seg)
    mask = _mask(rng, "p70")
    live = int(mask.sum())
    cap = 4096
    c_data = rng.integers(-(1 << 62), 1 << 62, cap, dtype=np.int64)
    c_data[:live] = data[mask]
    c_seg = np.full(cap, k - 1, np.int64)  # dead rows: the last segment
    c_seg[:live] = seg[mask]
    c_mask = np.arange(cap) < live
    static = _jitted(k, sorted_seg)(jnp.asarray(data), jnp.asarray(seg),
                                    jnp.asarray(mask))
    compact = _jitted(k, sorted_seg)(jnp.asarray(c_data), jnp.asarray(c_seg),
                                     jnp.asarray(c_mask))
    assert np.asarray(static).tobytes() == np.asarray(compact).tobytes()


CASES = [  # (dtype, K, sorted ids, the rung it must be built from)
    pytest.param(jnp.int64, 1, False, "reduce", id="int64-k1"),
    pytest.param(jnp.int64, 6, False, "masked", id="int64-k6"),
    pytest.param(jnp.int64, 64, False, "masked", id="int64-k64"),
    pytest.param(jnp.int64, 64, True, "masked", id="int64-k64-sorted"),
    pytest.param(jnp.int64, 65, False, "scatter", id="int64-k65"),
    pytest.param(jnp.int64, 65, True, "cumsum", id="int64-k65-sorted"),
    pytest.param(jnp.int64, 100_096, False, "scatter", id="int64-k100096"),
    pytest.param(jnp.int64, 100_096, True, "cumsum",
                 id="int64-k100096-sorted"),
    pytest.param(jnp.float64, 6, False, "scatter", id="float64-k6"),
    pytest.param(jnp.float64, 65, True, "scatter", id="float64-k65-sorted"),
]


def _shapes(dtype):
    return (jax.ShapeDtypeStruct((N,), dtype),
            jax.ShapeDtypeStruct((N,), jnp.int64),
            jax.ShapeDtypeStruct((N,), jnp.bool_))


def _scatter_adds(text):
    """The scatters of a lowered program that ADD into their slots. (One
    that sets — the permutation inverse inside a co-sorted
    ``searchsorted``, which ``seg_bounds`` takes past 4,096 segments —
    returns its update and is none of a sum's.)"""
    return len(re.findall(
        r'"stablehlo\.scatter"\([^\n]*\n[^\n]*\n\s*%\d+ = stablehlo\.add ',
        text))


@pytest.mark.parametrize("dtype,k,sorted_seg,rung", CASES)
def test_stablehlo_scatter_tripwire(dtype, k, sorted_seg, rung):
    """No scatter-add in an exact sum with K <= 64 or over sorted ids;
    K > 64 on unsorted ids has exactly one (the column's own, not one a
    limb), as a user float's sum (which keeps row order) at any K. An
    int64 sum never travels as f64, which the chip emulates as a pair
    of f32."""
    text = _jitted(k, sorted_seg).lower(*_shapes(dtype)).as_text()
    assert _scatter_adds(text) == (rung == "scatter")
    if k <= 4096:
        assert ("scatter" in text) == (rung == "scatter")
    if dtype == jnp.int64:
        assert "f64" not in text


def _pass_cases():
    """(op, dtype, K): K around the slots a pass fills for the dtype."""
    for op in OPS:
        for dtype in ((jnp.int64, jnp.float32) if op in ("min", "max")
                      else (jnp.int64,)):
            g = K._slots_a_pass(dtype)
            for k in sorted({2, 6, g, g + 1, 64}):
                yield pytest.param(op, dtype, k,
                                   id=f"{op}-{np.dtype(dtype).name}-k{k}")


@pytest.mark.parametrize("op,dtype,k", _pass_cases())
def test_stablehlo_pass_tripwire(op, dtype, k):
    """The masked rung lowers to one (variadic) reduction for every G
    slots: ceil(K / G) reads of the column, where one reduction a slot
    read it K times (the chip's compiler does not merge them: PR 29)."""
    text = _jitted(k, False, op).lower(*_shapes(dtype)).as_text()
    assert text.count("stablehlo.reduce") == K._masked_passes(k, dtype)
    assert "scatter" not in text


@pytest.mark.parametrize("op", list(OPS))
def test_global_aggregate_is_one_plain_reduction(op):
    text = _jitted(1, False, op).lower(*_shapes(jnp.int32)).as_text()
    assert text.count("stablehlo.reduce") == 1
    assert "scatter" not in text


def _events():
    return [e for e in metrics.recent(4096) if e["kind"] == "seg_sum"]


@pytest.mark.parametrize("dtype,k,sorted_seg,rung", CASES)
def test_trace_time_event_names_the_rung(rng, dtype, k, sorted_seg, rung):
    metrics.reset()
    fn = _jitted(k, sorted_seg)
    args = (jnp.asarray(_columns(rng)["money"]).astype(dtype),
            jnp.asarray(_seg(rng, k, sorted_seg)),
            jnp.asarray(_mask(rng, "p70")))
    fn(*args)
    (ev,) = _events()
    assert (ev["rung"], ev["k"], ev["rows"]) == (rung, k, N)
    assert ev["dtype"] == np.dtype(dtype).name
    assert "limbs" not in ev
    assert ev["passes"] == (K._masked_passes(k, dtype)
                            if rung == "masked" else None)
    fn(*args)  # the compiled program runs: nothing is recorded
    assert len(_events()) == 1


def test_global_sum_keeps_the_plain_reduction(rng):
    metrics.reset()
    data, mask = _columns(rng)["wraps"], _mask(rng, "p70")
    seg = np.zeros(N, np.int64)
    got = _jitted(1, False)(jnp.asarray(data), jnp.asarray(seg),
                            jnp.asarray(mask))
    assert np.array_equal(np.asarray(got),
                          _op_reference("sum", data, seg, mask, 1))
    (ev,) = _events()
    assert ev["rung"] == "reduce" and "limbs" not in ev


def _sorted_case(rng, k, column):
    """(data, sorted ids, mask) over max(N, 2 K) rows. ``wraps``: values
    under 2^56, so a group's few dozen rows sum inside int64 while the
    column's running total leaves it; ``negative``: the same, negated."""
    n = max(N, 2 * k)
    seg = np.sort(rng.integers(0, k - 1, n, dtype=np.int64))
    data = rng.integers(1 << 54, 1 << 56, n, dtype=np.int64)
    return (-data if column == "negative" else data), seg, rng.random(n) < 0.7


@pytest.mark.parametrize("column", ["wraps", "negative"])
@pytest.mark.parametrize("k", [65, 200, 100_096])
def test_sorted_int64_sum_is_one_cumsum_and_exact_where_the_total_wraps(
        rng, k, column):
    """Past the masked rung a decimal sum over sorted ids (the sort-based
    aggregate's) is the cumsum rung on the int64 column itself:
    ``csum[end] - csum[start] + x[start]`` wraps back to the group's own
    sum wherever the running total of the whole column has wrapped."""
    data, seg, mask = _sorted_case(rng, k, column)
    exact = [0] * k          # Python integers: no wrap
    for s, x in zip(seg[mask].tolist(), data[mask].tolist()):
        exact[s] += x
    assert max(abs(v) for v in exact) < 1 << 63 <= abs(sum(exact))
    metrics.reset()
    got = np.asarray(_jitted(k, True)(jnp.asarray(data), jnp.asarray(seg),
                                      jnp.asarray(mask)))
    (ev,) = _events()
    assert (ev["rung"], ev["k"], ev["dtype"]) == ("cumsum", k, "int64")
    assert got.dtype == np.int64
    assert np.array_equal(got, _op_reference("sum", data, seg, mask, k))
    assert got.tolist() == exact


def test_q1_sums_are_masked_and_q6_is_a_reduction(engine):
    """Both engines build TPC-H Q1's decimal sums (K = 6 slots) from the
    masked rung and Q6's global sum from the plain reduction."""
    from spark_tpu.tpch.gen import generate_tables, register_views
    from spark_tpu.tpch.queries import QUERIES

    # an SF no other test uses, so that the stages are traced here
    register_views(engine, generate_tables(0.0031, seed=27))
    for query, rung in ((1, "masked"), (6, "reduce")):
        metrics.reset()
        assert engine.sql(QUERIES[query]).collect()
        events = _events()
        assert events, f"q{query} traced no seg_sum"
        assert {(e["rung"], e["dtype"]) for e in events} == {
            (rung, "int64")}, events
        assert not any("limbs" in e for e in events)
        assert {e["k"] for e in events} == {6 if query == 1 else 1}

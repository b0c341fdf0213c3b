"""reduce_trace.py on a synthetic trace whose every number was worked out
by hand (data/synthetic_xspace.txt, an XSpace text proto that
``ProfileData`` reads): two device planes, a line that must not count, an
event clipped by the window, operations nested in a while loop, two that
overlap, and bench.* annotations on the host.

    window  bench.slice = [1000, 11000) ns
    dev 0   busy [1000,1500) + [3000,5000) + [8000,9500) = 4000 ns
    dev 1   busy [2000,3000) + [8000,10000)              = 3000 ns
"""

import os

import pytest

import reduce_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "synthetic_xspace.txt")


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    with open(DATA) as f:
        return reduce_trace.planes_of(ProfileData.from_text_proto(f.read()))


@pytest.fixture(scope="module")
def reduced(planes):
    return reduce_trace.reduce(planes, chips=2)


def test_window_and_busy(reduced):
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(10000e-9)
    assert reduced["busy_s_per_device"] == pytest.approx([4000e-9, 3000e-9])
    assert reduced["busy_s"] == pytest.approx(3500e-9)
    assert reduced["executions"] == 2


def test_collective_time_is_a_union_per_device(reduced):
    # dev 0: all-reduce [8000,9000); dev 1: [8000,10000); mean of the two
    assert reduced["collective_s"] == pytest.approx(1500e-9)


def test_top_operations_add_up_to_busy(reduced):
    ops = dict(reduced["device_ops"])
    # self times, summed over the devices and divided by their number:
    # the while loses its two nested scatters (2000 - 500 - 1000), the
    # all-reduce on dev 0 loses the 500 ns that fusion.1 overlaps
    assert ops == pytest.approx({
        "fusion.1": (500 + 1000 + 1000) / 2 * 1e-9,
        "all-reduce.4": (500 + 2000) / 2 * 1e-9,
        "scatter.3": 1500 / 2 * 1e-9,
        "while.2": 500 / 2 * 1e-9})
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])
    assert [n for n, _ in reduced["device_ops"]][-1] == "while.2"


def test_idle_gaps_by_annotation(reduced):
    gaps = dict(reduced["idle_gaps"])
    # dev 0 idles in [1500,3000), [5000,8000) and [9500,11000)
    assert gaps == pytest.approx({
        "bench.collect": 3500e-9, "bench.sql": 1500e-9,
        "bench.slice": 1000e-9,
        "longest_single_gap.bench.collect": 3000e-9})
    idle = sum(v for k, v in gaps.items() if not k.startswith("longest"))
    assert idle == pytest.approx(
        reduced["window_s"] - reduced["busy_s_per_device"][0])


def test_interval_helpers():
    u = reduce_trace.union([(5, 7), (1, 3), (2, 4), (7, 7), (6, 9)])
    assert u == [(1, 4), (5, 9)]
    assert reduce_trace.total(u) == 7
    assert reduce_trace.gaps(u, 0, 10) == [(0, 1), (4, 5), (9, 10)]
    assert reduce_trace.self_times(
        [("a", 0, 10), ("b", 2, 3), ("b", 6, 2), ("c", 12, 1)]) == {
            "a": 5, "b": 5, "c": 1}


def test_wrong_traces_are_refused(planes):
    with pytest.raises(ValueError, match="device planes"):
        reduce_trace.reduce(planes, chips=4)
    no_slice = [(p, [(ln, [e for e in ev if e[0] != "bench.slice"])
                     for ln, ev in lines]) for p, lines in planes]
    with pytest.raises(ValueError, match="bench.slice"):
        reduce_trace.reduce(no_slice)
    no_device = [(p, lines) for p, lines in planes if "TPU" not in p]
    with pytest.raises(ValueError, match="no device plane"):
        reduce_trace.reduce(no_device)


def test_listing_names_planes_and_lines(planes):
    text = reduce_trace.listing(planes)
    assert "PLANE '/device:TPU:0'" in text and "LINE 'XLA Ops'" in text
    assert "scatter.3" in text

"""From a jax.profiler trace (xplane) to numbers: device busy time as the
union of operation intervals, idle gaps by what the host was doing, the
operations that took most time, and collective time.

Only ``jax.profiler.ProfileData`` reads the file. The arithmetic works on
plain tuples, so benchmark/tests/test_reduce_trace.py fixes it on a small
synthetic trace. The plane and line names below are the TPU profiler's
documented ones; PR 25 could get no chip, so they have not yet been held
against a listing of a real trace from the v5e (PERF.md, Findings):

    python benchmark/reduce_trace.py <file.xplane.pb>     prints that listing
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]                 # name, start ns, duration ns
Line = Tuple[str, List[Event]]
Plane = Tuple[str, List[Line]]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")   # one plane per chip
OPS_LINE = "XLA Ops"                              # the line of HLO operations
HOST_PLANE = "/host:CPU"
SLICE = "bench.slice"                             # annotation round the slice
ANNOTATION_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-reduce|all-to-all|all-gather|collective-permute|reduce-scatter"
    r"|collective-broadcast")


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, host_ops: bool = False) -> List[Plane]:
    """The planes of an xplane file. ``host_ops``: the CPU rehearsal's
    pretended device plane (``host_ops_as_device``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return host_ops_as_device(data) if host_ops else planes_of(data)


def planes_of(data) -> List[Plane]:
    return [(plane.name,
             [(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events])
              for line in plane.lines])
            for plane in data.planes]


def host_ops_as_device(data) -> List[Plane]:
    """For the CPU rehearsal only (benchmark/tests): XLA:CPU writes its
    operations into the host plane, on the lines of its client's threads,
    marked by an ``hlo_op`` stat. They are lifted into one pretended
    device plane so that the whole path from trace to metric runs in a
    test. The command itself never gets here: it refuses a CPU."""
    ops: List[Event] = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if any(k == "hlo_op" for k, _ in e.stats):
                    ops.append((e.name, float(e.start_ns),
                                float(e.duration_ns)))
    return planes_of(data) + [("/device:TPU:0", [(OPS_LINE, ops)])]


# ---- interval arithmetic ----------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: List[Event]) -> Dict[str, float]:
    """ns per operation name, each event counted without the time of the
    events nested inside it (a while loop holds its body's operations on
    the same line), so that the names add up to the busy time."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []                # [name, end, self ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] += done[2]
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, dur])
    for done in stack:
        out[done[0]] += done[2]
    return dict(out)


# ---- the reduction ----------------------------------------------------------


def _annotations(planes: List[Plane]) -> List[Event]:
    out = []
    for pname, lines in planes:
        if pname != HOST_PLANE:
            continue
        for _lname, events in lines:
            out += [e for e in events if e[0].startswith(ANNOTATION_PREFIX)]
    return out


def reduce(planes: List[Plane], chips: Optional[int] = None) -> Dict:
    """The numbers of one traced slice.

    The window is the ``bench.slice`` annotation. ``busy_s`` is the union
    of the operation intervals on a device's operations line, clipped to
    the window, as a mean over the device planes (``chips`` of them are
    expected when given). ``idle_gaps`` attributes every idle nanosecond
    of the first device to the innermost ``bench.*`` annotation that
    covers it (``bench.slice`` itself: between two executions).
    """
    notes = _annotations(planes)
    slices = [e for e in notes if e[0] == SLICE]
    if len(slices) != 1:
        raise ValueError(f"{len(slices)} '{SLICE}' annotations in the trace, "
                         "not 1")
    lo, hi = slices[0][1], slices[0][1] + slices[0][2]
    inner = [(n, s, s + d) for n, s, d in notes if n != SLICE]

    devices = []
    for pname, lines in planes:
        if not DEVICE_PLANE.match(pname):
            continue
        ops = [ev for lname, events in lines if lname == OPS_LINE
               for ev in events]
        devices.append((pname, ops))
    if not devices:
        raise ValueError(
            "no device plane with a line '%s' in the trace; planes: %s"
            % (OPS_LINE, [(p, [name for name, _ in lines])
                          for p, lines in planes]))
    if chips is not None and len(devices) != chips:
        raise ValueError(f"{len(devices)} device planes in the trace, the "
                         f"cell has {chips} chips")
    devices.sort()

    busy_ns, collective_ns = [], []
    op_ns: Dict[str, float] = collections.defaultdict(float)
    first_busy: List[Tuple[float, float]] = []
    for i, (_pname, ops) in enumerate(devices):
        inside = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                  for n, s, d in ops if min(s + d, hi) > max(s, lo)]
        busy = union((s, s + d) for _n, s, d in inside)
        if i == 0:
            first_busy = busy
        busy_ns.append(total(busy))
        collective_ns.append(total(union(
            (s, s + d) for n, s, d in inside if COLLECTIVE.search(n))))
        for name, ns in self_times(inside).items():
            op_ns[name] += ns
    n = len(devices)

    idle: Dict[str, float] = collections.defaultdict(float)
    longest = (0.0, SLICE)
    for a, b in gaps(first_busy, lo, hi):
        parts: Dict[str, float] = collections.defaultdict(float)
        for name, s, e in inner:
            if min(b, e) > max(a, s):
                parts[name] += min(b, e) - max(a, s)
        between = (b - a) - sum(parts.values())
        if between > 0:
            parts[SLICE] += between
        for name, ns in parts.items():
            idle[name] += ns
        if b - a > longest[0]:
            longest = (b - a, max(parts, key=parts.get))
    idle_gaps = sorted(([k, v / 1e9] for k, v in idle.items()),
                       key=lambda kv: -kv[1])[:9]
    if longest[0] > 0:
        idle_gaps.append([f"longest_single_gap.{longest[1]}",
                          longest[0] / 1e9])

    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy_ns],
        "collective_s": sum(collective_ns) / n / 1e9,
        "executions": sum(1 for name, _s, _e in inner
                          if name == "bench.collect"),
        "device_ops": sorted(([k, v / n / 1e9] for k, v in op_ns.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": idle_gaps,
    }


def listing(planes: List[Plane], top: int = 12) -> str:
    """Planes, lines and the most frequent event names: what to look at
    before trusting DEVICE_PLANE and OPS_LINE on a new chip."""
    out = []
    for pname, lines in planes:
        out.append(f"PLANE {pname!r}")
        for lname, events in lines:
            span = ""
            if events:
                t0 = min(s for _n, s, _d in events)
                t1 = max(s + d for _n, s, d in events)
                span = f" [{t0:.0f} .. {t1:.0f}] ns"
            out.append(f"  LINE {lname!r}: {len(events)} events{span}")
            count = collections.Counter(n for n, _s, _d in events)
            dur = collections.defaultdict(float)
            for name, _s, d in events:
                dur[name] += d
            for name, k in count.most_common(top):
                out.append(f"    {k:7d} x {dur[name] / 1e6:12.3f} ms  "
                           f"{name[:100]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(listing(load(sys.argv[1])))

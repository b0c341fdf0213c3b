"""The program's span stream, read two ways.

From the metrics ring (``ctx["slice_events"]``, the ``span`` events of
spark_tpu/trace/): self times per span name and execution, for the
per-layer metrics ``parse_ms`` ... ``glue_ms`` (benchmark/layer_metrics/).
A span's self time is its duration less its children's, so the names of
one execution partition its root.

From the profiler's trace (every sampled span is also a ``spark.<name>``
annotation on ``/host:CPU``, on the device's clock): ``launch_ms``, the
time from the start of a stage's dispatch to the first operation on the
device, and the device's idle time by the innermost annotation that
covers it:

    python benchmark/span_times.py <file.xplane.pb>

prints each annotation's self time per execution, the first device's idle
time by innermost ``spark.*`` / ``bench.*`` annotation, the longest single
gap with the spans it fell in, and ``launch_ms``. The arithmetic works on
plain tuples and dicts (benchmark/tests/test_span_times.py).
"""

from __future__ import annotations

import bisect
import collections
import os
import statistics
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import reduce_trace
from reduce_trace import Event, Plane

ROOT = "query.execute"
#: per-layer metric -> the span names whose self times it sums
METRIC_SPANS: Dict[str, Tuple[str, ...]] = {
    "parse_ms": ("query.parse",),
    "optimize_ms": ("query.optimize",),
    "plan_ms": ("query.plan",),
    "dispatch_ms": ("stage.run", "stage.fused", "stage.dispatch",
                    "compile.probe"),
    "device_wait_ms": ("device.wait", "stage.device"),
    "fetch_ms": ("fetch.copy",),
    "rows_ms": ("query.rows", "query.fetch"),
    "analysis_ms": ("query.analysis",),     # the accepted reader's own
}
NAMED = frozenset(n for names in METRIC_SPANS.values() for n in names)
SPAN_PREFIX = "spark."
COLLECT = reduce_trace.ANNOTATION_PREFIX + "collect"
DISPATCH = SPAN_PREFIX + "stage.dispatch"


# ---- the ring: span events --------------------------------------------------


def by_trace(events: Iterable[Dict]) -> List[List[Dict]]:
    """The ``span`` events grouped by ``trace_id``, in order of arrival."""
    groups: Dict[str, List[Dict]] = {}
    for e in events:
        if e.get("kind") == "span" and e.get("trace_id") is not None:
            groups.setdefault(e["trace_id"], []).append(e)
    return list(groups.values())


def self_ms(spans: Sequence[Dict]) -> Dict[str, float]:
    """span_id -> self time of the spans of ONE trace: ``ms`` less the
    ``ms`` of the direct children. A span whose parent is not in the list
    (a remote peer's, or one the ring has dropped) takes from nobody; two
    children that ran side by side can take more than there is, so a self
    time stops at 0."""
    own = {e["span_id"]: float(e["ms"]) for e in spans}
    for e in spans:
        parent = e.get("parent_id")
        if parent in own:
            own[parent] -= float(e["ms"])
    return {k: max(0.0, v) for k, v in own.items()}


def per_execution(events: Iterable[Dict]) -> List[Dict[str, float]]:
    """One dict per trace: span name -> summed self ms. The key ``glue``
    holds the self time, under a ``query.execute``, of every span that no
    metric names (the root's own, ``storage.pin``, ``mview.probe``, ...);
    the key ``root`` the duration of ``query.execute``."""
    out = []
    for spans in by_trace(events):
        own = self_ms(spans)
        by_id = {e["span_id"]: e for e in spans}
        under_root: Dict[str, bool] = {}

        def in_query(e: Dict) -> bool:
            sid = e["span_id"]
            if sid not in under_root:
                parent = by_id.get(e.get("parent_id"))
                under_root[sid] = e["name"] == ROOT or (
                    parent is not None and in_query(parent))
            return under_root[sid]

        sums: Dict[str, float] = collections.defaultdict(float)
        for e in spans:
            sums[e["name"]] += own[e["span_id"]]
            if e["name"] == ROOT:
                sums["root"] += float(e["ms"])
            if e["name"] not in NAMED and in_query(e):
                sums["glue"] += own[e["span_id"]]
        out.append(dict(sums))
    return out


def median_ms(events: Iterable[Dict], names: Sequence[str]
              ) -> Optional[float]:
    """Median, over the traces that hold any of ``names``, of their summed
    self time in a trace; None where no trace holds one."""
    values = [sum(d.get(n, 0.0) for n in names)
              for d in per_execution(events) if any(n in d for n in names)]
    return statistics.median(values) if values else None


def metric(ctx: Dict, name: str) -> Optional[float]:
    """A per-layer metric of METRIC_SPANS from the traced slice."""
    return median_ms(ctx["slice_events"], METRIC_SPANS[name])


# ---- the profiler's trace: spark.* annotations and device operations --------


def load_planes(path: str) -> List[Plane]:
    """The planes of an xplane file; where it has no device plane (the
    CPU rehearsal) XLA:CPU's operations are lifted into a pretended one,
    as reduce_trace.host_ops_as_device does for the harness."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = reduce_trace.planes_of(data)
    if any(reduce_trace.DEVICE_PLANE.match(p) for p, _ in planes):
        return planes
    return reduce_trace.host_ops_as_device(data)


def slice_planes(ctx: Dict) -> Optional[List[Plane]]:
    """The planes of the slice the harness has just traced."""
    cell = ctx["cell"]
    trace_dir = os.path.join(cell.bench_dir, ".trace", cell.entry["name"])
    try:
        return load_planes(reduce_trace.newest_xplane(trace_dir))
    except FileNotFoundError:
        return None


def annotations(planes: List[Plane]) -> List[Event]:
    """Every ``bench.*`` and ``spark.*`` event of the host plane."""
    return [e for pname, lines in planes if pname == reduce_trace.HOST_PLANE
            for _lname, events in lines for e in events
            if e[0].startswith((reduce_trace.ANNOTATION_PREFIX,
                                SPAN_PREFIX))]


def first_device_ops(planes: List[Plane]) -> List[Event]:
    devices = sorted((pname, lines) for pname, lines in planes
                     if reduce_trace.DEVICE_PLANE.match(pname))
    if not devices:
        return []
    return [e for lname, events in devices[0][1]
            if lname == reduce_trace.OPS_LINE for e in events]


def launch_ms(planes: List[Plane]) -> Optional[float]:
    """Median, over the ``bench.collect`` annotations, of the time from the
    start of the first ``spark.stage.dispatch`` inside one to the start of
    the first operation on the first device after it."""
    notes = annotations(planes)
    ops = sorted(s for _n, s, _d in first_device_ops(planes))
    dispatches = sorted(s for n, s, _d in notes if n == DISPATCH)

    def first_from(starts: List[float], lo: float, hi: float):
        i = bisect.bisect_left(starts, lo)
        return starts[i] if i < len(starts) and starts[i] < hi else None

    values = []
    for name, lo, dur in notes:
        if name != COLLECT:
            continue
        dispatch = first_from(dispatches, lo, lo + dur)
        if dispatch is None:
            continue
        op = first_from(ops, dispatch, lo + dur)
        if op is not None:
            values.append((op - dispatch) / 1e6)
    return statistics.median(values) if values else None


def innermost(notes: List[Event], lo: float, hi: float
              ) -> List[Tuple[float, float, Tuple[str, ...]]]:
    """[lo, hi) cut into stretches, each with the chain of annotations that
    cover it, outermost first (empty where none does). The innermost is the
    one that started last."""
    marks = sorted({lo, hi} | {t for _n, s, d in notes for t in (s, s + d)
                               if lo < t < hi})
    spans = sorted(((s, s + d, n) for n, s, d in notes
                    if s + d > lo and s < hi), key=lambda x: (x[0], -x[1]))
    out, live, at = [], [], 0
    for a, b in zip(marks, marks[1:]):
        while at < len(spans) and spans[at][0] <= a:
            live.append(spans[at])
            at += 1
        live = [x for x in live if x[1] > a]
        out.append((a, b, tuple(x[2] for x in live)))
    return out


def idle_by_span(planes: List[Plane]) -> Dict:
    """The first device's idle time inside the slice (``bench.slice`` where
    the trace has one, else the extent of the annotations), by the
    innermost annotation covering it; the share of the idle time inside
    ``bench.collect`` that a ``spark.*`` annotation names; the longest
    single gap, split the same way, and the chain of annotations round its
    longest stretch."""
    notes = annotations(planes)
    if not notes:
        return {}
    window = [e for e in notes if e[0] == reduce_trace.SLICE] or notes
    lo = min(s for _n, s, _d in window)
    hi = max(s + d for _n, s, d in window)
    notes = [e for e in notes if e[0] != reduce_trace.SLICE]
    busy = reduce_trace.union(
        (max(s, lo), min(s + d, hi)) for _n, s, d in first_device_ops(planes)
        if min(s + d, hi) > max(s, lo))
    idle: Dict[str, float] = collections.defaultdict(float)
    in_collect = named = 0.0
    longest = (0.0, {}, ())              # gap ns, ns by innermost, chain
    stretches = innermost(notes, lo, hi)
    at = 0
    for a, b in reduce_trace.gaps(busy, lo, hi):
        while stretches[at][1] <= a:
            at += 1
        best = (0.0, ())
        parts: Dict[str, float] = collections.defaultdict(float)
        i = at
        while i < len(stretches) and stretches[i][0] < b:
            s, e, chain = stretches[i]
            i += 1
            ns = min(e, b) - max(s, a)
            parts[chain[-1] if chain else reduce_trace.SLICE] += ns
            if COLLECT in chain:
                in_collect += ns
                if chain[-1].startswith(SPAN_PREFIX):
                    named += ns
            if ns > best[0]:
                best = (ns, chain)
        for name, ns in parts.items():
            idle[name] += ns
        if b - a > longest[0]:
            longest = (b - a, parts, best[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(idle.values()) / 1e9,
        "idle_by_span": sorted(([k, v / 1e9] for k, v in idle.items()),
                               key=lambda kv: -kv[1]),
        "idle_in_collect_s": in_collect / 1e9,
        "named_share": named / in_collect if in_collect else None,
        "longest_gap_s": longest[0] / 1e9,
        "longest_gap_by_span": sorted(
            ([k, v / 1e9] for k, v in longest[1].items()),
            key=lambda kv: -kv[1]),
        "longest_gap_in": list(longest[2]),
    }


def annotation_self_ms(planes: List[Plane]) -> List[Tuple[str, int, float]]:
    """(annotation, count, self ms in all) per ``spark.*`` / ``bench.*``
    name, each host line on its own (a thread's annotations nest)."""
    count: Dict[str, int] = collections.Counter()
    ms: Dict[str, float] = collections.defaultdict(float)
    for pname, lines in planes:
        if pname != reduce_trace.HOST_PLANE:
            continue
        for _lname, events in lines:
            mine = [e for e in events
                    if e[0].startswith((reduce_trace.ANNOTATION_PREFIX,
                                        SPAN_PREFIX))]
            count.update(n for n, _s, _d in mine)
            for name, ns in reduce_trace.self_times(mine).items():
                ms[name] += ns / 1e6
    return sorted(((n, count[n], ms[n]) for n in ms), key=lambda r: -r[2])


def report(planes: List[Plane]) -> str:
    out = []
    rows = annotation_self_ms(planes)
    runs = max(1, sum(c for n, c, _ms in rows if n == COLLECT))
    out.append(f"self time by annotation ({runs} x {COLLECT}):")
    out.append(f"  {'annotation':<28} {'count':>7} {'self ms':>11} "
               f"{'ms/execution':>13}")
    for name, c, ms in rows:
        out.append(f"  {name:<28} {c:>7} {ms:>11.3f} {ms / runs:>13.4f}")
    idle = idle_by_span(planes)
    if idle:
        out.append(f"first device: idle {idle['idle_s']:.6f} s of "
                   f"{idle['window_s']:.6f} s, by innermost annotation:")
        for name, s in idle["idle_by_span"]:
            out.append(f"  {name:<28} {s:>11.6f} s "
                       f"{100 * s / idle['idle_s']:>6.2f} %")
        share = idle["named_share"]
        out.append(
            f"idle inside {COLLECT}: {idle['idle_in_collect_s']:.6f} s, "
            + ("none" if share is None else f"{100 * share:.2f} %")
            + f" of it under a {SPAN_PREFIX}* annotation")
        out.append(
            f"longest single gap {idle['longest_gap_s'] * 1e3:.4f} ms: "
            + ", ".join(f"{name} {s * 1e3:.4f} ms"
                        for name, s in idle["longest_gap_by_span"][:4])
            + "; most of it in "
            + (" > ".join(idle["longest_gap_in"]) or "no annotation"))
    launch = launch_ms(planes)
    out.append("launch_ms: " + ("nothing to read" if launch is None
                                else f"{launch:.4f}"))
    return "\n".join(out)


if __name__ == "__main__":
    print(report(load_planes(sys.argv[1])))

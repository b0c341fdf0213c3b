"""Device operations by the operator that built them.

The program wraps each operator's ``trace()`` of a fused stage in
``jax.named_scope("spark.<Operator>")`` (spark_tpu/trace:
``operator_scope``), so an HLO instruction's ``op_name`` reads
``jit(stage_fn)/.../spark.HashAggregateExec/reduce_sum``. Where XLA fuses
across two scopes the fusion carries one ``op_name`` (its root's), and the
operation counts under the scope that names.

``jax.profiler.ProfileData`` shows an event's own stats only, and the
``op_name`` is not among them, so this file reads the xplane's protobuf
wire format itself (xplane.proto, hlo.proto; field numbers below) and
looks in two places, in this order:

1. any string stat of the event or of its event metadata (the TPU
   profiler's ``tf_op`` and kin) that holds a ``spark.<Operator>`` path
   component;
2. the HLO module the profiler keeps in the ``/host:metadata`` plane under
   the event's ``program_id``: the instruction of the event's name, its
   ``metadata.op_name``.

A program without the scopes (the parent of PR 28) gives no scoped
operation, and the readers built on this return ``None``.

    python benchmark/op_scopes.py <file.xplane.pb>

prints the device time by scope and what the first events' stats hold.
"""

from __future__ import annotations

import collections
import os
import re
import struct
import sys
from typing import Dict, Iterator, List, Optional, Tuple

import reduce_trace

SCOPE = re.compile(r"(?:^|/)spark\.([A-Za-z_]\w*)")
METADATA_PLANE = "/host:metadata"
#: (name, start ns, duration ns, scope or None)
ScopedOp = Tuple[str, float, float, Optional[str]]


# ---- protobuf wire format ---------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for number, _wire, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


# ---- xplane.proto -----------------------------------------------------------
# XSpace: planes 1. XPlane: name 2, lines 3, event_metadata 4 (map),
# stat_metadata 5 (map). XLine: name 2, timestamp_ns 3, events 4.
# XEvent: metadata_id 1, offset_ps 2, duration_ps 3, stats 4.
# XStat: metadata_id 1, uint64 3, int64 4, str 5, bytes 6, ref 7.
# XEventMetadata: id 1, name 2, stats 5. XStatMetadata: id 1, name 2.


def _stat(buf) -> Tuple[int, int, object]:
    """(stat metadata id, field number of the value, value)."""
    sid, kind, value = 0, 0, None
    for number, _wire, v in fields(buf):
        if number == 1:
            sid = v
        else:
            kind, value = number, v
    return sid, kind, value


class Plane:
    def __init__(self, buf):
        self.name = ""
        self.lines: List[Tuple[str, int, List]] = []
        self.stat_names: Dict[int, str] = {}
        #: event metadata id -> (name, [stat])
        self.event_meta: Dict[int, Tuple[str, List]] = {}
        for number, _wire, v in fields(buf):
            if number == 2:
                self.name = _text(v)
            elif number == 3:
                self.lines.append(self._line(v))
            elif number == 4:
                key, value = _map_entry(v)
                name, stats = "", []
                for n2, _w2, v2 in fields(value):
                    if n2 == 2:
                        name = _text(v2)
                    elif n2 == 5:
                        stats.append(_stat(v2))
                self.event_meta[key] = (name, stats)
            elif number == 5:
                key, value = _map_entry(v)
                for n2, _w2, v2 in fields(value):
                    if n2 == 2:
                        self.stat_names[key] = _text(v2)

    @staticmethod
    def _line(buf) -> Tuple[str, int, List]:
        name, t0, events = "", 0, []
        for number, _wire, v in fields(buf):
            if number == 2:
                name = _text(v)
            elif number == 3:
                t0 = v
            elif number == 4:
                mid = offset = dur = 0
                stats = []
                for n2, _w2, v2 in fields(v):
                    if n2 == 1:
                        mid = v2
                    elif n2 == 2:
                        offset = v2
                    elif n2 == 3:
                        dur = v2
                    elif n2 == 4:
                        stats.append(_stat(v2))
                events.append((mid, offset, dur, stats))
        return name, t0, events

    def stat_values(self, stats: List) -> Dict[str, object]:
        """Stat name -> value; strings decoded, references resolved,
        byte strings left as memoryviews."""
        out = {}
        for sid, kind, value in stats:
            if kind == 5:
                value = _text(value)
            elif kind == 7:
                value = self.stat_names.get(value, "")
            out[self.stat_names.get(sid, str(sid))] = value
        return out


def read_planes(path: str) -> List[Plane]:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return [Plane(v) for number, _wire, v in fields(space) if number == 1]


# ---- hlo.proto --------------------------------------------------------------
# HloProto: hlo_module 1. HloModuleProto: computations 3.
# HloComputationProto: instructions 2. HloInstructionProto: name 1,
# metadata 7. OpMetadata: op_name 2.


def hlo_op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> ``metadata.op_name`` of one serialized
    HloProto, over every computation."""
    out: Dict[str, str] = {}
    for n0, _w0, module in fields(hlo_proto):
        if n0 != 1:
            continue
        for n1, _w1, computation in fields(module):
            if n1 != 3:
                continue
            for n2, _w2, instruction in fields(computation):
                if n2 != 2:
                    continue
                name = op_name = ""
                for n3, w3, v3 in fields(instruction):
                    if n3 == 1 and w3 == 2:
                        name = _text(v3)
                    elif n3 == 7 and w3 == 2:
                        for n4, w4, v4 in fields(v3):
                            if n4 == 2 and w4 == 2:
                                op_name = _text(v4)
                if name:
                    out[name] = op_name
    return out


class _Programs:
    """The HLO modules of ``/host:metadata`` by program id, parsed when
    first asked for."""

    def __init__(self, planes: List[Plane]):
        self._protos: Dict[int, object] = {}
        self._names: Dict[int, Dict[str, str]] = {}
        for plane in planes:
            if plane.name != METADATA_PLANE:
                continue
            for key, (_name, stats) in plane.event_meta.items():
                for _sid, kind, value in stats:
                    if kind == 6:
                        self._protos[key] = value

    def op_name(self, program_id, instruction: str) -> Optional[str]:
        if program_id not in self._protos:
            return None
        if program_id not in self._names:
            try:
                self._names[program_id] = hlo_op_names(
                    self._protos[program_id])
            except (ValueError, IndexError, struct.error):
                self._names[program_id] = {}
        return self._names[program_id].get(instruction)


# ---- operations with their scopes -------------------------------------------


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost ``spark.<Operator>`` of an ``op_name`` path."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def scoped_ops(planes: List[Plane], host_ops: bool = False
               ) -> List[Tuple[str, List[ScopedOp]]]:
    """(device plane, its operations with their scopes), one entry per
    chip. ``host_ops``: the CPU rehearsal, where XLA:CPU's operations
    lie on the host plane's lines, marked by an ``hlo_op`` stat, and
    stand for one pretended device (as reduce_trace.host_ops_as_device)."""
    programs = _Programs(planes)
    out: List[Tuple[str, List[ScopedOp]]] = []
    pretended: List[ScopedOp] = []
    for plane in planes:
        device = bool(reduce_trace.DEVICE_PLANE.match(plane.name))
        if not device and not (host_ops
                               and plane.name == reduce_trace.HOST_PLANE):
            continue
        ops: List[ScopedOp] = []
        # an event metadata's stats are the same for each of its events:
        # decoded once
        of_meta: Dict[int, Tuple[str, Dict[str, object]]] = {}
        for lname, t0, events in plane.lines:
            if device and lname != reduce_trace.OPS_LINE:
                continue
            for mid, offset, dur, stats in events:
                if mid not in of_meta:
                    name, meta_stats = plane.event_meta.get(mid, ("", []))
                    of_meta[mid] = (name, plane.stat_values(meta_stats))
                name, values = of_meta[mid]
                if stats:
                    values = {**values, **plane.stat_values(stats)}
                if not device and "hlo_op" not in values:
                    continue
                scope = next(filter(None, (
                    scope_of(v) for v in values.values()
                    if isinstance(v, str))), None)
                if scope is None and "program_id" in values:
                    # the TPU profiler names an event by the instruction's
                    # whole text: "%fusion.5 = pred[...] fusion(...)"
                    scope = scope_of(programs.op_name(
                        values["program_id"],
                        name.split(" = ")[0].lstrip("%")))
                ops.append((name, t0 + offset / 1e3, dur / 1e3, scope))
        if device:
            out.append((plane.name, ops))
        else:
            pretended += ops
    if host_ops and not out:
        out.append(("/device:TPU:0", pretended))
    return sorted(out)


def has_device_plane(planes: List[Plane]) -> bool:
    return any(reduce_trace.DEVICE_PLANE.match(p.name) for p in planes)


def slice_bounds(planes: List[Plane]) -> Optional[Tuple[float, float]]:
    """[start, end) ns of the one ``bench.slice`` annotation."""
    for plane in planes:
        if plane.name != reduce_trace.HOST_PLANE:
            continue
        for _lname, t0, events in plane.lines:
            for mid, offset, dur, _stats in events:
                if plane.event_meta.get(mid, ("",))[0] == reduce_trace.SLICE:
                    lo = t0 + offset / 1e3
                    return lo, lo + dur / 1e3
    return None


def scope_seconds(path: str) -> Optional[Dict[Optional[str], float]]:
    """Device seconds inside the traced slice by scope (``None``: under
    no scope), each operation counted without the operations nested in it
    (reduce_trace.self_times), mean over the chips. A trace without a
    device plane is the CPU rehearsal's. None where the trace has no
    slice or no device operation."""
    planes = read_planes(path)
    bounds = slice_bounds(planes)
    devices = scoped_ops(planes, host_ops=not has_device_plane(planes))
    if bounds is None or not any(ops for _p, ops in devices):
        return None
    lo, hi = bounds
    total: Dict[Optional[str], float] = collections.defaultdict(float)
    for _pname, ops in devices:
        # self_times keys by name: make the key carry the scope
        keyed = [((name, scope), max(s, lo), min(s + d, hi) - max(s, lo))
                 for name, s, d, scope in ops if min(s + d, hi) > max(s, lo)]
        for (_name, scope), ns in reduce_trace.self_times(keyed).items():
            total[scope] += ns
    return {k: v / len(devices) / 1e9 for k, v in total.items()}


def scope_ms_per_execution(ctx: Dict, scope: str) -> Optional[float]:
    """Device ms an execution in operations under ``spark.<scope>`` in
    the slice the harness has just traced; None where there is no trace
    file, it cannot be read, or the program wrote no such scope."""
    cell = ctx["cell"]
    trace_dir = os.path.join(cell.bench_dir, ".trace", cell.entry["name"])
    try:
        seconds = scope_seconds(reduce_trace.newest_xplane(trace_dir))
    except (OSError, ValueError, IndexError, struct.error):
        return None
    executions = (ctx.get("trace") or {}).get("executions")
    if not seconds or not executions or not seconds.get(scope):
        return None
    return seconds[scope] * 1e3 / executions


def main(path: str, show: int = 8) -> None:
    planes = read_planes(path)
    cpu = not has_device_plane(planes)
    seconds = scope_seconds(path) or {}
    for scope, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
        print(f"{s * 1e3:12.3f} ms  {scope}")
    for pname, ops in scoped_ops(planes, cpu):
        print(f"PLANE {pname}: {len(ops)} operations, "
              f"{sum(1 for o in ops if o[3])} under a scope")
    # what the first operations' stats hold
    for plane in planes:
        if not (reduce_trace.DEVICE_PLANE.match(plane.name)
                or (cpu and plane.name == reduce_trace.HOST_PLANE)):
            continue
        for lname, _t0, events in plane.lines:
            if lname != reduce_trace.OPS_LINE and not cpu:
                continue
            for mid, _o, _d, stats in events:
                name, meta = plane.event_meta.get(mid, ("", []))
                values = {**plane.stat_values(meta),
                          **plane.stat_values(stats)}
                if cpu and "hlo_op" not in values:
                    continue
                if show <= 0:
                    return
                show -= 1
                print(name[:200], {
                    k: (f"<{len(v)} bytes>" if isinstance(v, memoryview)
                        else v) for k, v in values.items()})


if __name__ == "__main__":
    main(sys.argv[1])

"""Kernels: the least time the chip could take to move what a GROUP BY on
a key column must move, as a share of the device time an execution spends
in the aggregate: operations built under ``spark.HashAggregateExec``,
``spark.GroupSort`` and ``spark.GroupSum`` (benchmark/op_scopes.py). Bound
by bytes: a sum is a few integer operations a row.

Bytes: the query's work, not the implementation's, so a program that stops
sorting or scattering is judged by the same count. In: the rows the
aggregate was built for (the ``rows`` of the stage's ``group_by`` build
event, strategy ``sorted``) times a row of its inputs, each read once. Out:
the groups it was sized from (the event's ``groups``) times a row of the
result. Peak: benchmark/peaks.json. ``None`` where the program records no
such event (the parent of PR 35) or writes none of the scopes."""

import op_scopes

#: query -> (bytes a row in, bytes a group out)
ROW_BYTES = {
    # in: l_suppkey, l_extendedprice, l_discount as the int32 they are
    # resident as, and the live mask (bool); out: the key (int32) and one
    # exact int64 sum
    "q15_revenue": (3 * 4 + 1, 4 + 8),
}
SCOPES = ("HashAggregateExec", "GroupSort", "GroupSum")


def agg_bytes(query: str, rows: int, groups: int) -> int:
    row, group = ROW_BYTES[query]
    return rows * row + groups * group


def read(ctx):
    done = [ex for ex in ctx["executions"] if ex.error is None]
    built = [e for e in ctx["setup_events"] if e["kind"] == "group_by"
             and e.get("strategy") == "sorted" and e.get("groups")]
    if not done or not built or any(ex.query.name not in ROW_BYTES
                                    for ex in done):
        return None
    device_ms = sum(op_scopes.scope_ms_per_execution(ctx, scope) or 0.0
                    for scope in SCOPES)
    if not device_ms:
        return None
    event = built[-1]   # the stage that runs, built after the blocking run
    bytes_an_execution = sum(
        agg_bytes(ex.query.name, event["rows"], event["groups"])
        for ex in done) / len(done)
    least_ms = 1e3 * bytes_an_execution / (
        ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * least_ms / device_ms

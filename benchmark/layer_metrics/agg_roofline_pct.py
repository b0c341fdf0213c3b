"""Kernels: the least time the chip could take to read what the grouped
aggregate must read, as a share of the device time an execution spends in
operations built under ``spark.HashAggregateExec`` (benchmark/op_scopes.py).
Bound by bytes: the sums are a few integer operations a row.

Bytes: the stage's row capacity (the ``rows`` of the ``seg_sum`` build
events of set-up: what the program itself was built for) times the bytes a
row of the aggregate's inputs: the columns its sums read, its group codes
and the live mask, each read once. Peak: benchmark/peaks.json."""

import op_scopes

#: query -> bytes a row the aggregate must read
ROW_BYTES = {
    # l_quantity, l_extendedprice, l_discount, l_tax: decimal(12,2) as
    # int64, from which all seven sums derive inside the aggregate; two
    # dictionary codes (int32); the live mask (bool)
    "q1": 4 * 8 + 2 * 4 + 1,
}


def agg_bytes(query: str, capacity: int) -> int:
    return capacity * ROW_BYTES[query]


def read(ctx):
    done = [ex for ex in ctx["executions"] if ex.error is None]
    rows = [e["rows"] for e in ctx["setup_events"] if e["kind"] == "seg_sum"]
    if not done or not rows or any(ex.query.name not in ROW_BYTES
                                   for ex in done):
        return None
    device_ms = op_scopes.scope_ms_per_execution(ctx, "HashAggregateExec")
    if not device_ms:
        return None
    capacity = max(rows)
    bytes_an_execution = sum(agg_bytes(ex.query.name, capacity)
                             for ex in done) / len(done)
    least_ms = 1e3 * bytes_an_execution / (
        ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * least_ms / device_ms

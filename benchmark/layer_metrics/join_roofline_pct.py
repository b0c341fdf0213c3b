"""Kernels: the least time the chip could take to move what the query's
joins must move, as a share of the device time an execution spends in
operations built under ``spark.JoinExec`` (benchmark/op_scopes.py). Bytes:
``join_bytes(path)`` of the query's file, reckoned from the data as the
query's work and not from the program's capacities, so a program that
pads or sorts less is judged by the same count; peak:
benchmark/peaks.json. ``None`` where the query's file has no join byte
count or the program writes no such scope."""

import op_scopes


def read(ctx):
    done = [ex for ex in ctx["executions"] if ex.error is None]
    if not done or not all(
            hasattr(getattr(ex.query, "module", None), "join_bytes")
            for ex in done):
        return None
    device_ms = op_scopes.scope_ms_per_execution(ctx, "JoinExec")
    if not device_ms:
        return None
    cache = {}
    for ex in done:
        if ex.query.name not in cache:
            cache[ex.query.name] = ex.query.module.join_bytes(
                ctx["data_path"])
    bytes_an_execution = sum(cache[ex.query.name] for ex in done) / len(done)
    least_ms = 1e3 * bytes_an_execution / (
        ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * least_ms / device_ms

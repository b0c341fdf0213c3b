"""Kernels: the least time the device could take for an execution (the
bytes of the resident columns the query must read, over the peak HBM
bandwidth of the cell's chips) as a share of the time the device was busy
for it. The query's least HBM time against what the device spent, not one
kernel's share. Bytes: ``hbm_bytes(path)`` of the query's file; peak:
benchmark/peaks.json by ``device_kind``; busy time: the profiler trace."""


def read(ctx):
    trace = ctx["trace"]
    done = [ex for ex in ctx["executions"] if ex.error is None]
    if trace is None or not done or trace["busy_s"] <= 0:
        return None
    if not all(hasattr(ex.query.module, "hbm_bytes") for ex in done):
        return None
    cache = {}
    for ex in done:
        if ex.query.name not in cache:
            cache[ex.query.name] = ex.query.module.hbm_bytes(ctx["data_path"])
    least_s = sum(cache[ex.query.name] for ex in done) / (
        ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * least_s / trace["busy_s"]

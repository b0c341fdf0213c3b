"""Mesh: device time per execution inside collective operations
(all-reduce, all-to-all, all-gather, collective-permute, reduce-scatter),
union per chip, mean over the chips, from the profiler trace."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["executions"]:
        return None
    return trace["collective_s"] * 1e3 / trace["executions"]

"""Kernels: device time an execution in operations built under
``spark.GroupSum``, the second half of the sort-based aggregate
(``HashAggregateExec._trace_sorted``: every aggregate's ``_compute_agg``
over the sorted group ids, which is ``kernels.seg_sum`` with K > 64 and its
counts, and the groups' first keys), from the profiler trace by the
operations' ``op_name`` (benchmark/op_scopes.py). ``None`` where the
program writes no such scope (the parent of PR 35, or a query whose
aggregate takes the direct path)."""

import op_scopes


def read(ctx):
    return op_scopes.scope_ms_per_execution(ctx, "GroupSum")

"""Kernels: device time an execution in operations built under
``spark.GroupSort``, the first half of the sort-based aggregate
(``HashAggregateExec._trace_sorted``: the ``lexsort_permutation`` of the
grouping keys, the gather of every column by it, the change-flag group
ids), from the profiler trace by the operations' ``op_name``
(benchmark/op_scopes.py). ``None`` where the program writes no such scope
(the parent of PR 35, or a query whose aggregate takes the direct path)."""

import op_scopes


def read(ctx):
    return op_scopes.scope_ms_per_execution(ctx, "GroupSort")

"""Kernels: device time an execution in operations built under
``spark.JoinExec`` (the traced replay of a join: key packing, the lookup
in the cached index or table, the gathers of the build side), from the
profiler trace by the operations' ``op_name`` (benchmark/op_scopes.py).
``None`` where the program writes no operator scope."""

import op_scopes


def read(ctx):
    return op_scopes.scope_ms_per_execution(ctx, "JoinExec")

"""Entry / SQL layer: the benchmark's clock around ``spark.sql(text)``
(parse, resolve, optimise; no device work), median over the traced slice."""

import statistics


def read(ctx):
    ms = [(ex.t_sql - ex.t0) * 1e3 for ex in ctx["executions"]
          if ex.error is None]
    return statistics.median(ms) if ms else None

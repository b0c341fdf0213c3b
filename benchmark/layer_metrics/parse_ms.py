"""Entry / SQL layer: the program's ``query.parse`` span (``spark.sql``:
text to resolved logical plan), self time, median over the traced slice.
``sql_ms`` is the benchmark's own clock round the same call."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "parse_ms")

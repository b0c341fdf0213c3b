"""Fetch: ``query.rows`` (decode, build Python rows) + the self time of
``query.fetch`` (the packer's dispatch and the host-side views), summed per
execution, median over the traced slice."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "rows_ms")

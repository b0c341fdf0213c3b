"""Fetch: ``fetch.copy`` (device to host copy of buffers that are ready),
summed per execution, median over the traced slice."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "fetch_ms")

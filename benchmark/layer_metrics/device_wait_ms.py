"""Device: ``device.wait`` (fetch_host: the host blocked until the enqueued
work is done) + ``stage.device`` (the mesh engine's sync after a stage),
self times summed per execution, median over the traced slice."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "device_wait_ms")

"""Device: from the start of a stage's dispatch on the host to the first
operation on the device, on the profiler's clock: the first
``spark.stage.dispatch`` inside each ``bench.collect`` to the first device
operation after it, median over the traced slice."""

import span_times


def read(ctx):
    planes = span_times.slice_planes(ctx)
    return None if planes is None else span_times.launch_ms(planes)

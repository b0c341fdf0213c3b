"""Fused stage: ``stage.run`` + ``stage.fused`` + ``stage.dispatch`` +
``compile.probe`` (cache keys and lookup, then the jitted call: flatten and
enqueue), self times summed per execution, median over the traced slice."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "dispatch_ms")

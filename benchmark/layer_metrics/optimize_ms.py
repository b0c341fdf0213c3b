"""Entry / SQL layer: the ``query.optimize`` spans (logical optimisation at
execution time), self time per execution, median over the traced slice."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "optimize_ms")

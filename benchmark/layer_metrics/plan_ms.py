"""Planner: the ``query.plan`` spans (logical to physical, scan-cache
lookup, compaction replay, adaptive binding), self time per execution,
median over the traced slice."""

import span_times


def read(ctx):
    return span_times.metric(ctx, "plan_ms")

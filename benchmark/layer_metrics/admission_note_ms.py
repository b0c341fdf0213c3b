"""Entry / SQL layer: the program's ``admission.note`` spans (the query's
peak ``stage_bytes`` read back from the metrics ring for the scheduler's
admission table: once under the optimized plan, once under the raw one),
self times summed per execution, median over the traced slice. A program
without the span: nothing to read."""

import span_times


def read(ctx):
    return span_times.median_ms(ctx["slice_events"], ("admission.note",))

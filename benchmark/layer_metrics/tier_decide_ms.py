"""Entry / SQL layer: the program's ``tier.decide`` span (recovery.py: the
out-of-HBM tier decision, resident / chunked / planned_chunked, taken again
on every execution before the engine runs), self time per execution, median
over the traced slice. A program without the span: nothing to read."""

import span_times


def read(ctx):
    return span_times.median_ms(ctx["slice_events"], ("tier.decide",))

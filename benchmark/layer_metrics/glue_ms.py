"""Entry / SQL layer, the wrappers: what is left of ``query.execute`` when
the spans that ``optimize_ms`` ... ``rows_ms`` and ``analysis_ms`` name are
taken out: the root's own time, ``storage.pin``, ``mview.probe`` and any
span no metric names. Per execution, median over the traced slice."""

import span_times


def read(ctx):
    return span_times.median_ms(ctx["slice_events"], ("glue",))

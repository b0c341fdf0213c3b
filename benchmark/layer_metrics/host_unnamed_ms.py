"""Entry / SQL layer: what a span still does not name. Per execution,
``glue`` (span_times.per_execution: the self time under ``query.execute`` of
every span no metric names) less the two spans inside it that have a metric of
their own, ``tier.decide`` (tier_decide_ms) and ``admission.note``
(admission_note_ms): the root's own time, ``storage.pin``'s own, ``mview.probe``.
Median over the traced slice, so ``glue_ms`` is about the three summed; on a
program without the two spans it is ``glue_ms``."""

import statistics

import span_times

NAMED_IN_GLUE = ("tier.decide", "admission.note")


def read(ctx):
    values = [max(0.0, d["glue"] - sum(d.get(n, 0.0) for n in NAMED_IN_GLUE))
              for d in span_times.per_execution(ctx["slice_events"])
              if "glue" in d]
    return statistics.median(values) if values else None

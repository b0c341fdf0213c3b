"""Entry / SQL layer: the program's ``query.analysis`` span (static plan
analysis and the submit gate), median over the traced slice."""

import statistics


def read(ctx):
    ms = [e["ms"] for e in ctx["slice_events"]
          if e["kind"] == "span" and e.get("name") == "query.analysis"]
    return statistics.median(ms) if ms else None

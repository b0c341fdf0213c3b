"""Planner, first execution: seconds spent sizing joins in set-up, the sum
of the ``join.size`` spans (planner._size_join: a join's key ranges, its
matches and its build side's index, each a compiled program; their
compiles are in it on a cold cache). ``None`` where the program records no
such span (a program that sizes a join operation by operation)."""


def read(ctx):
    ms = [e["ms"] for e in ctx["setup_events"]
          if e["kind"] == "span" and e.get("name") == "join.size"]
    return sum(ms) / 1e3 if ms else None

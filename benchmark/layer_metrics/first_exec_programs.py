"""Planner, first execution: the compiled programs the query's first
execution ran, from the ``first_exec`` build event of set-up (its
``programs``: each join's sizing and index programs, the join stages the
next join is sized over, and the whole query's stage). On a cold compile
cache each is a compile, so this is what ``first_exec_s`` pays for by
count. ``None`` where the program records no such event."""


def read(ctx):
    firsts = [e for e in ctx["setup_events"] if e["kind"] == "first_exec"]
    return sum(e["programs"] for e in firsts) if firsts else None

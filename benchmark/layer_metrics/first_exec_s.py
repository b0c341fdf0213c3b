"""Planner, blocking run: the benchmark's clock around the first warm-up
execution (scan, per-operator programs, adaptive statistics)."""


def read(ctx):
    return ctx["first_exec_s"]

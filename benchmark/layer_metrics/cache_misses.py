"""Fused stage + compile cache: persistent-cache misses in set-up
(``metrics.compile_cache_stats()``): programs compiled, not loaded."""


def read(ctx):
    return ctx["setup_cache"]["misses"]

"""Fused stage + compile cache: persistent-cache lookups plus
``stage_compile`` events inside the traced slice. Should be 0: nothing
compiles at steady state."""


def read(ctx):
    return ctx["slice_cache_lookups"] + sum(
        1 for e in ctx["slice_events"] if e["kind"] == "stage_compile")

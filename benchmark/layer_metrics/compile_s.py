"""Fused stage + compile cache: seconds of ``stage_compile`` events in
set-up (trace + compile or cache load + enqueue of each fused stage)."""


def read(ctx):
    ms = [e["ms"] for e in ctx["setup_events"]
          if e["kind"] == "stage_compile"]
    return sum(ms) / 1e3

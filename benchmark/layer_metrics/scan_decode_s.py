"""Scan layer: seconds the host spent decoding parquet (and evaluating
pushed filters) in set-up, the sum of the ``scan`` events' ``decode_ms``.
Their ``transfer_ms`` is an enqueue time and is not read (PERF.md)."""


def read(ctx):
    ms = [e["decode_ms"] for e in ctx["setup_events"]
          if e["kind"] == "scan" and "decode_ms" in e]
    return sum(ms) / 1e3 if ms else None

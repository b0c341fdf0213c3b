"""Scan layer: seconds from the decoded table to a batch that is on the
device (dictionary encode, host to device transfer, waited for), the sum of
the ``scan`` events' ``transfer_ms`` in set-up."""


def read(ctx):
    ms = [e["transfer_ms"] for e in ctx["setup_events"]
          if e["kind"] == "scan" and "transfer_ms" in e]
    return sum(ms) / 1e3 if ms else None

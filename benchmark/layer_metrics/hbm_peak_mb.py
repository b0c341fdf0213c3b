"""Device: the allocator's peak (``memory_stats()["peak_bytes_in_use"]``)
after the slice, on the fullest of the cell's chips, in MB (1e6 bytes)."""


def read(ctx):
    peaks = [s["peak_bytes_in_use"] for s in ctx["memory_stats"]
             if "peak_bytes_in_use" in s]
    return max(peaks) / 1e6 if peaks else None

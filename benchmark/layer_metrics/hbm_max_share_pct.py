"""Mesh: live array bytes on the fullest chip as a share of the live
bytes on all of the cell's chips (25 % is even on four; ROADMAP A9)."""


def read(ctx):
    live = ctx["live_bytes"]
    if not live or sum(live.values()) == 0:
        return None
    return 100.0 * max(live.values()) / sum(live.values())

"""The device's idle share of the window, in %, averaged over the chips
the cell uses: 1 - (union of op intervals) / window."""
from bench import trace


def read(ctx, layer):
    idle = trace.idle_share(ctx["events"])
    return None if idle is None else 100.0 * idle

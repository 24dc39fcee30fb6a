"""Device time of the selected kernel or program, in microseconds per
thousand words of the counter the layer names (``per``)."""
from bench import trace


def read(ctx, layer):
    ns = trace.selected_ns(ctx["events"], layer["selector"])
    words = ctx["counters"].get(layer["per"], 0)
    if not ns or not words:
        return None
    return ns / 1e3 / (words / 1e3)

"""Median duration, in ms, of the benchmark's host span ``span`` inside
the window."""
import statistics

from bench import trace


def read(ctx, layer):
    spans = trace.host_spans(ctx["events"], layer["span"])
    if not spans:
        return None
    return statistics.median(s.dur_ns for s in spans) / 1e6

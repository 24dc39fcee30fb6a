"""Share of the roofline: the least time the chip needs for the work
(``bench/work.py``, from the counters the layer's ``args`` name), over
the selected device time, in %. Which bound set it is printed."""
import sys

from bench import trace, work


def read(ctx, layer):
    ns = trace.selected_ns(ctx["events"], layer["selector"])
    if not ns:
        return None
    c = ctx["counters"]
    job = getattr(work, layer["work"])(
        **{arg: c[name] for arg, name in layer["args"].items()})
    share, bound = job.roofline(ns / 1e9, ctx["peak"])
    print(f"{layer['name']}: {job.ops:.0f} ops, {job.bytes:.0f} bytes in"
          f" {ns / 1e9:.6f} s of device time, bound by {bound}",
          file=sys.stderr)
    return share

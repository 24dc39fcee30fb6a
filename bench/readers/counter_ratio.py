"""The ratio of two of the window's counters, ``num`` over ``den``."""


def read(ctx, layer):
    c = ctx["counters"]
    num, den = c.get(layer["num"]), c.get(layer["den"])
    if not num or not den:
        return None
    return num / den

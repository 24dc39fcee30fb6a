"""Corpus words indexed per second: the words of every whole build that
finished in the window, over the window's time."""


def measure(run):
    served = run["served"]
    words = sum(ch.n_words for ch in run["bodies"])
    return len(served.done) * words / (served.t_end - served.t0)

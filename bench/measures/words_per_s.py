"""Words whose roots were returned, over the whole window: all the work of
the requests that finished, over all the window's time."""


def measure(run):
    served, system = run["served"], run["system"]
    words = sum(r.n_words for r in (system.result(rid) for _i, rid, _d, _t
                                    in served.done) if r.failure is None)
    return words / (served.t_end - served.t0)

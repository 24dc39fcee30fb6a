"""95th percentile (nearest rank), over every request due in the window,
of the time from when the request was due to when its result was
complete. A failed or never-finished request counts as the longest
wait the run allows."""
from bench.drive import percentile


def measure(run):
    served, system = run["served"], run["system"]
    lat = [t - due for _i, rid, due, t in served.done
           if system.result(rid).failure is None]
    lost = len(served.done) + len(served.missing) - len(lat)
    lat += [served.t_end - served.t0 + run["grace_s"]] * lost
    return 1e3 * percentile(lat, 95)

"""Mean host wall of one park in the window (ms): ``rescale(0)``, whose
copy of the state to the host is synchronous."""


def read(run):
    parks = [t1 - t0 for kind, _, _, t0, t1 in run.rescales if kind == "park"]
    return 1e3 * sum(parks) / len(parks) if parks else None

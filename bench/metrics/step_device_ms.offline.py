"""Device time per engine step, ms: the union of device op intervals in
the traced window over the engine steps dispatched in it."""


def read(run):
    t = run.trace
    if t is None or not t["steps"]:
        return None
    return 1e3 * t["busy_s"] / t["steps"]

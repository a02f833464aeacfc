"""Share of the traced window in which no operation ran on the device, %."""


def read(run):
    t = run.trace
    return None if t is None else 100.0 * t["idle_share"]

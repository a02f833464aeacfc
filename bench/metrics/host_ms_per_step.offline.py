"""Scheduler host time per engine step over the window, ms: the engine's
own dispatch_s + telemetry_s counters over its step counter."""


def read(run):
    return run.host_ms_per_step()

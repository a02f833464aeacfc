"""Output tokens retired in the window per second of the window (host
clock, harness stamps)."""


def read(run):
    return run.tokens_in_window() / run.seconds

"""Peak device memory in use after the window, GiB
(memory_stats()["peak_bytes_in_use"] of the first device)."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2 ** 30

"""Peak device memory in use after the window (runtime counter), GB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9

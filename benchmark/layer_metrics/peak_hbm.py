"""Peak device memory of the fullest chip after the window, in GB."""


def read(run, spec):
    return run["memory_peak_bytes"] / 1e9

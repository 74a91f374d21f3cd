"""1 - (union of the intervals in which any operation ran on the device)
/ traced window, averaged over the chips used."""


def read(run, spec):
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])

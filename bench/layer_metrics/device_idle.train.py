"""Share of the traced window in which no op ran on the device (mean over
chips), in a training cell."""


def read(run):
    red = run.reduced
    if red is None:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)

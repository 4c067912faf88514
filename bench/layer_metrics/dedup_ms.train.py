"""Device ms a training step spends under the program's ``lpt.dedup`` scope:
sorting the batch's ids to unique rows and summing their gradients.  Per
``bench.train_step`` span in the traced window, mean over chips."""

SCOPE = "lpt.dedup"


def read(run):
    red = run.reduced
    if red is None:
        return None
    seconds, steps = red.scope_seconds(SCOPE), red.count("bench.train_step")
    if seconds <= 0 or steps == 0:
        return None
    return 1e3 * seconds / steps

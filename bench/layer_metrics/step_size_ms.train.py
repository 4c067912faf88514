"""Device ms a training step spends under the program's ``alpt.step_size``
scope: ALPT's step-size pass and update, a second, fenced DCN forward and
backward through the fake-quantized updated rows.  Per ``bench.train_step``
span in the traced window, mean over chips."""

SCOPE = "alpt.step_size"


def read(run):
    red = run.reduced
    if red is None:
        return None
    seconds, steps = red.scope_seconds(SCOPE), red.count("bench.train_step")
    if seconds <= 0 or steps == 0:
        return None
    return 1e3 * seconds / steps

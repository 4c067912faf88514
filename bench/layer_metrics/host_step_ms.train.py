"""The host's ms a training step: the mean duration of the program's
``train.step`` span in the traced window (the batch's copies to the device
and the jitted call; the wait for the step before is outside it)."""

SPAN = "train.step"


def read(run):
    red = run.reduced
    if red is None:
        return None
    return red.span_ms(SPAN)

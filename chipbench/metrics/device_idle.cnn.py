"""device_idle.cnn: share of the traced window in which no operation ran
on the chip, in the paper-net cells (moves samples_per_s). From the device trace:
one minus the union of the operations' intervals over the window."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * run.trace.idle_share()

"""device_idle.llm: share of the traced window in which no operation ran
on the chip, in the smollm-135m cells (moves tokens_per_s). From the device trace:
one minus the union of the operations' intervals over the window."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * run.trace.idle_share()

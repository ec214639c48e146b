"""idle_h2d.cnn: share of the traced window in which the chip ran no
operation while the driving thread was copying a round's batch to it
(the program's ``sdflb.batch_h2d`` span), in the paper-net cells (moves
samples_per_s). Device idle gaps intersected with the span's intervals,
averaged over devices."""
from chipbench import host_spans


def read(run):
    s = host_spans.idle_inside(run.trace, ["sdflb.batch_h2d"])
    if s is None:
        return None
    return 100.0 * s / run.trace.window_s()

"""idle_stall.cnn: share of the traced window in which the chip ran no
operation while the driving thread was blocked in the node: the score
sync, the wait for the previous round's block, the settler hand-off, or
a garbage collection (the spans ``sdflb.score_sync``,
``sdflb.head_wait``, ``sdflb.handoff``, ``sdflb.gc``), in the paper-net
cells (moves samples_per_s). Device idle gaps intersected with the
union of the spans' intervals, averaged over devices."""
from chipbench import host_spans

SPANS = ["sdflb.score_sync", "sdflb.head_wait", "sdflb.handoff", "sdflb.gc"]


def read(run):
    s = host_spans.idle_inside(run.trace, SPANS)
    if s is None:
        return None
    return 100.0 * s / run.trace.window_s()

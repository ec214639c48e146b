"""settle_wait_ms.cnn: mean time a finished round waits before the
settler starts on it, in the paper-net cells (moves
round_latency_p95_s). The program's counter
``RoundRecord.spans["sdflb.settle_queue"]`` (end of the round's finish on
the driving thread to the start of its settlement), over the window's
settled rounds of a traced run. The last traced round is left out: its
hand-off comes only after the profiler has stopped and written the trace,
a wait that no round of an untraced run has."""
from chipbench import host_spans


def read(run):
    s = host_spans.record_mean(run, "sdflb.settle_queue",
                               skip=run.traced_rounds[-1:])
    return None if s is None else 1000.0 * s

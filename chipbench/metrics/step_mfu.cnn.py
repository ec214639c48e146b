"""step_mfu.cnn: the round program's share of the chip's bf16 peak, in
the paper-net cells (moves samples_per_s). The model FLOPs that the aggregated
workers' samples need (the configuration's ``flops_per_sample``) over
the summed device time of the round program's executions in the trace."""
from chipbench.tracing import ROUND_PROGRAM


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_s(ROUND_PROGRAM)
    flops = run.flops_per_sample * run.samples(run.traced_rounds)
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])

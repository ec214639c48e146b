"""trust_roofline: the trust kernels' share of their HBM roofline, in the
paper-net cells (moves samples_per_s). The least work of trust scoring
and aggregation is to read the (W, D) update matrix once and write the
D-vector aggregate once; that many bytes over the chip's HBM bandwidth,
over the summed device time of the trust kernels' operations in the
trace. No implementation can read above 100%.

In the trace (TPU v5 lite, JAX 0.9) the kernels appear on the ``XLA Ops``
line under the names matched by ``TRUST_KERNELS`` in ``tracing.py``."""
from chipbench.tracing import TRUST_KERNELS


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_s(TRUST_KERNELS)
    if t <= 0:
        return None
    least = len(run.traced_rounds) * (run.update_bytes + 4 * run.dim)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / t

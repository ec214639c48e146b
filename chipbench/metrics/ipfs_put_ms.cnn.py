"""ipfs_put_ms.cnn: mean host time of one round's IPFS put (the global
params read from the device, serialised and stored) on the settler
thread, in the paper-net cells (moves round_latency_p95_s). The
program's span ``RoundRecord.spans["sdflb.ipfs_put"]``, over the window's
settled rounds of a traced run."""
from chipbench import host_spans


def read(run):
    s = host_spans.record_mean(run, "sdflb.ipfs_put")
    return None if s is None else 1000.0 * s

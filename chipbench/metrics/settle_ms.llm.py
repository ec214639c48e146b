"""settle_ms.llm: mean host time of settling one round (IPFS put of the
global params, contract, Merkle commit, block seal) on the settler
thread, in the smollm-135m cells. The program's own span,
``RoundRecord.settle_time``, over the window's rounds."""


def read(run):
    times = [r.settle_time for r in run.records if r.settled]
    if not times:
        return None
    return 1000.0 * sum(times) / len(times)

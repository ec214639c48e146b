"""The readers of the program's own spans on a synthetic trace and
records whose intervals are known, and their silence where a program
has no spans."""
import pytest

from chipbench import harness, host_spans, tracing
from chipbench.tracing import Event, Trace

E = Event
CELLS = ["papernet-sync-w1024", "papernet-async-w1024"]
NEW = ["idle_h2d.cnn", "idle_stall.cnn", "settle_wait_ms.cnn",
       "ipfs_put_ms.cnn"]

# window [0, 10] s; the chip idles in [0, 1], [4, 6] and [9, 10]
OPS = [E("%fusion.1 = f32[8] fusion()", 1, 4), E("%fusion.2", 6, 9)]
HOST = [E(tracing.ROUND_SPAN, 0, 5), E(tracing.ROUND_SPAN, 5, 10),
        # inside idle time: 0.5 + 0.5 s
        E("sdflb.batch_h2d", 0.5, 1.2), E("sdflb.batch_h2d", 4.0, 4.5),
        # union [3, 5.5] and [9.2, 9.6]: idle 1.5 + 0.4 s
        E("sdflb.score_sync", 3, 5), E("sdflb.head_wait", 4.8, 5.5),
        E("sdflb.handoff", 9.2, 9.4), E("sdflb.gc", 9.3, 9.6),
        # not a span either reader counts
        E("sdflb.other", 0, 10)]
TR = Trace({0: OPS}, {0: []}, HOST, (0.0, 10.0))


class _Rec:
    def __init__(self, spans=None, settled=True):
        self.settle_time, self.settled, self.participation = 0.01, \
            settled, None
        if spans is not None:
            self.spans = spans


# the second round is the last traced one: its hand-off waited for the
# profiler to stop; the last has not settled
RECS = [_Rec({"sdflb.settle_queue": 0.010, "sdflb.ipfs_put": 0.001}),
        _Rec({"sdflb.settle_queue": 1.000, "sdflb.ipfs_put": 0.002}),
        _Rec({"sdflb.settle_queue": 0.030, "sdflb.ipfs_put": 0.003}),
        _Rec({"sdflb.settle_queue": 9.0, "sdflb.ipfs_put": 9.0},
             settled=False)]


def _read(cell_name, name, trace=TR, recs=RECS):
    cell = harness.load_cell(cell_name)
    run = harness.Run(
        cell=cell, records=recs, trace=trace, traced_rounds=recs[:2],
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        flops_per_sample=1000, samples_per_worker=10,
        update_bytes=4_000_000, dim=1000)
    return harness.read_metric(cell, name, run)


def test_overlap():
    a = [(0, 1), (4, 6), (9, 10)]
    assert host_spans.overlap(a, [(0.5, 4.5)]) == pytest.approx(1.0)
    assert host_spans.overlap(a, [(5, 9.5), (9.7, 12)]) == pytest.approx(1.8)
    assert host_spans.overlap(a, []) == 0.0


def test_idle_inside_averages_devices():
    assert host_spans.idle_inside(TR, ["sdflb.batch_h2d"]) \
        == pytest.approx(1.0)
    two = Trace({0: OPS, 1: [E("x", 0, 10)]}, {}, HOST, (0.0, 10.0))
    assert host_spans.idle_inside(two, ["sdflb.batch_h2d"]) \
        == pytest.approx(0.5)


@pytest.mark.parametrize("cell", CELLS)
def test_readers_on_known_intervals(cell):
    assert _read(cell, "idle_h2d.cnn") == pytest.approx(10.0)
    assert _read(cell, "idle_stall.cnn") == pytest.approx(19.0)
    # settled rounds only, and no wait behind the profiler's stop
    assert _read(cell, "settle_wait_ms.cnn") == pytest.approx(20.0)
    assert _read(cell, "ipfs_put_ms.cnn") == pytest.approx(2.0)


def test_new_readers_are_in_both_cells():
    for cell in CELLS:
        names = {m["name"] for m in harness.load_cell(cell).per_layer}
        assert set(NEW) <= names


def test_idle_split_stays_inside_device_idle():
    idle = _read(CELLS[0], "device_idle.cnn")
    assert _read(CELLS[0], "idle_h2d.cnn") \
        + _read(CELLS[0], "idle_stall.cnn") <= idle


@pytest.mark.parametrize("name", NEW)
def test_silent_without_spans(name):
    no_spans = Trace({0: OPS}, {0: []},
                     [e for e in HOST if not e.name.startswith("sdflb.")],
                     (0.0, 10.0))
    bare = [_Rec(), _Rec()]
    no_ops = Trace({}, {}, HOST, (0.0, 10.0))
    if name.startswith("idle_"):
        assert _read(CELLS[0], name, trace=no_spans) is None
    else:
        assert _read(CELLS[0], name, recs=bare) is None
    assert _read(CELLS[0], name, trace=None) is None
    assert _read(CELLS[0], name, trace=no_ops) is None

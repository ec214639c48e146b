"""Host spans of the node (``repro.core.spans``): the helper's sums and
nesting, its events in a profiler trace, and the durations every round
of a node run keeps in ``RoundRecord.spans``."""
import gc
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederationConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core.async_sim import WorkerProfile
from repro.core.protocol import SDFLBProtocol
from repro.core.spans import span, trace_gc
from repro.data.datasets import make_federated_mnist

TC = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
DRIVING = {"sdflb.batch_h2d", "sdflb.handoff", "sdflb.head_wait",
           "sdflb.score_sync"}
SETTLER = {"sdflb.settle", "sdflb.ipfs_put", "sdflb.settle_queue"}


def test_span_nests_and_sums_repeats():
    into = {}
    with span("outer", into):
        for _ in range(2):
            with span("inner", into):
                time.sleep(0.002)
    assert set(into) == {"outer", "inner"}
    assert into["inner"] >= 0.004
    assert into["outer"] >= into["inner"]


def test_span_without_a_dict_keeps_nothing():
    with span("alone") as s:
        pass
    assert s.into is None
    assert s.t0 > 0


def test_span_entered_by_hand_closes_once():
    into = {}
    s = span("by_hand", into).__enter__()
    s.close()
    assert into["by_hand"] >= 0.0
    assert list(into) == ["by_hand"]


def test_trace_gc_registers_one_hook():
    trace_gc()
    trace_gc()
    hooks = [h for h in gc.callbacks
             if getattr(h, "__module__", "") == "repro.core.spans"]
    assert len(hooks) == 1


def _host_lines(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    return [[e.name for e in line.events]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines]


def test_span_lands_on_the_calling_threads_line(tmp_path):
    trace_gc()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("sdflb.test_outer"):
            with span("sdflb.test_inner"):
                jnp.ones(8).block_until_ready()
            gc.collect()

        def side():
            with span("sdflb.test_side"):
                pass
        t = threading.Thread(target=side)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    lines = _host_lines(tmp_path)
    mine, = [n for n in lines if "sdflb.test_outer" in n]
    assert "sdflb.test_inner" in mine
    assert "sdflb.gc" in mine
    assert "sdflb.test_side" not in mine
    assert any("sdflb.test_side" in n for n in lines)


def _check_spans(recs):
    assert recs
    for r in recs:
        assert set(r.spans) == DRIVING | SETTLER, r.round_index
        assert all(v >= 0.0 for v in r.spans.values())
        assert r.settle_time == r.spans["sdflb.settle"]
        assert r.chain_time == r.spans["sdflb.handoff"]
        assert r.spans["sdflb.ipfs_put"] <= r.spans["sdflb.settle"]
        assert not hasattr(r, "wall_time")


@pytest.mark.parametrize("depth", [0, 2])
def test_sync_rounds_keep_every_span(depth):
    fed = FederationConfig(num_clusters=1, workers_per_cluster=3,
                           trust_threshold=0.2, pipeline_depth=depth)
    ds = make_federated_mnist(3, samples=256, seed=0)
    proto = SDFLBProtocol(get_config("paper-net"), fed, TC,
                          use_blockchain=True, seed=0)
    recs = [proto.run_round(ds.round_batches(16)) for _ in range(3)]
    proto.flush()
    _check_spans(recs)
    if depth == 0:
        # the inline driver settles round r-1 inside round r's hand-off
        assert recs[1].chain_time >= recs[0].settle_time
    proto.finalize()


def test_event_rounds_keep_every_span():
    W = 4
    fed = FederationConfig(num_clusters=2, workers_per_cluster=2,
                           async_mode=True, buffer_size=2,
                           trust_threshold=0.3, merkle_chunk_size=1)
    profiles = [WorkerProfile(speed=1.0 + 0.5 * w, jitter=0.1)
                for w in range(W)]
    ds = make_federated_mnist(W, samples=256, seed=1)
    proto = SDFLBProtocol(get_config("paper-net"), fed, TC,
                          use_blockchain=True, seed=1,
                          arrival_profiles=profiles)
    recs = proto.run_events(lambda r: ds.round_batches(16), events=4)
    proto.flush()
    _check_spans(recs)
    assert all(np.asarray(r.participation).sum() > 0 for r in recs)
    proto.finalize()

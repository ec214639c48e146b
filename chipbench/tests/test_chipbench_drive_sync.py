"""The paper-net synchronous cell's driver loop at CPU size (W = 4): the
result line, the traced run, and the check seeing each fault that the
timed path can have."""
import jax
import pytest

from chipbench_tiny import drive

CELL = "papernet-sync-w1024"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_result_line(tiny_bench):
    out, logs = drive(tiny_bench, CELL)
    assert list(out) == KEYS
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "round_latency_p95_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(len(v) == 2 for v in out["checks"].values())
    # every program of the window was compiled in set-up
    assert any(line.endswith("compilations in the window: 0")
               for line in logs)


def test_traced_run(tiny_bench):
    out, logs = drive(tiny_bench, CELL, trace=True)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert out["correct"] is True
    # a CPU trace has no TPU plane: the device readers stay silent and
    # only the settler's span is read
    assert set(out["metrics"]) == {"settle_ms.cnn"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _broken(fault):
    """``make_fl_round`` with ``fault`` planted under the timed path."""
    from repro.core import fl_step
    make = fl_step.make_fl_round

    def broken(*a, **kw):
        f = make(*a, **kw)

        def round_fn(gp, opt, batch, rngs=None, participation=None,
                     async_state=None):
            if fault == "half_batch":       # the mean over the rest
                batch = jax.tree.map(lambda x: x[:, :, :x.shape[2] // 2],
                                     batch)
            args = (gp, opt, batch, rngs, participation)
            res = f(*args) if async_state is None else f(*args, async_state)
            out = res if async_state is None else res[0]
            if fault == "state_unchanged":
                out = out._replace(global_params=gp, opt_state=opt)
            if fault == "answer_altered":   # worker 0's score
                out = out._replace(scores=out.scores.at[0].add(0.05))
            return out if async_state is None else (out, res[1])
        return round_fn
    return broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_each_fault_fails_the_check(tiny_bench, monkeypatch, fault):
    from repro.core import fl_step
    monkeypatch.setattr(fl_step, "make_fl_round", _broken(fault))
    out, _ = drive(tiny_bench, CELL, seconds=0.3)
    assert out["correct"] is False
    assert any(v > lim for v, lim in out["checks"].values())

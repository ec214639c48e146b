"""Chip smoke: the SDFL-B training round, once, on one TPU.

Drives the main path through the entry points a user calls —
``SDFLBProtocol`` → the jitted ``fl_step`` round → the trust kernels → host
settlement → a sealed ledger that deep-verifies — at real cohort sizes,
with random weights made from a seed:

  a) paper-net, synchronous: W = 8 clusters × 128 workers, 32 images each,
     5 rounds with the chain on (the fused Pallas trust path, picked by
     ``fused_trust_path="auto"``);
  b) the same round from (a)'s state with ``fused_trust_path="off"``: the
     per-leaf jnp path must agree with the kernels;
  c) paper-net, event-driven: W = 1024 with heterogeneous arrivals, a
     256-update buffer, 6 events (the staleness-discounting async kernel);
  d) smollm-135m at its published widths: W = 2, adamw, 4 × 1024 tokens
     per worker, 2 rounds (the per-leaf trust path).

    python chip_smoke.py

Each phase prints one JSON line (compile seconds, median round seconds
with the scores synced to the host, ``peak_bytes_in_use`` so far, whether
the compiled round holds a Pallas kernel). The last line is the device
record. Without a TPU it exits 1 before any phase runs.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

# Fused (Pallas) vs per-leaf (XLA) round from the same state: both compute
# the same f32 math, with reductions over D = 21,840 in a different order
# (128-row chunks × D tiles in the kernels, XLA's own tree in the per-leaf
# path). That moves a statistic by a few f32 ulps per partial sum, about
# 1e-6 relative; 1e-4 leaves a wide margin and still catches a tiling,
# masking or precision fault, which moves a score by 1e-3 or more. Updated
# params sit near 0.1 and move by ~1e-3 a round, so 1e-6 absolute is the
# same 1e-4 relative bound on the aggregate.
SCORE_RTOL, SCORE_ATOL = 1e-4, 1e-6
PARAM_RTOL, PARAM_ATOL = 1e-5, 1e-6


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _compile(task, batch, participation=None):
    """AOT-compile the round the task dispatches: (seconds, has kernel)."""
    t0 = time.perf_counter()
    text = task.lower_round(batch, participation).compile().as_text()
    return time.perf_counter() - t0, "tpu_custom_call" in text


def _timed(fn, n):
    """Call ``fn`` n times; the seconds of each call."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


def _line(phase, W, compile_s, round_s, kernel, **extra):
    return {"phase": phase, "W": W, "compile_s": compile_s,
            "round_s_median": statistics.median(round_s) if round_s else None,
            "round_s": round_s, "peak_bytes_in_use": _peak_bytes(),
            "tpu_custom_call": kernel, **extra}


def _check_ledger(proto, total0):
    assert proto.ledger.verify_chain(deep=True), "ledger deep-verify failed"
    total = proto.contract.total_value()
    assert abs(total - total0) <= 1e-9 * total0, \
        f"value not conserved: {total0} -> {total}"


def paper_net_sync(*, clusters=8, per_cluster=128, images=32, rounds=5,
                   seed=0, expect_kernel=True):
    """Phase (a). Returns its report line and the state phase (b) starts
    from."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core.protocol import SDFLBProtocol
    from repro.data.datasets import make_federated_mnist

    cfg = get_config("paper-net")
    fed = FederationConfig(num_clusters=clusters,
                           workers_per_cluster=per_cluster)
    tc = TrainConfig()
    W = clusters * per_cluster
    proto = SDFLBProtocol(cfg, fed, tc, use_blockchain=True, seed=seed)
    total0 = proto.contract.total_value()
    ds = make_federated_mnist(W, samples=W * images, seed=seed)
    batches = [ds.round_batches(images) for _ in range(rounds + 1)]

    compile_s, kernel = _compile(proto.task, batches[0])
    assert kernel == expect_kernel, f"trust kernel in round: {kernel}"
    recs = []
    round_s = _timed(lambda: recs.append(
        proto.run_round(batches[len(recs)])), rounds)
    for rec in recs:
        assert np.isfinite(rec.scores).all(), "non-finite trust scores"
    # the state (b) starts from, taken before the task is closed
    state = (cfg, fed, tc, proto.global_params, proto.opt_state,
             batches[rounds], jax.random.PRNGKey(seed + 1))
    proto.flush()
    proto.finalize()
    _check_ledger(proto, total0)
    line = _line("a_paper_net_sync", W, compile_s, round_s[1:], kernel,
                 blocks=len(proto.ledger.blocks))
    return line, state


def kernel_vs_reference(state, *, expect_kernel=True):
    """Phase (b): one round from (a)'s state, fused kernels vs the per-leaf
    path, each compiled ahead of time and called once; returns the report
    line with the largest differences."""
    from repro.core import fl_step

    cfg, fed, tc, gp, opt, batch, rng = state
    batch = {k: jnp.asarray(v)[:, None] for k, v in batch.items()}
    outs, compile_s, kernel = {}, {}, {}
    for path in ("auto", "off"):
        f = dataclasses.replace(fed, fused_trust_path=path)
        t0 = time.perf_counter()
        compiled = jax.jit(fl_step.make_fl_round(cfg, f, tc)).lower(
            gp, opt, batch, rng).compile()
        compile_s[path] = time.perf_counter() - t0
        kernel[path] = "tpu_custom_call" in compiled.as_text()
        outs[path] = jax.device_get(compiled(gp, opt, batch, rng))
    assert kernel == {"auto": expect_kernel, "off": False}, kernel
    fused, ref = outs["auto"], outs["off"]
    diffs = {}
    for name, a, b, rtol, atol in (
            ("scores", fused.scores, ref.scores, SCORE_RTOL, SCORE_ATOL),
            ("weights", fused.weights, ref.weights, SCORE_RTOL, SCORE_ATOL),
            ("params", fused.global_params, ref.global_params,
             PARAM_RTOL, PARAM_ATOL)):
        pairs = list(zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        for x, y in pairs:
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol,
                                       err_msg=name)
        diffs[f"max_abs_diff_{name}"] = max(
            float(np.max(np.abs(x - y))) for x, y in pairs)
    return {"phase": "b_kernel_vs_reference", "W": len(fused.scores),
            "compile_s": compile_s, "tpu_custom_call": kernel,
            "score_rtol": SCORE_RTOL, "score_atol": SCORE_ATOL,
            "param_rtol": PARAM_RTOL, "param_atol": PARAM_ATOL, **diffs,
            "peak_bytes_in_use": _peak_bytes()}


def paper_net_events(*, clusters=8, per_cluster=128, images=32,
                     buffer_size=256, events=6, seed=0, expect_kernel=True):
    """Phase (c): event-driven rounds with heterogeneous arrivals."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core import async_sim
    from repro.core.protocol import SDFLBProtocol
    from repro.data.datasets import make_federated_mnist

    cfg = get_config("paper-net")
    W = clusters * per_cluster
    fed = FederationConfig(num_clusters=clusters,
                           workers_per_cluster=per_cluster, async_mode=True,
                           buffer_size=buffer_size)
    profiles = async_sim.heterogeneous_profiles(W, seed=seed)
    proto = SDFLBProtocol(cfg, fed, TrainConfig(), use_blockchain=True,
                          seed=seed, arrival_profiles=profiles)
    total0 = proto.contract.total_value()
    ds = make_federated_mnist(W, samples=W * images, seed=seed)
    batches = [ds.round_batches(images) for _ in range(events)]

    compile_s, kernel = _compile(proto.task, batches[0], np.ones(W))
    assert kernel == expect_kernel, f"trust kernels in round: {kernel}"
    recs = []
    round_s = _timed(lambda: recs.extend(proto.run_events(
        lambda r: batches[r % events], events=1)), events)
    proto.flush()
    stale_on_chain = 0
    for rec in recs:
        assert np.isfinite(rec.scores).all(), "non-finite trust scores"
        for w in np.nonzero(rec.participation)[0]:
            proof = proto.contract.settlement_proof(rec.round_index, int(w))
            assert proto.contract.verify_settlement(proof)
            assert proof["record"]["staleness"] == rec.staleness[w]
            stale_on_chain = max(stale_on_chain,
                                 int(proof["record"]["staleness"]))
    assert stale_on_chain > 0, "no stale update reached the chain"
    proto.finalize()
    _check_ledger(proto, total0)
    return _line("c_paper_net_events", W, compile_s, round_s[1:], kernel,
                 events=len(recs), max_staleness_on_chain=stale_on_chain)


def smollm_rounds(*, cfg=None, seq=1024, batch=4, rounds=2, seed=0,
                  expect_kernel=False):
    """Phase (d): smollm-135m (published widths unless ``cfg`` is given)
    through the same path, per-leaf trust."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core.protocol import SDFLBProtocol
    from repro.data.datasets import synthetic_tokens

    cfg = cfg or get_config("smollm-135m")
    fed = FederationConfig(num_clusters=1, workers_per_cluster=2,
                           top_k_rewarded=1)
    tc = TrainConfig(optimizer="adamw", remat=True, grad_clip=1.0)
    proto = SDFLBProtocol(cfg, fed, tc, use_blockchain=True, seed=seed)
    total0 = proto.contract.total_value()
    data = [synthetic_tokens(2, batch, seq, cfg.vocab_size, seed=seed + r)
            for r in range(rounds)]

    compile_s, kernel = _compile(proto.task, data[0])
    assert kernel == expect_kernel, f"trust kernel in round: {kernel}"
    recs = []
    round_s = _timed(lambda: recs.append(
        proto.run_round(data[len(recs)])), rounds)
    for rec in recs:
        assert np.isfinite(rec.losses).all(), "non-finite losses"
        assert np.isfinite(rec.scores).all(), "non-finite trust scores"
    proto.finalize()
    _check_ledger(proto, total0)
    return _line("d_smollm_135m", 2, compile_s, round_s[1:], kernel,
                 tokens_per_worker=batch * seq,
                 losses=[float(x) for x in recs[-1].losses])


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    line, state = paper_net_sync()
    print(json.dumps(line), flush=True)
    print(json.dumps(kernel_vs_reference(state)), flush=True)
    del state
    print(json.dumps(paper_net_events()), flush=True)
    print(json.dumps(smollm_rounds()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Pallas kernel micro-benchmarks.

On this CPU container the kernels execute in interpret mode — timings are
NOT TPU-representative (documented); the derived column reports the
modeled TPU-v5e time from bytes/bandwidth, which is what §Roofline uses.
The jnp oracle is timed for a like-for-like CPU comparison."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import bench_json, csv_row, timeit
from repro.kernels import ops, ref

HBM_BW = 819e9


def run():
    key = jax.random.PRNGKey(0)
    W, D = 16, 1 << 20
    u = jax.random.normal(key, (W, D), jnp.bfloat16)
    wts = jax.random.uniform(jax.random.fold_in(key, 1), (W,))

    jd = jax.jit(ref.trust_agg_ref)
    us = timeit(jd, u, wts, iters=5)
    model_us = (W * D * 2) / HBM_BW * 1e6
    csv_row("trust_agg_jnp_cpu", us, f"modeled_v5e_us={model_us:.1f}")
    us = timeit(lambda a, b: ops.trust_weighted_aggregate(a, b), u, wts,
                iters=2, warmup=1)
    csv_row("trust_agg_pallas_interpret", us, "CPU interpret (not TPU perf)")

    js = jax.jit(ref.trust_score_ref)
    us = timeit(js, u, iters=5)
    csv_row("trust_score_jnp_cpu", us, f"modeled_v5e_us={model_us:.1f}")

    B, H, KV, hd, S, win = 4, 32, 8, 128, 32768, 4096
    q = jax.random.normal(key, (B, H, hd), jnp.bfloat16)
    kc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd), jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 3), (B, S, KV, hd), jnp.bfloat16)
    jr = jax.jit(lambda q, k, v: ref.swa_decode_ref(q, k, v, S - 1, win))
    us = timeit(jr, q, kc, vc, iters=3)
    win_bytes = B * win * KV * hd * 2 * 2
    full_bytes = B * S * KV * hd * 2 * 2
    csv_row("swa_decode_jnp_fullscan_cpu", us,
            f"modeled_v5e_us={full_bytes / HBM_BW * 1e6:.1f}")
    csv_row("swa_decode_kernel_window_model", 0.0,
            f"modeled_v5e_us={win_bytes / HBM_BW * 1e6:.1f} "
            f"({S / win:.0f}x less HBM than full scan)")
    return True


def run_fused_round(worker_counts=(256, 1024, 4096, 10240), *, e2e=True,
                    wall_gate=True, json_name="fused_round"):
    """Fused flat-pack trust round vs the per-leaf reference on the paper
    CNN's shapes (D=21840 f32), swept over cohort sizes up to the
    10k-client target.

    Per W: CPU wall time of both step-3–5 pipelines (stats → scores →
    weights → aggregate), the unfused path's streamed passes over the W×D
    update volume as XLA's ``cost_analysis`` counts them (operand bytes
    per op — fusion dedup is invisible to it, so this is an upper-bound
    style count and is only used for the *unfused* side), the fused
    chain's passes from exact BlockSpec-geometry accounting
    (``fused_round.update_passes`` — the ≤2 gate), and modeled TPU-v5e
    time from bytes/bandwidth. Gates (CI): fused passes ≤ 2 and no
    CPU wall regression of the default path (fused ≤ 1.15× unfused at
    the largest W ≤ 4096 — interpret-mode Pallas is NOT on this path;
    on CPU the fused chain dispatches to the identical flat-jnp math).
    """
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core import fl_step, hierarchy, trust
    from repro.kernels import fused_round, pack
    from repro.models import api

    cfg = get_config("paper-net")
    key = jax.random.PRNGKey(0)
    gp, _ = api.init(cfg, key, tp=1)
    spec = pack.pack_spec(gp)
    D = spec.total
    payload = {"D": D, "dtype": "float32", "sweep": [],
               "gates": {"fused_passes_max": 2.0,
                         "wall_ratio_max": 1.15 if wall_gate else None}}

    for W in worker_counts:
        fed = FederationConfig(num_clusters=1, workers_per_cluster=W,
                               trust_threshold=0.2)
        kw = jax.random.fold_in(key, W)
        flat = jax.random.normal(kw, (W, D), jnp.float32) * 0.01
        upd = pack.unpack_stack(flat, spec)
        lb = jax.random.uniform(jax.random.fold_in(kw, 1), (W,)) + 1.0
        la = lb - 0.1

        def per_leaf(upd, lb, la, fed=fed):
            s = trust.scores_from_stats(trust.update_stats(upd, lb, la), fed)
            w = trust.trust_weights(s, fed)
            return hierarchy.aggregate_fused(upd, w)

        def fused(flat, lb, la, fed=fed):
            s = trust.scores_from_stats(
                trust.update_stats_flat(flat, lb, la), fed)
            w = trust.trust_weights(s, fed)
            return ops.fused_agg(flat, w)

        iters = 2 if W >= 4096 else 5
        unfused_us = timeit(jax.jit(per_leaf), upd, lb, la,
                            iters=iters, warmup=1)
        fused_us = timeit(jax.jit(fused), flat, lb, la,
                          iters=iters, warmup=1)
        cost = jax.jit(per_leaf).lower(upd, lb, la).compile().cost_analysis()
        vol = W * D * 4
        unfused_passes = cost.get("bytes accessed", 0.0) / vol
        fused_passes = fused_round.update_passes(W, D, jnp.float32)
        model_fused_us = fused_round.streamed_bytes(
            W, D, jnp.float32)["total"] / HBM_BW * 1e6
        model_unfused_us = unfused_passes * vol / HBM_BW * 1e6
        row = {"W": W, "unfused_us": unfused_us, "fused_us": fused_us,
               "unfused_passes_cost_analysis": unfused_passes,
               "fused_passes_analytic": fused_passes,
               "modeled_v5e_us_unfused": model_unfused_us,
               "modeled_v5e_us_fused": model_fused_us}
        payload["sweep"].append(row)
        csv_row(f"fused_round_W{W}_unfused_jnp_cpu", unfused_us,
                f"passes~{unfused_passes:.2f} (cost_analysis) "
                f"modeled_v5e_us={model_unfused_us:.1f}")
        csv_row(f"fused_round_W{W}_fused_flat_cpu", fused_us,
                f"passes={fused_passes:.2f} (BlockSpec-exact) "
                f"modeled_v5e_us={model_fused_us:.1f}")
        assert fused_passes <= 2.0, \
            f"fused chain streams the update volume {fused_passes}x > 2"

    # interpret-mode Pallas at the smallest W: kernel-correctness cost
    # only — Python-interpreted tiles, NOT representative of TPU perf
    Ws = worker_counts[0]
    flat_s = jax.random.normal(key, (Ws, D), jnp.float32)
    wt = jax.random.uniform(jax.random.fold_in(key, 1), (Ws,))
    us = timeit(lambda a, b: ops.trust_weighted_aggregate(a, b),
                flat_s, wt, iters=2, warmup=1)
    csv_row(f"fused_round_W{Ws}_pallas_interpret", us,
            "CPU interpret (not TPU perf)")

    if wall_gate:
        gate_rows = [r for r in payload["sweep"] if r["W"] <= 4096]
        r = gate_rows[-1]
        ratio = r["fused_us"] / r["unfused_us"]
        payload["gates"]["wall_ratio_measured"] = ratio
        assert ratio <= 1.15, \
            (f"fused path regressed the default round at W={r['W']}: "
             f"{r['fused_us']:.0f}us vs {r['unfused_us']:.0f}us")

    if e2e:
        # whole paper-CNN round, knob off vs on (auto==on for the CNN)
        W, B = 256, 32
        batch = {"images": jax.random.normal(key, (W, 1, B, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.fold_in(key, 2),
                                              (W, 1, B), 0, 10)}
        tc = TrainConfig()
        for knob in ("off", "on"):
            fed = FederationConfig(num_clusters=1, workers_per_cluster=W,
                                   trust_threshold=0.2,
                                   fused_trust_path=knob)
            opt = fl_step.init_worker_opt(gp, fed, tc)
            fn = jax.jit(fl_step.make_fl_round(cfg, fed, tc))
            us = timeit(fn, gp, opt, batch, jax.random.PRNGKey(1),
                        iters=3, warmup=1)
            payload[f"e2e_round_W{W}_{knob}_us"] = us
            csv_row(f"fused_round_e2e_W{W}_knob_{knob}", us, "full round")

    bench_json(json_name, payload)
    return payload


if __name__ == "__main__":
    run()
    run_fused_round()

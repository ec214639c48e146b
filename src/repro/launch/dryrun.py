import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, print memory/cost analysis, and extract the roofline
terms. MUST be the process entrypoint (device count locks on first jax init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out]
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs.base import FederationConfig
from repro.configs.registry import ARCH_IDS, INPUT_SHAPES, applicable
from repro.launch import mesh as meshlib
from repro.launch import specs as speclib

# collective cost convention (ring algorithms, bytes moved per device per op,
# expressed as a multiple of the per-device HLO operand/result bytes)
COLLECTIVE_FACTORS = {
    "all-reduce": 2.0,          # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)\[([0-9,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def _first_shape_bytes(line: str) -> int:
    m = _SHAPE_RE.search(line)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES[dt]


def _split_computations(hlo_text: str):
    """{computation_name: [lines]} from an HLO text dump."""
    comps, cur, name = {}, None, None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)(?:\.clone)? \(.*\) -> .* \{",
                     line.strip())
        if m:
            name = m.group(1)
            cur = comps.setdefault(name, [])
            continue
        if line.strip() == "}":
            name, cur = None, None
            continue
        if cur is not None:
            cur.append(line.strip())
    return comps


def _trip_count(cond_lines):
    """Best-effort loop bound from a while condition computation: the
    largest s32 constant compared against the induction variable."""
    best = 1
    for s in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", s):
            best = max(best, int(m.group(1)))
    return best


def collective_bytes(hlo_text: str):
    """Per-device collective bytes summed over the partitioned HLO, with
    collectives inside while bodies multiplied by the loop trip count
    (lax.scan lowers to while; XLA cost tools count bodies once — we don't).
    Returns (total weighted bytes, per-op-kind breakdown)."""
    comps = _split_computations(hlo_text)
    # map body -> trip count via while instructions anywhere in the module
    body_trip = {}
    for lines in comps.values():
        for s in lines:
            m = re.search(r"while\(.*condition=%?([\w.\-]+).*body=%?([\w.\-]+)",
                          s)
            if m:
                cond, body = m.group(1), m.group(2)
                body_trip[body] = _trip_count(comps.get(cond, []))

    # nested loops: effective multiplier = product along the call chain;
    # compute by propagating (bodies referencing inner whiles already carry
    # their inner multiplication when we walk each computation separately)
    def comp_multiplier(name, seen=()):
        mult = body_trip.get(name, 1) if name in body_trip else 1
        return mult

    breakdown = {k: {"count": 0, "bytes": 0.0} for k in COLLECTIVE_FACTORS}

    def scan_comp(name, multiplier, seen):
        if name in seen:
            return
        seen = seen | {name}
        for s in comps.get(name, []):
            m = re.search(r"=\s+[^=]*?\b"
                          r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                          r"collective-permute)\b", s)
            if m and "-done" not in s.split("=")[0]:
                kind = m.group(1)
                b = _first_shape_bytes(s)
                breakdown[kind]["count"] += multiplier
                breakdown[kind]["bytes"] += b * COLLECTIVE_FACTORS[kind] * multiplier
            w = re.search(r"while\(.*body=%?([\w.\-]+)", s)
            if w:
                body = w.group(1)
                scan_comp(body, multiplier * body_trip.get(body, 1), seen)
            # descend into fusions/calls that might wrap collectives
            c = re.search(r"(?:fusion|call)\(.*(?:calls|to_apply)=%?([\w.\-]+)", s)
            if c:
                scan_comp(c.group(1), multiplier, seen)

    entry = next((n for n in comps if n.startswith("main")), None)
    if entry is None:
        entry = max(comps, key=lambda n: len(comps[n]), default=None)
    if entry is not None:
        scan_comp(entry, 1, frozenset())
    total = sum(v["bytes"] for v in breakdown.values())
    return total, breakdown


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) for train;
    2·N·D for prefill; 2·N per token for decode."""
    from repro.configs.registry import get_config, get_shape
    cfg = get_config(arch)
    sh = get_shape(shape_name)
    sds, _ = speclib.init_specs(cfg, 16)
    n_total = sum(x.size for x in jax.tree.leaves(sds))
    if cfg.moe.enabled:
        e = cfg.moe
        per_layer_routed = 3 * cfg.d_model * e.d_ff_expert
        n_active = (n_total
                    - cfg.num_layers * e.num_experts * per_layer_routed
                    + cfg.num_layers * e.top_k * per_layer_routed)
    else:
        n_active = n_total
    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    factor = 6 if sh.kind == "train" else 2
    return factor * n_active * tokens, n_active


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            head_gather: bool = False, local_steps: int = 1,
            setup_override=None):
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    fed = FederationConfig()
    kw = {}
    if INPUT_SHAPES[shape_name].kind == "train":
        kw = {"head_gather": head_gather, "local_steps": local_steps}
    setup = setup_override or speclib.setup_for
    fn, args, in_sh, out_sh, donate = setup(arch, shape_name, mesh, fed, **kw)

    t0 = time.monotonic()
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    class _NoMem:
        temp_size_in_bytes = argument_size_in_bytes = 0
        output_size_in_bytes = alias_size_in_bytes = 0

    mem = compiled.memory_analysis() or _NoMem()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll_total, coll_breakdown = collective_bytes(hlo)

    n_dev = mesh.devices.size
    flops_total = float(cost.get("flops", 0.0))
    bytes_total = float(cost.get("bytes accessed", 0.0))
    # cost_analysis of an SPMD module reports per-partition numbers
    compute_s = flops_total / meshlib.PEAK_FLOPS_BF16
    memory_s = bytes_total / meshlib.HBM_BW
    collective_s = coll_total / meshlib.ICI_BW

    mf, n_active = model_flops(arch, shape_name)
    useful = mf / (flops_total * n_dev) if flops_total else 0.0

    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "flops_per_device": flops_total,
        "bytes_per_device": bytes_total,
        "collective_bytes_per_device": coll_total,
        "collective_breakdown": {k: v for k, v in coll_breakdown.items()
                                 if v["count"]},
        **terms,
        "dominant": dominant,
        "model_flops": mf,
        "params_active": n_active,
        "useful_flops_ratio": useful,
        "peak_memory_per_device_gb":
            float(getattr(mem, "temp_size_in_bytes", 0)
                  + getattr(mem, "argument_size_in_bytes", 0)
                  + getattr(mem, "output_size_in_bytes", 0)
                  - getattr(mem, "alias_size_in_bytes", 0)) / 2**30,
        "temp_gb": float(getattr(mem, "temp_size_in_bytes", 0)) / 2**30,
        "args_gb": float(getattr(mem, "argument_size_in_bytes", 0)) / 2**30,
        "lower_s": t_lower, "compile_s": t_compile,
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--head-gather", action="store_true",
                    help="paper-faithful cluster-head gather aggregation")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--json", default="")
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape)]

    results, failures = [], []
    for a, s in combos:
        ok, reason = applicable(a, s)
        if not ok:
            print(f"SKIP  {a:18s} {s:12s} {reason}")
            results.append({"arch": a, "shape": s, "skipped": reason})
            continue
        try:
            r = run_one(a, s, multi_pod=args.multi_pod,
                        head_gather=args.head_gather,
                        local_steps=args.local_steps)
            results.append(r)
            print(f"OK    {a:18s} {s:12s} mesh={r['mesh']} "
                  f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                  f"coll={r['collective_s']:.4f}s dom={r['dominant']:10s} "
                  f"mem/dev={r['peak_memory_per_device_gb']:.2f}GiB "
                  f"compile={r['compile_s']:.0f}s")
            sys.stdout.flush()
        except Exception as e:
            failures.append((a, s, repr(e)))
            print(f"FAIL  {a:18s} {s:12s} {e!r}")
            traceback.print_exc()
            sys.stdout.flush()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILURES"); sys.exit(1)
    print("\nALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()

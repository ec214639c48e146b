"""The moonlight-16b-a3b cell: its run loop at CPU size (4 silos, small
widths), its two readers on a synthetic trace whose intervals are known,
and its configuration's sizes and FLOP counts at the published widths
(shapes only, nothing is allocated)."""
import json
import pathlib
import types

import jax
import numpy as np
import pytest

from chipbench import checks, harness, traffic, tracing
from chipbench.tracing import Event, Trace
from chipbench_tiny import drive, make_tiny
from test_chipbench_drive_sync import KEYS

CELL = "moonlight-silo-w4-s8k"
HOME = pathlib.Path(harness.__file__).resolve().parent
SMALL_CONFIG = dict(num_hidden_layers=3, hidden_size=64,
                    num_attention_heads=4, num_key_value_heads=4,
                    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, intermediate_size=128,
                    moe_intermediate_size=32, router_experts=8,
                    n_routed_experts=4, num_experts_per_tok=3,
                    vocab_size=256)
SMALL_TRAFFIC = dict(seq_len=64, trace_rounds=2)
SETTLEMENT = ("unsealed", "chain_invalid", "penalty_mismatch",
              "cid_mismatch")
TRAINING = ("loss_gap", "grad_gap", "delta_gap", "score_gap")


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A copy of the benchmark with the cell at CPU size. Its settlement
    limits are the cell's; its training numbers are reported without a
    limit, since the chip's limits are set at the published widths."""
    bench = make_tiny(tmp_path_factory.mktemp("checkout"))
    home = bench.parent / "chipbench"
    for path, sizes in ((home / "configs" / "moonlight-16b-a3b.json",
                         SMALL_CONFIG),
                        (home / "workloads" / "xsilo-lora-w4-s8k.json",
                         SMALL_TRAFFIC)):
        d = json.loads(path.read_text())
        d.update(sizes)
        if "lora" in d:
            d["lora"] = dict(d["lora"], r=4, lora_alpha=8)
        path.write_text(json.dumps(d))
    lim_path = home / "limits" / f"{CELL}.json"
    limits = json.loads(lim_path.read_text())
    limits.update({k: {"limit": 1e9} for k in TRAINING})
    lim_path.write_text(json.dumps(limits))
    return bench


def test_result_line(small):
    out, logs = drive(small, CELL)
    assert list(out) == KEYS
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "round_latency_p95_s",
                                   "setup_s"}
    for name in SETTLEMENT:
        assert out["checks"][name][0] == 0
    assert all(np.isfinite(out["checks"][k][0]) for k in TRAINING)
    assert any(line.endswith("compilations in the window: 0")
               for line in logs)


def test_traced_run(small):
    out, _ = drive(small, CELL, trace=True)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert out["correct"] is True
    # a CPU trace has no TPU plane: both device readers stay silent
    assert out["metrics"] == {}


def test_control_fails_the_committed_limits(small):
    """The configuration's control (8-bit float matmuls, bf16 storage)
    at CPU size, judged by the cell's committed training limits, fails
    them; the reference against itself passes."""
    cell = harness.load_cell(CELL, small)
    seed = 2**32 + 17
    rows = traffic.rounds(cell.config, cell.traffic, seed,
                          cell.traffic["warmup_rounds"])
    masks = checks.expected_masks(cell, seed, len(rows))
    init = checks.init_params(cell, seed)
    ref = checks.run_reference(cell, seed, rows, masks)
    limits = {k: v for k, v in harness.load_json(
        HOME / "limits" / f"{CELL}.json").items() if k in TRAINING}
    same = checks.compare(ref, checks.run_reference(cell, seed, rows, masks),
                          init)
    assert checks.judge(same, limits)["correct"] is True, same
    ctl = checks.run_reference(cell, seed, rows, masks,
                               **checks.control(cell.config))
    numbers = checks.compare(ref, ctl, init)
    assert checks.judge(numbers, limits)["correct"] is False, numbers


def _half_sequence(cell):
    """The cell with each silo's loss over the first half of its sequence:
    the harness's own half-batch fault halves rows, and a silo here holds
    one row, so that fault leaves it nothing."""
    whole = cell.model.loss

    def loss(c):
        f = whole(c)

        def half(p, rows, key, qm):
            return f(p, jax.tree.map(lambda x: x[:, :x.shape[1] // 2], rows),
                     key, qm)
        return half
    return cell._replace(model=types.SimpleNamespace(init=cell.model.init,
                                                     loss=loss))


def _lr_times_two(cell):
    train = dict(cell.config["train"], lr=2 * cell.config["train"]["lr"])
    return cell._replace(config=dict(cell.config, train=train))


@pytest.mark.parametrize("plant,fails", [(_half_sequence, "grad_gap"),
                                         (_lr_times_two, "delta_gap")],
                         ids=["half_sequence", "lr_x2"])
def test_fault_fails_the_committed_limits(small, plant, fails):
    """A fault planted in the reference at CPU size fails the cell's
    committed training limits, on the number named: silos training on
    half of each sequence, or a step at twice the learning rate."""
    cell = harness.load_cell(CELL, small)
    seed = 2**32 + 17
    rows = traffic.rounds(cell.config, cell.traffic, seed,
                          cell.traffic["warmup_rounds"])
    masks = checks.expected_masks(cell, seed, len(rows))
    limits = {k: v for k, v in harness.load_json(
        HOME / "limits" / f"{CELL}.json").items() if k in TRAINING}
    numbers = checks.compare(
        checks.run_reference(cell, seed, rows, masks),
        checks.run_reference(plant(cell), seed, rows, masks),
        checks.init_params(cell, seed))
    assert numbers[fails] > limits[fails]["limit"], numbers


# -- the readers on known intervals --------------------------------------------

E = Event
# window [0, 10] s; the round program runs [1, 5] and [6, 9]; the grouped
# products 0.5 s of it in all
OPS = [E("%fusion.7 = bf16[8]{0} fusion()", 1, 2),
       E("%ragged-dot-none.1 = bf16[96,64]{1,0} custom-call()", 2, 2.2),
       E("%ragged-dot-metadata = (s32[9]) custom-call()", 2.2, 2.25),
       E("%ragged-dot-none = bf16[96,64]{1,0} custom-call()", 6, 6.25)]
MODULES = [E("jit_fl_round(9)", 1, 5), E("jit_fl_round(9)", 6, 9)]
TR = Trace({0: OPS}, {0: MODULES}, [E(tracing.ROUND_SPAN, 0, 10)],
           (0.0, 10.0))


class _Rec:
    def __init__(self, counters=None):
        self.settled, self.participation = True, None
        if counters is not None:
            self.counters = counters


def _read(name, recs, trace=TR):
    cell = harness.load_cell(CELL)
    run = harness.Run(
        cell=cell, records=recs, trace=trace, traced_rounds=recs,
        peaks={"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12},
        flops_per_sample=1000, samples_per_worker=8192,
        update_bytes=0, dim=0)
    return harness.read_metric(cell, name, run)


RECS = [_Rec({"moe.pairs_held": 1000.0, "moe.max_expert_load": 200.0}),
        _Rec({"moe.pairs_held": 3000.0, "moe.max_expert_load": 500.0})]


def test_step_mfu_on_known_intervals():
    # 2 rounds x 4 silos x 8,192 tokens x 1,000 FLOPs over 7 s at 1e15
    assert _read("step_mfu.moonlight", RECS) == pytest.approx(
        100 * 2 * 4 * 8192 * 1000 / (7 * 1e15))


def test_moe_roofline_on_known_intervals():
    # 4,000 pairs x 3 passes x 2 * 3 * 2048 * 1408 FLOPs over 0.5 s
    want = 100 * 4000 * 3 * 2 * 3 * 2048 * 1408 / (0.5 * 1e15)
    assert _read("moe_roofline.moonlight", RECS) == pytest.approx(want)


@pytest.mark.parametrize("name", ["step_mfu.moonlight",
                                  "moe_roofline.moonlight"])
def test_readers_silent_without_their_sources(name):
    assert _read(name, RECS, trace=None) is None
    no_ops = Trace({0: []}, {0: []}, [], (0.0, 10.0))
    assert _read(name, RECS, trace=no_ops) is None
    if name.startswith("moe_roofline"):
        # a program without the counter (one that holds no expert share)
        assert _read(name, [_Rec(), _Rec()]) is None


# -- the configuration at its published widths ----------------------------------


def _config():
    path = HOME / "configs" / "moonlight-16b-a3b.json"
    return harness.load_json(path), harness.load_module(
        path.with_suffix(".py"))


def test_weights_match_program_params():
    from repro.configs.base import ModelConfig
    from repro.models import api
    c, m = _config()
    key = jax.random.PRNGKey(0)
    cfg = ModelConfig(**m.program(c)["model"])
    shapes = [jax.eval_shape(f, key) for f in (
        lambda k: m.init(c, k), lambda k: api.init(cfg, k, tp=1)[0])]
    assert jax.tree.map(lambda x: (x.shape, x.dtype), shapes[0]) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), shapes[1])
    assert sum(x.size for x in jax.tree.leaves(shapes[0])) == 1_315_840


def test_param_count_by_hand():
    c, m = _config()
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    dense = attn + 3 * 2048 * 11264 + 2 * 2048
    moe = attn + 8 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64 + 64 \
        + 2 * 2048
    frozen = 2 * 20480 * 2048 + dense + 4 * moe + 2048
    assert frozen == 568_484_608
    assert m.param_count(c) == frozen + 1_315_840


def test_flops_by_hand():
    c, m = _config()
    t = {"seq_len": 8192, "protocol": {"w_loss": 0.2}}
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    moe = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    base = 5 * attn + 3 * 2048 * 11264 + 4 * moe + 2048 * 20480
    causal = 3 * 5 * 2 * 4096 * 16 * (128 + 64 + 128)
    assert m.flops_per_sample(c, t) == int(4 * base + 6 * 1_315_840 + causal)
    assert m.held_expert_flops(c, t) == 3 * 2 * 3 * 2048 * 1408

"""The check that decides ``correct``: every number compared, and its
limit from ``limits/<cell>.json``.

Settlement (every round of the run, exact):

- ``unsealed``: rounds whose block never sealed;
- ``chain_invalid``: 1 when the ledger fails its deep verification;
- ``value_drift``: stake, deposit and payouts not conserved (relative);
- ``penalty_mismatch``: (round, worker) pairs whose on-chain penalty is
  not what the round's own scores, the stake left and the job's rules
  give;
- ``cid_mismatch``: rounds whose model cid is not the hash of the
  published blob, or whose blob is not the round's global params;
- ``cohort_mismatch`` (event-driven cells): (round, worker) pairs whose
  arrival differs from the arrival model's (``arrivals.py``).

Training (the first ``warmup_rounds`` rounds, against ``reference.py``):

- ``loss_gap``: worst worker's relative gap of its post-step loss;
- ``grad_gap``: worst leaf's gap between the norms of the first
  gradient, as the optimizer state holds it after one step;
- ``delta_gap``: worst leaf's gap between the norms of the global
  params' change over the rounds;
- ``score_gap``: largest gap of a trust score.

Leaf gaps are over the larger of the leaf's reference norm and the
median leaf's. Leaves whose reference first gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of ``grad_gap`` and ``delta_gap``.
"""
from __future__ import annotations

import hashlib
from typing import NamedTuple, Optional

import numpy as np

from chipbench import arrivals, reference as R, traffic as gen

SMALL_LEAF = 1e-3


class ProgramOut(NamedTuple):
    """What the program (or the reference in its place) produced in the
    compared rounds."""
    losses: np.ndarray             # (rounds, W) post-step losses
    scores: np.ndarray             # (rounds, W)
    grad_norms: dict               # {leaf path: norm} of the first gradient
    params: object                 # global params after the rounds


def grad_spec(config: dict) -> tuple:
    """Where the program's optimizer state keeps the first gradient, and
    the factor it was scaled by: SGD's momentum buffer holds
    (1 - dampening) g, AdamW's first moment (1 - b1) g."""
    t = config["train"]
    if t["optimizer"] == "sgd":
        return ("momentum", 1.0 - t["dampening"])
    return ("m", 1.0 - t["adam_b1"])


def first_grad_norms(opt_state, spec: tuple):
    """{leaf path: norm} of the first gradient, read from the program's
    optimizer state after one step (``spec`` from ``grad_spec``)."""
    import jax
    import jax.numpy as jnp
    name, factor = spec
    flat, _ = jax.tree_util.tree_flatten_with_path(opt_state[name])
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) / factor for p, x in flat}


def dim(params) -> int:
    import jax
    return int(sum(x.size for x in jax.tree.leaves(params)))


def update_bytes(params, W: int) -> int:
    import jax
    return int(sum(W * x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(params)))


def _protocol(traffic: dict) -> R.Protocol:
    p = traffic["protocol"]
    return R.Protocol(w_cosine=p["w_cosine"], w_norm=p["w_norm"],
                      w_loss=p["w_loss"],
                      trust_threshold=p["trust_threshold"],
                      soft_trust_weighting=p["soft_trust_weighting"],
                      staleness_alpha=p["staleness_alpha"])


def _optim(config: dict) -> R.Optim:
    t = config["train"]
    if t["optimizer"] == "sgd":
        return R.Optim("sgd", t["lr"], momentum=t["momentum"],
                       dampening=t["dampening"], nesterov=t["nesterov"],
                       weight_decay=t["weight_decay"],
                       grad_clip=t["grad_clip"])
    return R.Optim("adamw", t["lr"], b1=t["adam_b1"], b2=t["adam_b2"],
                   eps=t["adam_eps"], weight_decay=t["weight_decay"],
                   grad_clip=t["grad_clip"])


def staleness_from(masks: list) -> list:
    """Pre-round staleness of each round from the arrival masks: a worker
    that arrives starts again at 0, every other worker ages by one."""
    out, s = [], None
    for m in masks:
        if m is None:
            out.append(None)
            continue
        s = np.zeros(len(m), np.int64) if s is None else s
        out.append(s.copy())
        s = np.where(np.asarray(m) > 0, 0, s + 1)
    return out


def expected_masks(cell, seed: int, rounds: int) -> list:
    """Each round's arrivals as the traffic's arrival model gives them;
    ``None`` for every round of a synchronous cell."""
    t = cell.traffic
    if t["mode"] == "sync":
        return [None] * rounds
    return arrivals.cohorts(gen.arrival_profiles(t, seed),
                            seed=gen.derive(seed, gen.TASK),
                            task_id=t["protocol"].get("task_id", "task-0"),
                            buffer_size=t["buffer_size"], events=rounds)


def settlement(prog, recs: list, unsealed: int, params_at: object,
               at_round: int, masks: list) -> dict:
    """Settlement numbers over every round ``recs`` of one run.
    ``params_at`` is the global params after round ``at_round`` − 1,
    whose published blob must hold them; ``masks`` are the rounds'
    expected arrivals."""
    import jax
    task = prog.task
    pr = prog.proto
    t = task.fed
    numbers = {"unsealed": unsealed}
    numbers["chain_invalid"] = 0 if pr.ledger.verify_chain(deep=True) else 1
    total = task.contract.total_value()
    numbers["value_drift"] = abs(total - prog.total0) / prog.total0
    stake = np.full(task.W, float(t.worker_stake))
    full = t.worker_stake * t.penalty_pct / 100.0
    alpha = t.staleness_alpha if t.async_mode else 0.0
    stale = staleness_from(masks)
    numbers["cohort_mismatch"] = sum(
        int(np.sum(np.asarray(r.participation) != m)) for r, m in
        zip(recs, masks) if m is not None)
    bad_pen = 0
    for rec, st in zip(recs, stale):
        cap = full if st is None else full * (1.0 + st) ** (-alpha)
        bad = np.asarray(rec.scores) < t.trust_threshold
        want = np.where(bad, np.minimum(cap, stake), 0.0)
        stake = stake - want
        got = np.asarray(rec.penalties, np.float64)
        bad_pen += int(np.sum(np.abs(got - want) > 1e-9)) if rec.settled \
            else task.W
        if st is not None and not np.array_equal(st, rec.staleness):
            bad_pen += task.W
    numbers["penalty_mismatch"] = bad_pen
    bad_cid = 0
    for rec in recs:
        if not rec.model_cid or hashlib.sha256(
                pr.ipfs.read_blob(rec.model_cid)).hexdigest() \
                != rec.model_cid:
            bad_cid += 1
    for rec, params in ((recs[at_round - 1], params_at),
                        (recs[-1], task.global_params)):
        if rec.model_cid:
            got = pr.ipfs.get_leaves(rec.model_cid)
            want = [np.asarray(x, np.float32)
                    for x in jax.device_get(jax.tree.leaves(params))]
            if len(got) != len(want) or not all(
                    np.array_equal(a, b) for a, b in zip(got, want)):
                bad_cid += 1
    numbers["cid_mismatch"] = bad_cid
    return numbers


def task_keys(seed: int, rounds: int, W: int):
    """The per-worker keys of each round, as the task derives them from
    its seed: one split for its weights, then one per round, then one
    per worker."""
    import jax
    rng = jax.random.PRNGKey(gen.derive(seed, gen.TASK))
    _, rng = jax.random.split(rng)
    keys = []
    for _ in range(rounds):
        rng, rkey = jax.random.split(rng)
        keys.append(jax.random.split(rkey, W))
    return keys


def run_reference(cell, seed: int, rows: list, masks: list, *,
                  precision: str = "highest", quant: Optional[str] = None,
                  store: Optional[str] = None, fault: Optional[str] = None
                  ) -> ProgramOut:
    """The reference over the compared rounds. For the control,
    ``precision`` is its matmul precision and ``quant`` and ``store`` its
    compute and storage dtypes (see ``reference.make_round``); ``fault``
    plants a fault in it: ``half``
    (each worker's loss over the first half of its rows only) or
    ``altered`` (worker 0's reported loss and score nudged where they
    are produced)."""
    import jax
    import jax.numpy as jnp

    config, t = cell.config, cell.traffic
    W = gen.num_workers(t)
    opt, pr = _optim(config), _protocol(t)
    q, st = R.quantizer(quant), R.quantizer(store)
    model_loss = cell.model.loss(config)
    if fault == "half":
        def loss_fn(p, rows_w, key, qm):
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], rows_w)
            return model_loss(p, half, key, qm)
    else:
        loss_fn = model_loss
    stale = staleness_from(masks)
    with jax.default_matmul_precision(precision):
        # params, optimizer state and pending buffers are replaced every
        # round: donating them keeps one copy on the chip
        fl_round = jax.jit(R.make_round(
            loss_fn, opt, pr, quant=q, store=st,
            chunk=t["reference_workers_at_once"],
            score_loss_post=pr.w_loss > 0), donate_argnums=(0, 1, 6))
        key = jax.random.PRNGKey(gen.derive(seed, gen.WEIGHTS))
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                              jax.jit(lambda k: cell.model.init(config, k))(
                                  key))
        params = jax.tree.map(st, params)
        opt_w = jax.tree.map(lambda x: jnp.broadcast_to(x, (W,) + x.shape),
                             R.opt_init(params, opt))
        pending = None if masks[0] is None else jax.tree.map(
            lambda x: jnp.zeros((W,) + x.shape, jnp.float32), params)
        losses, scores, grad_norms = [], [], None
        for r, (batch, keys) in enumerate(zip(rows, task_keys(
                seed, len(rows), W))):
            m = None if masks[r] is None else jnp.asarray(masks[r])
            s = None if stale[r] is None else jnp.asarray(stale[r])
            out = fl_round(params, opt_w, jax.tree.map(jnp.asarray, batch),
                           keys, m, s, pending)
            params, opt_w, pending = out.params, out.opt, out.pending
            lp = np.asarray(out.loss_post if pr.w_loss > 0 else out.loss_pre)
            sc = np.asarray(out.scores)
            if fault == "altered":
                lp, sc = lp.copy(), sc.copy()
                lp[0] *= 1.01
                sc[0] = min(1.0, sc[0] + 0.05)
            losses.append(lp)
            scores.append(sc)
            if r == 0:
                grad_norms = R.leaf_norms(R.first_grad(opt_w, opt))
        return ProgramOut(np.stack(losses), np.stack(scores), grad_norms,
                          jax.device_get(params))


def compare(ref: ProgramOut, prog: ProgramOut, init_params) -> dict:
    """The training numbers of ``prog`` against ``ref``; both started from
    ``init_params``."""
    import jax
    import jax.numpy as jnp
    small = {k for k, v in ref.grad_norms.items()
             if v < SMALL_LEAF * np.median(list(ref.grad_norms.values()))}

    def change(params):
        return jax.tree.map(lambda a, b: jnp.asarray(a, jnp.float32)
                            - jnp.asarray(b, jnp.float32),
                            params, init_params)
    gp = {k: float(v) for k, v in prog.grad_norms.items()}
    return {
        "loss_gap": float(np.max(np.abs(prog.losses - ref.losses)
                                 / np.maximum(np.abs(ref.losses), 1e-6))),
        "grad_gap": R.worst_leaf_gap(gp, ref.grad_norms, small),
        "delta_gap": R.worst_leaf_gap(R.leaf_norms(change(prog.params)),
                                      R.leaf_norms(change(ref.params)),
                                      small),
        "score_gap": float(np.max(np.abs(prog.scores - ref.scores))),
    }


# the control for each precision a configuration states: the step below
# it that would tempt a later change. float32 at ``highest`` goes to three
# bfloat16 passes (``high``); other float32 to bfloat16 throughout;
# bfloat16 to 8-bit float matmuls with the params kept in bfloat16, as an
# fp8 training path does
CONTROLS = {("float32", "highest"): {"precision": "high"},
            ("float32", None): {"quant": "bfloat16", "store": "bfloat16"},
            ("bfloat16", None): {"quant": "float8_e4m3fn",
                                 "store": "bfloat16"}}


def control(config: dict) -> dict:
    """``run_reference`` arguments of the configuration's control."""
    dtype = config.get("torch_dtype") or config["dtype"]
    return CONTROLS[dtype, config.get("matmul_precision")]


def init_params(cell, seed: int):
    import jax
    key = jax.random.PRNGKey(gen.derive(seed, gen.WEIGHTS))
    return jax.device_get(jax.jit(
        lambda k: cell.model.init(cell.config, k))(key))


def against_reference(cell, seed: int, rows: list, prog: ProgramOut) -> dict:
    ref = run_reference(cell, seed, rows,
                        expected_masks(cell, seed, len(rows)))
    return compare(ref, prog, init_params(cell, seed))


def judge(numbers: dict, limits: dict) -> dict:
    """Each limited number against its limit; a number that is missing
    or not finite fails."""
    out, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= lim["limit"]
        ok &= bool(good)
        out[name] = [None if v is None else float(v), lim["limit"]]
    return {"correct": ok, "checks": out}

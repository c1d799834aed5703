"""The sequence cell's comparison with the float32 oracle
(``harness/oracle_seq.py``), run after the window and outside every
timing, at the configuration's own widths. Three parts:

- **a step's tensors**: the first microbatch of a seeded step batch
  through the function the step differentiates
  (``seqrec.sampled_softmax_terms``) against the oracle: hidden states,
  router logits and top-k sets, positive and negative logits;
- **the step itself**: the TIMED program (``seqrec._train_step_jit``,
  the same jitted object and shapes as the window's steps) run once on
  that batch from a known state, against the oracle's ``jax.grad``
  summed over the microbatches and a plain Adam: the loss it reports,
  the targets it counts, and, leaf by leaf, the change of the
  parameters. The known state is the trained parameters, a zero first
  moment, ``ADAM_T0`` steps behind it, and a second moment that holds
  each leaf's mean squared ORACLE gradient: Adam's update is then close
  to linear in the gradient, so a gradient wrong in scale or in part (a
  microbatch left out, a wrong weight gradient out of ``tgmm``) shows
  in proportion, where a first step from zero moments would show only
  its signs;
- **served scores**: the trained model through ``DeviceTopK`` against
  ``oracle user vector . output table``, each vector from the user's
  own history alone (no packing).

``operands`` (a dtype name) replaces the system by the ORACLE with its
matmul operands rounded through that dtype: the control that gives each
limit in ``oracle_seq`` its second reading. ``python3 -m
benchmark.harness.seq_check --operands float8_e4m3fn,bfloat16`` runs
set-up, one ``train()`` call, the system's comparison and those
controls on the chip.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.harness import oracle_seq
from benchmark.models import sequentialrec as seq_model

# queries a block of the oracle's attention takes against the whole row
Q_BLOCK = 1024
# optimizer steps the checked step's state has behind it
ADAM_T0 = 10.0

LIMITS = (("top8_differs_share", oracle_seq.TOP8_DIFFERS_MAX),
          ("router_rel_err", oracle_seq.ROUTER_RTOL),
          ("hidden_rel_err", oracle_seq.HIDDEN_RTOL),
          ("pos_logit_rel_err", oracle_seq.POS_LOGIT_RTOL),
          ("neg_logit_rel_err", oracle_seq.NEG_LOGIT_RTOL),
          ("step_loss_rel_err", oracle_seq.LOSS_RTOL),
          ("step_update_rel_err", oracle_seq.UPDATE_RTOL),
          ("served_rel_err", oracle_seq.SERVED_RTOL),
          ("served_median_rel_err", oracle_seq.SERVED_MEDIAN_RTOL))


def _device(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.asarray, tree)


def _zeros(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def step_batch(rows, params, seed: int, n_items: int):
    """A seeded step batch as the trainer shapes it: ``[n_micro, rows,
    L]`` ids, segments and positions (distinct rows), and the shared
    negatives."""
    rng = np.random.default_rng([int(seed), 11])
    bs = min(int(params.batch_size), len(rows))
    micro = int(params.micro_rows)
    micro = micro if 0 < micro < bs and bs % micro == 0 else bs
    sel = rng.choice(len(rows), size=bs, replace=False)
    shape = (bs // micro, micro, rows.seq_len)
    negs = rng.integers(0, n_items,
                        size=int(params.n_negatives)).astype(np.int32)
    return tuple(x[sel].reshape(shape)
                 for x in (rows.ids, rows.seg, rows.pos)), negs


def _oracle_step(theta, batch, negs, cfg, operands):
    """The oracle's loss of the step, the first microbatch's terms (on
    the host) and the step's gradients (a device tree), a microbatch at
    a time."""
    import jax
    import jax.numpy as jnp

    ids, seg, pos = batch
    n_micro = ids.shape[0]
    dtype = None if operands is None else jnp.dtype(operands)
    block = min(Q_BLOCK, ids.shape[-1])

    def micro_grad(theta, acc, ids, seg, pos, negs, n_targets):
        with jax.default_matmul_precision("highest"):
            (loss, terms), g = jax.value_and_grad(
                oracle_seq.micro_loss, has_aux=True)(
                    theta, ids, seg, pos, negs, n_targets, n_micro, cfg,
                    block, dtype)
        return loss, terms, jax.tree_util.tree_map(jnp.add, acc, g)

    run = jax.jit(micro_grad, donate_argnums=1)
    n_targets = max(float(np.count_nonzero(
        (seg[..., :-1] == seg[..., 1:]) & (seg[..., :-1] != 0))), 1.0)
    acc, loss, first = _zeros(theta), 0.0, None
    for i in range(n_micro):
        part, terms, acc = run(theta, acc, ids[i], seg[i], pos[i], negs,
                               n_targets)
        loss += float(part)
        if first is None:
            first = jax.device_get({
                "hidden": terms["hidden"], "pos_logit": terms["pos_logit"],
                "neg_logit": terms["neg_logit"],
                "logits": terms["routed"][-1]["logits"],
                "top": terms["routed"][-1]["chosen"]})
            first["top"] = oracle_seq.top_sets(first["top"])
        del terms
    return loss, n_targets, first, acc


def _adam_change(grads, second, lr: float):
    """What a plain Adam subtracts from each leaf, from a zero first
    moment and the second moments ``second`` (a scalar a leaf) after
    ``ADAM_T0`` steps."""
    import jax

    def change(g, v0):
        return oracle_seq.adam_update(0.0, v0, ADAM_T0, g, lr)[2]

    return jax.jit(lambda g, v: jax.tree_util.tree_map(change, g, v))(
        grads, second)


def _system_tensors(theta, batch, negs, spec):
    """The first microbatch through the function the step
    differentiates."""
    import jax

    from predictionio_tpu.ops import seqrec

    got = jax.jit(functools.partial(seqrec.sampled_softmax_terms,
                                    spec=spec))(
        theta, batch[0][0], batch[1][0], batch[2][0], negs)
    out = jax.device_get({
        "hidden": got["hidden"], "pos_logit": got["pos_logit"],
        "neg_logit": got["neg_logit"],
        "logits": got["stats"][-1]["logits"],
        "top": got["stats"][-1]["experts"]})
    out["top"] = np.sort(out["top"], axis=1)
    return out


def _system_step(params, theta, second, batch, negs):
    """The timed step program, once, from the known state; ``theta``
    is donated. Returns the new parameters (device) and what the step
    reports."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqrec

    run = seqrec._train_step_jit(seqrec.block_spec(params),
                                 float(params.learning_rate),
                                 float(params.l2))
    v = jax.tree_util.tree_map(
        lambda x, v0: jnp.full(x.shape, v0, jnp.float32), theta, second)
    state = (theta, _zeros(theta), v, jnp.full((), ADAM_T0, jnp.float32))
    state, out = run(state, *batch, negs)
    return state[0], jax.device_get(out)


def _tensor_readings(got, want, seg0) -> Dict[str, float]:
    real = np.asarray(seg0).reshape(-1) != 0
    same = (got["top"] == want["top"]).all(axis=1)
    D = want["hidden"].shape[-1]
    # a position's logits are its hidden state's: the same tokens
    ctx_same = (real & same).reshape(np.asarray(seg0).shape)[:, :-1]
    return {
        "top8_differs_share": float(1.0 - same[real].mean()),
        "router_rel_err": oracle_seq.rel_err(got["logits"], want["logits"],
                                             real),
        "hidden_rel_err": oracle_seq.rel_err(
            got["hidden"].reshape(-1, D), want["hidden"].reshape(-1, D),
            real & same),
        "pos_logit_rel_err": oracle_seq.rel_err(
            got["pos_logit"], want["pos_logit"], ctx_same),
        "neg_logit_rel_err": oracle_seq.rel_err(
            got["neg_logit"], want["neg_logit"], ctx_same)}


def check_step(params, rows, n_items: int, theta_host, seed: int,
               operands: Optional[str] = None) -> Dict[str, Any]:
    """Readings of the first two parts; ``theta_host`` is the trained
    model's parameters on the host."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import seqrec

    cfg = seq_model.oracle_cfg(params)
    lr = float(params.learning_rate)
    batch, negs = step_batch(rows, params, seed, n_items)
    theta = _device(theta_host)
    want_loss, n_targets, want, grads = _oracle_step(theta, batch, negs,
                                                     cfg, None)
    second = jax.jit(lambda g: jax.tree_util.tree_map(
        lambda x: jnp.mean(jnp.square(x)), g))(grads)
    want_change = jax.device_get(_adam_change(grads, second, lr))
    del grads
    out: Dict[str, Any] = {}
    if operands is None:
        got = _system_tensors(theta, batch, negs, seqrec.block_spec(params))
        new, step = _system_step(params, theta, second, batch, negs)
        got_loss = float(step["loss"])
        out["step_targets"] = [float(step["targets"]), n_targets]
        got_change = jax.jit(
            lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b),
            donate_argnums=0)(_device(theta_host), new)
        del new
    else:
        got_loss, _, got, grads = _oracle_step(theta, batch, negs, cfg,
                                               operands)
        got_change = _adam_change(grads, second, lr)
        del grads
    del theta
    errs = jax.device_get(jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.linalg.norm((x - y).reshape(-1))
        / jnp.maximum(jnp.linalg.norm(y.reshape(-1)), 1e-30), a, b))(
            got_change, _device(want_change)))
    worst = max(errs, key=lambda k: float(errs[k]))
    out.update(_tensor_readings(got, want, batch[1][0]))
    out.update(step_loss=got_loss, step_loss_oracle=want_loss,
               step_loss_rel_err=abs(got_loss - want_loss) / abs(want_loss),
               step_update_rel_err=float(errs[worst]),
               step_update_worst_leaf=worst,
               step_update_by_leaf={k: float(f"{float(v):.3g}")
                                    for k, v in errs.items()})
    return out


def check_served(params, pd, model, events, seed: int, n_users: int,
                 predict, operands: Optional[str] = None) -> Dict[str, Any]:
    """``predict(user label) -> [(item label, score)]`` is the trained
    model's serving path. A seeded sample of users' served scores
    against ``oracle vector . output table``."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.seqrec import output_table

    cfg = seq_model.oracle_cfg(params)
    rows_of, cols = events
    rng = np.random.default_rng([int(seed), 12])
    # users of at most one short row of history, so that the sample
    # costs seconds (the packed rows of the step hold the long ones)
    L = min(512, params.max_seq_len)
    counts = np.bincount(rows_of, minlength=len(pd.user_map))
    pool = np.flatnonzero((counts > 1) & (counts <= L))
    users = rng.choice(pool, size=min(n_users, len(pool)), replace=False)
    ids = np.zeros((len(users), L), np.int32)
    seg = np.zeros((len(users), L), np.int32)
    starts = np.searchsorted(rows_of, users)
    for i, (u, a) in enumerate(zip(users.tolist(), starts.tolist())):
        k = int(counts[u])
        # the model's own indices of this user's items, in event order
        ids[i, :k] = [pd.item_map[f"i{c}"] for c in cols[a:a + k]]
        seg[i, :k] = 1
    pos = np.broadcast_to(np.arange(L, dtype=np.int32), ids.shape)
    theta = _device(model.theta)
    table = np.asarray(output_table(model.theta))[:len(pd.item_map)]
    _, _, vecs = oracle_seq.user_vectors(theta, ids, seg, pos, cfg)
    if operands is not None:
        low = jnp.dtype(operands)
        _, _, low_vecs = oracle_seq.user_vectors(theta, ids, seg, pos, cfg,
                                                 operands=low)
        low_table = np.asarray(jnp.asarray(table).astype(low)
                               .astype(jnp.float32))
        low_vecs = np.asarray(jnp.asarray(low_vecs).astype(low)
                              .astype(jnp.float32))
    worst, nothing = [], []
    for i, u in enumerate(users.tolist()):
        want_all = table @ vecs[i]
        if operands is None:
            served = [(pd.item_map[item], score)
                      for item, score in predict(f"u{u}")]
        else:
            got_all = low_table @ low_vecs[i]
            served = [(j, got_all[j]) for j in np.argsort(-got_all)[:10]]
        if not served:
            nothing.append(f"u{u}")
            continue
        scale = np.abs(want_all).max()
        worst.append(max(abs(score - want_all[j]) / scale
                         for j, score in served))
    # the worst user: a vector that is wrong for SOME users (a row's
    # end, a call's padding). The median user: precision, which a few
    # users whose last token took another expert do not decide
    return {"served_rel_err": float(np.max(worst, initial=0.0)),
            "served_median_rel_err": float(np.median(worst or [0.0])),
            "served_nothing": nothing}


def verdict(readings: Dict[str, Any], why: List[str]) -> None:
    """Every reading against its limit; what fails is added to
    ``why``."""
    for name, limit in LIMITS:
        if name in readings and not readings[name] <= limit:
            why.append(f"{name} {readings[name]:.3g} against the float32 "
                       f"oracle (limit {limit})")
    if "step_targets" in readings:
        got, want = readings["step_targets"]
        if got != want:
            why.append(f"the step counted {got:.0f} targets, its batch "
                       f"holds {want:.0f}")
    for user in readings.get("served_nothing", []):
        why.append(f"user {user} was served nothing")


def main(argv=None) -> int:
    """Set-up, one ``train()`` call, then the comparison of the system
    and of the oracle at each of ``--operands`` in the system's place:
    one JSON line of every set of readings."""
    import argparse
    import json
    import tempfile

    from benchmark import run as bench_run
    from benchmark.drivers import seq_train_calls
    from benchmark.harness import cell as cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="seqrec-olmoe-msd.train")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--operands", default="float8_e4m3fn")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.seconds, args.trace = 0.0, 0
    cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    device = bench_run.prepare_process(cell, args.rehearse)
    ctx = bench_run.Context(cell, args, tempfile.mkdtemp(prefix="pio-bench-"))
    cctx, algo, pd, _, events = seq_train_calls.prepare(ctx)
    model = algo.train(cctx, pd)
    line: Dict[str, Any] = {"device": device}
    for operands in [None] + args.operands.split(","):
        readings = seq_train_calls.compare(ctx, algo, pd, model, events,
                                           operands)
        why: List[str] = []
        verdict(readings, why)
        line[operands or "system"] = {"readings": readings, "why": why}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())

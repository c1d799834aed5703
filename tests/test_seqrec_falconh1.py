"""Falcon-H1's block (``falcon_h1``) on the sequence lane: EVERY layer
runs Mamba-2 heads (a constant-size float32 state and a convolution's
tail a session) and attention heads (key and value rows in blocks) side
by side on one normed input, one residual add for both, a dense SwiGLU
behind them; and the session lane that serves it from one SLOT and a
block table a session IN THE SAME LAYERS, under one manager. Everything
at toy widths on the CPU, seeded weights (norm weights and ``D``
perturbed, so that one left out or read plain shows), against the
float32 reference ``ops/falconh1_reference.py``, which advances the
scan one position at a time.

A cache block is 4 rows, a chunk of the chunked form 4 positions and a
prefill chunk 32 tokens, so the sessions below end inside chunks,
cross chunk and block borders inside one query's events and are
prefilled in several chunks.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import falconh1
from predictionio_tpu.ops import falconh1_reference as ref
from predictionio_tpu.ops import seqrec as S
from predictionio_tpu.ops import sessions
from predictionio_tpu.ops.sessions import FalconH1Backbone, SessionTopK

N_ITEMS = 50
MULTIPLIERS = {k: v for k, v in S.FALCON_H1_34B.items() if "multiplier" in k}
TOY = dict(
    block="falcon_h1", rank=32, n_heads=4, n_kv_heads=2, head_dim=16,
    n_layers=3, norm="rmsnorm", norm_eps=1e-5, positions="rope",
    rope_theta=1e11, tied=False, intermediate_size=48, mamba_n_heads=4,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=4, num_steps=0, seeded_weights=True, max_seq_len=128,
    seed=3, **MULTIPLIERS)
PERTURBED = ("ln1_g", "ln2_g", "ln_f_g", "gn_g", "d_skip")
# float32 on the CPU, the lane's chunked prefill against a recurrence
# one position at a time: rounding alone (other orders of the same
# float32 sums through 3 layers). Scores are ``lm_head_multiplier``
# (1/128) small: their spread is 0.007, the largest difference read 3e-8;
# streams, rows and states are O(0.1-1) and read 2e-6 at most
SCORE_ATOL = 2e-6
F32_ATOL = 5e-5


def build(**over):
    params = S.SeqRecParams(**{**TOY, **over})
    theta = S.init_theta(N_ITEMS, params)
    rng = np.random.default_rng(7)
    for k in theta:
        if k.endswith(PERTURBED):
            theta[k] = theta[k] + 0.2 * rng.normal(
                size=theta[k].shape).astype(np.float32)
    return params, theta, cfg_of(falconh1.hyb_spec(params))


def cfg_of(spec, n_items=N_ITEMS):
    return dict(
        n_layers=spec.n_layers, n_heads=spec.n_heads, n_kv=spec.n_kv,
        head_dim=spec.head_dim, ssm_heads=spec.ssm_heads,
        ssm_head_dim=spec.ssm_head_dim, d_state=spec.d_state,
        n_groups=spec.n_groups, conv=spec.conv, norm_eps=spec.norm_eps,
        rope_theta=spec.rope_theta, n_items=n_items, attn_in=spec.attn_in,
        attn_out=spec.attn_out, key_mult=spec.key_mult,
        emb_mult=spec.emb_mult, head_mult=spec.head_mult,
        ssm_in=spec.ssm_in, ssm_mults=spec.ssm_mults, ssm_out=spec.ssm_out,
        mlp_mults=spec.mlp_mults)


def history(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, N_ITEMS, n).astype(np.int32)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(sessions, "SESS_BLOCK", 4)
    monkeypatch.setattr(sessions, "HYB_CHUNK", 32)
    yield
    # a planted failure's traceback holds its lane in a cycle: collect
    # it here, so that no lane of this file is still "live" in another
    # file's /stats.json (the registry of live stores is a WeakSet)
    import gc

    gc.collect()


def server(params, theta, histories, **kw) -> SessionTopK:
    st = falconh1.serving_theta(theta, falconh1.hyb_spec(params))
    return SessionTopK(st["out_emb"][:N_ITEMS], st, params,
                       n_users=max(histories, default=0) + 1,
                       histories=histories,
                       **{"audit": 16, "microbatch": False, **kw})


def full(theta, cfg, events, **kw):
    n = len(events)
    return ref.forward(theta, np.asarray(events), cfg, at=[n - 1],
                       states_at=[n - 1], q_block=16, s_block=16, **kw)


def agrees(srv, theta, cfg, uid, events, atol=F32_ATOL):
    """The lane's latest answer for ``uid`` and its slot, against the
    reference's full forward over ``events``: every item's score, every
    layer's residual stream, both branches' outputs, the key and value
    rows written, every layer's state and tail."""
    got, slot = srv.audits(uid)[-1], srv.session_state(uid)
    want = full(theta, cfg, events)
    assert got["length"] == slot["length"] == len(events)
    np.testing.assert_allclose(got["scores"], want["scores"][0],
                               atol=min(atol, SCORE_ATOL * atol / F32_ATOL),
                               err_msg="scores")
    for key in ("layers", "att", "ssm", "mid", "k", "v"):
        np.testing.assert_allclose(got[key], want[key][:, 0], atol=atol,
                                   err_msg=key)
    for key in ("state", "tail"):
        np.testing.assert_allclose(
            slot[key], want["states"][len(events) - 1][key], atol=atol,
            err_msg=key)
    return got, want


# -- the scan: chunked form against the recurrence ------------------------------------

def scan_inputs(T: int, seed: int = 0, heads: int = 4, P: int = 8,
                N: int = 6):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(T, heads, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (T, heads))).astype(
        np.float32)
    A = -rng.uniform(1, 16, heads).astype(np.float32)
    Bm, Cm = (rng.normal(size=(T, heads, N)).astype(np.float32)
              for _ in range(2))
    S0 = rng.normal(size=(heads, P, N)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (S0, x, dt, A, Bm, Cm))


@pytest.mark.parametrize("chunk", [1, 4, 8, 16])
def test_chunked_form_is_the_recurrence(chunk):
    """Across chunk borders (16 positions in chunks of 1, 4, 8) and in
    one chunk: the same outputs and the same state, from a state that
    is not zero."""
    args = scan_inputs(16)
    s_want, y_want = falconh1.ssd_recurrent(*args)
    s_got, y_got = falconh1.ssd_chunked(*args, chunk)
    np.testing.assert_allclose(y_got, y_want, atol=2e-5)
    np.testing.assert_allclose(s_got, s_want, atol=2e-5)


@pytest.mark.parametrize("n", [5, 13, 130], ids=["5", "13", "130"])
def test_a_length_that_is_no_multiple_of_the_chunk_is_padded_by_steps_of_zero(
        n):
    """The chunked form takes whole chunks: rows behind the valid ones
    have ``dt = 0`` (decay 1, no input), at a chunk of 4 and at the
    published 128 alike, and the state handed on is the recurrence's
    after the valid rows."""
    for chunk in (4, 128):
        S0, x, dt, A, Bm, Cm = scan_inputs(n, seed=n)
        T = -(-n // chunk) * chunk
        pad = lambda a: jnp.pad(          # noqa: E731
            a, ((0, T - n),) + ((0, 0),) * (a.ndim - 1), constant_values=1.0)
        dt_p = jnp.pad(dt, ((0, T - n), (0, 0)))
        s_want, y_want = falconh1.ssd_recurrent(S0, x, dt, A, Bm, Cm)
        s_got, y_got = falconh1.ssd_chunked(S0, pad(x), dt_p, A, pad(Bm),
                                            pad(Cm), chunk)
        np.testing.assert_allclose(y_got[:n], y_want, atol=5e-5)
        np.testing.assert_allclose(s_got, s_want, atol=5e-5)


@pytest.mark.parametrize("ends", [(5,), (3, 9), (4, 4, 7), (16, 1)],
                         ids=["5", "3+9", "4+4+7", "16+1"])
def test_chunks_with_ragged_ends_carry_state_and_tail(ends):
    """A history cut into prefill chunks that end INSIDE a chunk of the
    chunked form (padding behind the valid rows) and across its
    borders: state and tail carried from call to call give what one
    pass of the recurrence gives."""
    params, theta, _ = build()
    spec = falconh1.hyb_spec(params)
    th = {k: jnp.asarray(v) for k, v in theta.items()}
    n = sum(ends)
    h = jnp.asarray(np.random.default_rng(1).normal(
        size=(n, spec.width)), jnp.float32)
    zero = (jnp.zeros((spec.ssm_heads, spec.ssm_head_dim, spec.d_state)),
            jnp.zeros((spec.conv - 1, spec.conv_width)))
    want, s_want, t_want = falconh1._ssm_mixer(
        th, 0, h, *zero, n, spec, falconh1.ssd_recurrent, "recurrent")
    (state, tail), at, outs = zero, 0, []
    for m in ends:
        C = -(-m // 4) * 4 + 4         # whole chunks, one of padding
        rows = jnp.zeros((C, spec.width)).at[:m].set(h[at:at + m])
        y, state, tail = falconh1.ssm_chunk(th, 0, rows, state, tail, m,
                                            spec)
        outs.append(y[:m])
        at += m
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=1e-5)
    np.testing.assert_allclose(state, s_want, atol=1e-5)
    np.testing.assert_array_equal(tail, t_want)


def test_padded_rows_leave_state_and_tail_bit_identical():
    """``ssm_step`` over a group of which one query brings no row and
    one three of eight: the first's state and tail come back bit for
    bit, the second's are those of its three rows alone."""
    params, theta, _ = build()
    spec = falconh1.hyb_spec(params)
    th = {k: jnp.asarray(v) for k, v in theta.items()}
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 8, spec.width)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(
        2, spec.ssm_heads, spec.ssm_head_dim, spec.d_state)), jnp.float32)
    tail = jnp.asarray(rng.normal(
        size=(2, spec.conv - 1, spec.conv_width)), jnp.float32)
    _, s1, t1 = falconh1.ssm_step(th, 1, h, state, tail,
                                  jnp.asarray([0, 3]), spec)
    np.testing.assert_array_equal(s1[0], state[0])
    np.testing.assert_array_equal(t1[0], tail[0])
    _, s3, t3 = falconh1._ssm_mixer(th, 1, h[1, :3], state[1], tail[1], 3,
                                    spec, falconh1.ssd_recurrent,
                                    "recurrent")
    np.testing.assert_allclose(s1[1], s3, atol=1e-6)
    np.testing.assert_array_equal(t1[1], t3)
    assert float(jnp.abs(s1[1] - state[1]).max()) > 1e-3


# -- the block against the reference ---------------------------------------------

@pytest.mark.parametrize("n", [40, 23], ids=["whole-chunks", "ragged"])
def test_full_forward_matches_reference(n):
    params, theta, cfg = build()
    ids = history(n)
    got, _ = S.encoder_forward(
        {k: jnp.asarray(v) for k, v in theta.items()}, ids[None],
        np.ones((1, n), np.int32), spec=S.block_spec(params))
    want = ref.forward(theta, ids, cfg, q_block=8, s_block=16)
    with jax.default_matmul_precision("highest"):
        scores = got[0] @ jnp.asarray(theta["out_emb"][:N_ITEMS]).T
    # every position's logits; their spread is 0.007
    np.testing.assert_allclose(scores, want["scores"], atol=SCORE_ATOL)
    assert want["scores"].std() > 1e-3


def test_both_kinds_name_every_layer_and_what_a_slot_holds():
    params, theta, _ = build()
    spec = falconh1.hyb_spec(params)
    attn, ssm = spec.kinds
    assert attn == ("attn", (0, 1, 2), None, ())
    assert ssm[:3] == ("ssm", (0, 1, 2), None)
    assert [(n, s, d) for n, s, d, _ in ssm[3]] == [
        ("state", (4, 8, 16), "float32"), ("tail", (3, 32 + 2 * 2 * 16),
                                           "float32")]
    assert (spec.d_ssm, spec.conv_width, spec.in_width) == (32, 96, 132)
    assert theta["l0_w_in"].shape == (32, 132)
    assert theta["l0_conv_b"].shape == (96,)
    assert theta["l2_w_gate"].shape == (32, 48)
    assert spec.mup.shape == (132,) and set(np.unique(spec.mup)) == {
        np.float32(m) for m in MULTIPLIERS["ssm_multipliers"]}
    whole = falconh1.hyb_spec(S.SeqRecParams(**S.FALCON_H1_34B, n_layers=72))
    assert whole.kinds[0][1] == whole.kinds[1][1] == tuple(range(72))
    assert (whole.group, whole.kv_width, whole.d_ssm, whole.conv_width,
            whole.in_width) == (5, 512, 4096, 5120, 9248)
    assert [s for _, s, _, _ in whole.state_shapes] == [
        (32, 128, 256), (3, 5120)]
    backbone = FalconH1Backbone(S.SeqRecParams(
        **S.FALCON_H1_34B, n_layers=6, max_seq_len=262144))
    # (HYB_CHUNK is patched to 32 here: whole chunks of the scan at least)
    assert (backbone.chunk, backbone.floor) == (128, 512)


def test_the_scans_parameters_are_drawn_in_the_familys_ranges():
    params, _, _ = build(mamba_n_heads=64, mamba_d_head=1, rank=32)
    theta = S.init_theta(N_ITEMS, params)
    rate = np.exp(theta["l0_a_log"])
    step = np.log1p(np.exp(theta["l0_dt_bias"]))     # softplus
    assert 1 <= rate.min() and rate.max() <= 16 and rate.std() > 2
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert np.log(step).std() > 0.8        # log-uniform over two decades
    assert 0.2 < theta["l0_conv_b"].std() < 0.4
    assert (theta["l0_d_skip"] == 1).all() and (theta["l0_gn_g"] == 1).all()
    served = falconh1.draw_serving_theta(N_ITEMS, params)
    np.testing.assert_allclose(served["l0_a_log"], theta["l0_a_log"],
                               rtol=1e-5)
    low = {k for k, v in served.items() if v.dtype != jnp.float32}
    assert not low          # (float32 compute here: nothing is rounded)
    assert falconh1.is_low("l0_w_in") and falconh1.is_low("out_emb") \
        and not falconh1.is_low("l0_conv") \
        and not falconh1.is_low("l0_conv_b") \
        and not falconh1.is_low("l0_a_log") \
        and not falconh1.is_low("l0_d_skip")


@pytest.mark.parametrize("over, match", [
    (dict(norm="layernorm"), "norm rmsnorm"),
    (dict(positions="learned"), "positions rope"),
    (dict(tied=True), "untied"),
    (dict(mamba_d_conv=0), "needs mamba_d_conv"),
    (dict(intermediate_size=0), "needs intermediate_size"),
    (dict(n_heads=6, n_kv_heads=4), "evenly"),
    (dict(mamba_n_groups=3), "groups"),
    (dict(ssm_multipliers=(1.0, 1.0)), "five slices"),
], ids=["layernorm", "learned", "tied", "no-conv", "no-mlp", "heads",
        "groups", "multipliers"])
def test_the_block_refuses_every_combination_but_its_own(over, match):
    with pytest.raises(ValueError, match=match):
        S.block_spec(S.SeqRecParams(**{**TOY, **over}))


def test_train_seqrec_refuses_the_block():
    params = S.SeqRecParams(**{**TOY, "num_steps": 3})
    bucket = S.bucket_sequences([history(9, 1), history(7, 2)], max_len=16)
    with pytest.raises(ValueError, match="not trained here.*no backward"):
        S.train_seqrec(bucket, N_ITEMS, params)


def test_scores_over_the_held_rows_are_those_rows_of_the_whole_tables():
    """The vocabulary slice: a lane that holds the first 30 rows of
    both tables scores exactly what the first 30 columns of the whole
    table's scores are, for a history whose ids lie in the slice (the
    catalog IS the slice)."""
    params, theta, cfg = build()
    held = 30
    events = (history(20, 1) % held).astype(np.int32)
    want = ref.forward(theta, events, cfg, at=[len(events) - 1],
                       q_block=8, s_block=16)["scores"][0]
    cut = dict(theta, item_emb=theta["item_emb"][:held],
               out_emb=theta["out_emb"][:held])
    st = falconh1.serving_theta(cut, falconh1.hyb_spec(params))
    srv = SessionTopK(st["out_emb"], st, params, n_users=1,
                      histories={0: events[:-2]}, audit=4, microbatch=False)
    assert srv.n_items == held
    srv.sess_topk(0, events[-2:], 5)
    got = srv.audits(0)[-1]["scores"]
    assert got.shape == (held,)
    np.testing.assert_allclose(got, want[:held], atol=SCORE_ATOL)
    sliced = ref.forward(cut, events, dict(cfg, n_items=held),
                         at=[len(events) - 1], q_block=8, s_block=16)
    np.testing.assert_array_equal(sliced["scores"][0], want[:held])
    srv.close()


# -- the session lane -------------------------------------------------------------------

@pytest.mark.parametrize("stored, steps", [
    (0, (3, 8, 1)), (21, (5, 11)), (70, (1, 2, 8))],
    ids=["from-nothing", "inside-a-chunk", "three-prefill-chunks"])
def test_prefill_then_extensions_match_the_full_forward(stored, steps):
    """A stored history prefilled (the chunked form, several chunks),
    then queries of 1-11 events (more than 8: steps in order) through
    slots and caches IN THE SAME LAYERS: scores, streams, both
    branches, rows and STATES are the reference's full forward's."""
    params, theta, cfg = build()
    events = history(stored, 1).tolist()
    srv = server(params, theta, {0: np.asarray(events, np.int32)})
    for j, n in enumerate(steps):
        new = history(n, 10 + j)
        idx, _ = srv.sess_topk(0, new, 5)
        events += new.tolist()
        got, want = agrees(srv, theta, cfg, 0, events)
        top = np.argsort(-np.where(np.isin(np.arange(N_ITEMS), events),
                                   -np.inf, want["scores"][0]))[:5]
        assert idx.tolist() == top.tolist()
    report = srv.session_report()
    assert [k["layers"] for k in report["kinds"]] == [3, 3]
    assert report["kinds"][1]["held"] == 1 and report["kinds"][1]["slotBytes"] \
        == 3 * 4 * (4 * 8 * 16 + 3 * 96)
    assert report["kinds"][0]["held"] == -(-len(events) // 4)
    srv.close()


def test_the_prefills_last_state_is_the_users_row():
    """The prefill's chunked form against the reference on logits: the
    hidden state a prefill leaves in the user's row scores the output
    table as the reference's last position does (no query yet)."""
    params, theta, cfg = build()
    events = history(45, 3)
    srv = server(params, theta, {0: events})
    srv.warmup(max_k=8)
    want = full(theta, cfg, events)
    with jax.default_matmul_precision("highest"):
        got = srv.last_hidden(0) @ theta["out_emb"][:N_ITEMS].T
    np.testing.assert_allclose(got, want["scores"][0], atol=SCORE_ATOL)
    slot = srv.session_state(0)
    assert slot["length"] == 45
    np.testing.assert_allclose(slot["state"], want["states"][44]["state"],
                               atol=F32_ATOL)
    srv.close()


def test_bfloat16_lane_against_the_float32_reference():
    """The served precision: bfloat16 operands, rows and tails, float32
    states. The model is dense (no router to flip), so a bf16 lane stays
    within rounding of the reference THROUGH the whole history: scores
    within 5% of their spread (read 0.012), streams and branches within
    2% (read 0.004), every head's state within 2% (read 0.005)."""
    params, theta, cfg = build(compute_dtype="bfloat16")
    spec = falconh1.hyb_spec(params)
    st = falconh1.serving_theta(theta, spec)
    served = {k: np.asarray(v.astype(jnp.float32)) for k, v in st.items()}
    events = history(40, 1).tolist()
    srv = SessionTopK(served["out_emb"][:N_ITEMS], st, params, n_users=1,
                      histories={0: np.asarray(events, np.int32)}, audit=4,
                      microbatch=False)
    assert srv._pool["state"][0].dtype == jnp.float32
    assert srv._pool["tail"][0].dtype == srv._pool["k"][0].dtype \
        == jnp.bfloat16
    new = history(5, 2)
    srv.sess_topk(0, new, 5)
    events += new.tolist()
    got, slot = srv.audits(0)[-1], srv.session_state(0)
    r = readings(got, slot, full(served, cfg, events), len(events))
    srv.close()
    assert r["score_err"] < 0.05 and r["layer_err"] < 0.02 \
        and r["branch_err"] < 0.02 and r["state_err"] < 0.02, r


def readings(got, slot, want, n):
    """The check's readings at toy size: scores, streams, branches,
    rows, states (worst head, relative) and tails."""
    s_want = want["states"][n - 1]
    L = len(got["layers"])
    heads = np.asarray(s_want["state"]).reshape(L, 4, -1)
    got_heads = np.asarray(slot["state"]).reshape(L, 4, -1)
    rel = lambda a, b: float(np.linalg.norm(a - b)        # noqa: E731
                             / (np.linalg.norm(b) + 1e-30))
    return {
        "score_err": float(np.abs(got["scores"] - want["scores"][0]).max()
                           / want["scores"][0].std()),
        "layer_err": max(rel(got["layers"][i], want["layers"][i, 0])
                         for i in range(L)),
        "branch_err": max(rel(got[b][i], want[b][i, 0])
                          for i in range(L) for b in ("att", "ssm")),
        "cache_err": max(rel(np.concatenate([got["k"][j], got["v"][j]]),
                             np.concatenate([want["k"][j, 0],
                                             want["v"][j, 0]]))
                         for j in range(L)),
        "state_err": float((np.linalg.norm(got_heads - heads, axis=-1)
                            / (np.linalg.norm(heads, axis=-1) + 1e-30)).max()),
        "tail_err": rel(slot["tail"], s_want["tail"])}


# a control, and the reading that has to catch it
CAUGHT_BY = {"state_bf16": "state_err", "no_d_skip": "branch_err",
             "no_conv_bias": "state_err", "no_key_mult": "cache_err",
             "no_ssm_out_mult": "branch_err",
             "no_attn_out_mult": "branch_err",
             "norm_before_gate": "branch_err", "stale_tail": "state_err",
             "slot_ahead": "tail_err", "no_head_mult": "score_err"}


@pytest.mark.parametrize("control", (None,) + ref.CONTROLS)
def test_every_control_of_the_reference_fails_the_comparison(control):
    """The lane against the reference degraded by one control: the
    sound pass reads rounding (1e-5); each control, a state kept in
    bfloat16 among them, moves the reading named for it by a hundred
    times that or more: the tolerances above are tight enough that
    computing in a lower precision than stated fails them."""
    params, theta, cfg = build()
    events = history(70, 1).tolist()
    srv = server(params, theta, {0: np.asarray(events, np.int32)})
    new = history(3, 9)
    srv.sess_topk(0, new, 5)
    events += new.tolist()
    got, slot = srv.audits(0)[-1], srv.session_state(0)
    # (the reference needs an event behind the compared one to hand out
    # a state that is ahead)
    want = ref.forward(theta, np.asarray(events + [0]), cfg, at=[72],
                       states_at=[72], q_block=16, s_block=16,
                       control=control, stale_at=70)
    r = readings(got, slot, want, len(events))
    srv.close()
    if control is None:
        assert max(r.values()) < 1e-4, r
    else:
        assert r[CAUGHT_BY[control]] > 1e-3, (control, r)
    assert set(CAUGHT_BY) == set(ref.CONTROLS)


def test_two_queries_of_one_user_in_one_group_see_their_own_prefixes():
    """Both land in one group of the lane; a dispatch overwrites the
    user's slot, so they ride in separate waves, the first answering
    for its own prefix and the second for both."""
    from predictionio_tpu.ops.serving import _Pending
    from predictionio_tpu.ops.sessions import _dispatch_sess_group

    params, theta, cfg = build()
    hist = {0: history(21, 1), 1: history(5, 2)}
    a, b, c = history(2, 5), history(3, 6), history(1, 7)
    srv = server(params, theta, hist)
    group = [_Pending((0, a), 5, 0.0, 0, 0.0), _Pending((1, c), 5, 0.0, 0, 0.0),
             _Pending((0, b), 5, 0.0, 0, 0.0)]
    for it in group:
        it.future.set_running_or_notify_cancel()
    _dispatch_sess_group(srv, group)
    lengths = [x["length"] for x in srv.audits(0)]
    assert lengths == [23, 26]
    first = srv.audits(0)[0]
    want = full(theta, cfg, hist[0].tolist() + a.tolist())
    np.testing.assert_allclose(first["scores"], want["scores"][0],
                               atol=SCORE_ATOL)
    agrees(srv, theta, cfg, 0, hist[0].tolist() + a.tolist() + b.tolist())
    agrees(srv, theta, cfg, 1, hist[1].tolist() + c.tolist())
    srv.close()


def test_a_session_is_admitted_released_and_evicted_from_both_kinds_at_once():
    """Two kinds over the SAME layers: a pool too small for all
    sessions: a session is admitted only when blocks AND a slot are
    free, the one touched longest ago leaves both kinds at once, its
    next touch prefills it again from the host's events, slot included,
    and it answers as before; the arrays are a LAYER's once a kind."""
    from predictionio_tpu.utils import metrics

    params, theta, cfg = build()
    hist = {u: history(18 + 4 * u, u) for u in range(4)}
    srv = server(params, theta, hist, pool_tokens=64)
    # 16 blocks of 4 for 26 the histories need: slots for 4 x 16 / 26
    assert srv._kind_blocks == [17, 4]
    assert srv._layer_kind == {0: 0, 1: 0, 2: 0}
    assert [a.shape[0] for a in srv._pool["k"]] == [17] * 3
    assert [a.shape for a in srv._pool["state"]] == [(4, 4, 8, 16)] * 3
    evicted = metrics.SESS_EVICTIONS.value()
    events = {u: h.tolist() for u, h in hist.items()}
    for j, u in enumerate((0, 1, 2, 3, 0, 2, 1, 3)):
        new = history(3, 50 + j)
        srv.sess_topk(u, new, 5)
        events[u] += new.tolist()
        agrees(srv, theta, cfg, u, events[u])
        held = srv._held_blocks()
        live = list(srv._sessions.values())
        assert held == [sum(len(s.held[k]) for s in live) for k in (0, 1)]
        assert all(len(s.held[1]) == 1 and s.held[0] for s in live)
        for k in (0, 1):    # no block or slot in two hands, none lost
            mine = [b for s in live for b in s.held[k]] + srv._frees[k]
            assert sorted(mine) == list(range(1, srv._kind_blocks[k]))
        assert metrics.SESS_CACHE_TOKENS.value() == 4 * held[0]
        assert metrics.SESS_STATE_SLOTS.value() == held[1] == len(live)
    assert metrics.SESS_EVICTIONS.value() > evicted
    assert metrics.SESS_STATE_CAPACITY.value() == 3
    assert metrics.SESS_STATE_SLOT_BYTES.value() == 3 * 4 * (512 + 288)
    report = srv.session_report()
    assert [(k["name"], k["layers"]) for k in report["kinds"]] == [
        ("attn", 3), ("ssm", 3)]
    assert report["capacityTokens"] == 4 * 16
    memory = srv.memory_report()["components"]
    assert memory["sessionStates"]["bytes"] == 3 * 4 * 512 * 4
    assert memory["sessionKeys"]["bytes"] == 3 * 17 * 4 * 32 * 4
    # released: both kinds at once, and nothing of it stays held
    u = next(iter(srv._sessions))
    before = srv._held_blocks()
    mine = [len(h) for h in srv._sessions[u].held]
    srv.release(u)
    assert srv._held_blocks() == [b - m for b, m in zip(before, mine)]
    assert srv.session_state(u) is None and srv.session_rows(u) is None
    srv.close()


@pytest.mark.parametrize("fails_in", ["_book_counters", "_unpack"])
def test_a_dispatch_that_fails_after_its_program_is_forgotten_in_both_kinds(
        fails_in, monkeypatch):
    """The program's arrays are swapped in BEFORE the session's length
    is booked. A dispatch that fails in between has advanced the slot
    by events the session does not hold; the lane then forgets the
    dispatch's sessions, blocks and slot at once: the next query
    prefills from the host's events and answers as if the failed one
    had never been."""
    params, theta, cfg = build()
    hist = {0: history(21, 1)}
    srv = server(params, theta, hist)
    srv.sess_topk(0, history(2, 2), 5)
    events = hist[0].tolist() + history(2, 2).tolist()

    def boom(*a, **k):
        raise RuntimeError("planted")

    if fails_in == "_unpack":
        monkeypatch.setattr(sessions, "_unpack", boom)
    else:
        monkeypatch.setattr(srv._bb, "_book_counters", boom)
    with pytest.raises(RuntimeError, match="planted"):
        srv.sess_topk(0, history(4, 3), 5)
    monkeypatch.undo()
    monkeypatch.setattr(sessions, "SESS_BLOCK", 4)
    monkeypatch.setattr(sessions, "HYB_CHUNK", 32)
    assert srv.cached_length(0) == 0 and srv.session_state(0) is None
    assert srv._held_blocks() == [0, 0]
    held_events = srv.session_events(0).tolist()
    assert held_events[:len(events)] == events
    new = history(3, 4)
    srv.sess_topk(0, new, 5)
    agrees(srv, theta, cfg, 0, held_events + new.tolist())
    assert srv.session_state(0)["length"] == len(held_events) + 3
    srv.close()


def test_a_row_of_ints_carries_one_block_table_and_one_slot_id():
    """``kind_layout`` of a backbone whose two kinds name the same
    layers: the block kind's rows and table, then ONE slot id, that the
    same layer's program reads; and the lane fills both from one
    session."""
    spec = falconh1.hyb_spec(S.SeqRecParams(**S.FALCON_H1_34B, n_layers=6))
    mine = tuple(sessions.LayerKind(*k) for k in spec.kinds)
    layout, width = sessions.kind_layout(mine, 8, 16384, 256)
    assert layout == ((11, -1, 19, 64), (-1, -1, 83, 1)) and width == 84
    assert sessions.kind_layout(mine, 2048, 8192, 256) == (
        ((2051, -1, 4099, 32), (-1, -1, 4131, 1)), 4132)
    params, theta, _ = build()
    srv = server(params, theta, {0: history(10, 1), 1: history(3, 2)})
    srv.sess_topk(1, history(1, 3), 5)
    srv.sess_topk(0, history(1, 3), 5)
    sess = srv._sessions[0]
    S_ = srv._s_bucket(16)
    row = srv._pad_row(8, S_)
    with srv._sess_lock:
        srv._reserve(sess, sess.length + 2, busy=())
        srv._kind_fill(row, sess, 8, S_, sess.length, 2)
    (w, _, t, nb), (_, _, at, _) = srv._kind_layout(8, S_)
    assert sess.length == 11 and len(sess.held[0]) == 4
    assert row[at] == sess.held[1][0] != 0
    assert row[t:t + 4].tolist() == sess.held[0]
    # positions 11 and 12: the last row of block 2, the first of block 3
    assert row[w:w + 2].tolist() == [sess.held[0][2] * 4 + 3,
                                     sess.held[0][3] * 4]
    srv.close()


# what ``kind_layout`` gave the four backbones the benchmark already
# serves, at the shapes their cells dispatch (T 8, their longest
# bucket) and prefill, before a layer could be of two kinds
LAYOUTS_AS_THEY_WERE = {
    "glm_moe_dsa": (((11, -1, 19, 128),), 147, ((2051, -1, 4099, 128),), 4227),
    "sdar_moe": (((11, -1, 19, 128),), 147, ((2051, -1, 4099, 128),), 4227),
    "smallthinker": (((11, -1, 19, 128), (147, 155, 156, 18)), 174,
                     ((2051, -1, 4099, 128), (4227, 6275, 6276, 25)), 6301),
    "qwen3_next": (((11, -1, 19, 128), (-1, -1, 147, 1)), 148,
                   ((2051, -1, 4099, 128), (-1, -1, 4227, 1)), 4228)}


def _published_backbone(block):
    return sessions.backbone_of(S.SeqRecParams(**{
        "glm_moe_dsa": dict(S.GLM_5, n_layers=4, n_dense_layers=1,
                            experts_held=8),
        "sdar_moe": dict(S.SDAR_30B_A3B, n_layers=4),
        "smallthinker": dict(S.SMALLTHINKER_21B_A3B, n_layers=8),
        "qwen3_next": dict(S.QWEN3_NEXT_80B_A3B, n_layers=8,
                           experts_held=128)}[block]))


@pytest.mark.parametrize("block", sorted(LAYOUTS_AS_THEY_WERE))
def test_kind_layout_of_the_four_existing_backbones_is_what_it_was(block):
    bb = _published_backbone(block)
    kinds = tuple(bb.kinds)
    got = sessions.kind_layout(kinds, 8, 32768, 256) \
        + sessions.kind_layout(kinds, 2048, 32768, 256)
    assert got == LAYOUTS_AS_THEY_WERE[block]
    # a layer of these backbones is of ONE kind
    named = [i for k in kinds for i in k.layers]
    assert sorted(named) == list(range(bb.spec.n_layers))


def _toy_lane(block):
    """A lane of each of the five backbones at toy widths, two resident
    sessions."""
    common = dict(norm="rmsnorm", positions="rope", tied=False, num_steps=0,
                  seeded_weights=True, max_seq_len=128, seed=3)
    toys = {
        "glm_moe_dsa": dict(
            block="glm_moe_dsa", rank=32, n_heads=4, n_layers=3,
            q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2,
            index_head_dim=8, index_topk=4, n_dense_layers=1,
            dense_width=48, n_experts=4, expert_width=16,
            experts_per_token=2, n_shared_experts=1, **common),
        "sdar_moe": dict(
            block="sdar_moe", rank=32, n_heads=4, n_kv_heads=2, head_dim=8,
            n_layers=2, n_experts=4, expert_width=16, experts_per_token=2,
            norm_topk_prob=True, block_length=4, denoising_steps=2,
            **common),
        "smallthinker": dict(
            block="smallthinker", rank=32, n_heads=4, n_kv_heads=2,
            head_dim=8, n_layers=4, n_experts=4, expert_width=16,
            experts_per_token=2, norm_topk_prob=True, sliding_window_size=8,
            sliding_window_layout=(0, 1, 1, 1), **common),
        "qwen3_next": dict(
            block="qwen3_next", rank=32, n_heads=4, n_kv_heads=2,
            head_dim=16, partial_rotary_factor=0.25, n_layers=4,
            n_experts=4, expert_width=16, experts_per_token=2,
            norm_topk_prob=True, shared_expert_width=16, linear_key_heads=2,
            linear_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, linear_conv_kernel=4,
            full_attention_interval=4, **common),
        "falcon_h1": TOY}
    params = S.SeqRecParams(**toys[block])
    bb = sessions.backbone_of(params)
    rows = N_ITEMS + (1 if block == "sdar_moe" else 0)   # the mask token's
    st = bb.serving_theta(S.init_theta(rows, params))
    return SessionTopK(st["out_emb"][:N_ITEMS], st, params, n_users=2,
                       histories={0: history(9, 1), 1: history(14, 2)},
                       microbatch=False, backbone=bb)


@pytest.mark.parametrize("block", ["glm_moe_dsa", "sdar_moe", "smallthinker",
                                   "qwen3_next", "falcon_h1"])
def test_memory_report_counts_every_pool_array_once(block, monkeypatch):
    """``totalBytes`` is the store's own tables plus the backbone's
    weights plus the sum of the pool's arrays, each once, whether or
    not two kinds share layers; and the components' names are one a
    pool array."""
    from predictionio_tpu.ops import qwen3next
    from predictionio_tpu.ops.serving import DeviceTopK

    monkeypatch.setattr(sessions, "LIN_CHUNK", 32)
    monkeypatch.setattr(sessions, "SWA_CHUNK", 32)
    monkeypatch.setattr(qwen3next, "GDN_CHUNK", 4)
    srv = _toy_lane(block)
    report = srv.memory_report()
    store = DeviceTopK.memory_report(srv)["totalBytes"]
    pool = sum(a.nbytes for arrays in srv._pool.values() for a in arrays)
    weights = sum(v.nbytes for v in srv._theta.values())
    assert report["totalBytes"] == store + weights + pool
    named = [c for c in report["components"] if c.startswith("session")]
    assert len(named) == len(srv._pool)
    assert sum(report["components"][c]["bytes"] for c in named) == pool
    kinds = report["sessions"]["kinds"]
    assert [k["layers"] for k in kinds] == [len(k.layers)
                                            for k in srv._kinds]
    srv.close()


def test_ladder_is_complete_after_warm_up():
    """``warmup()`` compiles every program the lane can dispatch and
    prefills the stored sessions; queries of every group size then
    compile nothing and every dispatch is an ``aot`` hit; the slots'
    traffic is counted once a live query."""
    import time

    from predictionio_tpu.utils import device_telemetry, metrics

    metrics.install_jit_compile_listener()
    params, theta, cfg = build()
    hist = {u: history(6 + 9 * u, u) for u in range(4)}
    srv = server(params, theta, hist)
    srv.warmup(max_k=8)
    assert srv.session_report()["sessions"] == 4
    before = metrics.JIT_COMPILES.value()
    read = metrics.SESS_STATE_BYTES.value(dir="read")
    rows = metrics.SESS_ROWS_READ.value(kind="attn")
    t0 = time.time()
    lengths = 0
    for group in ([0], [1, 2], [0, 1, 2, 3]):
        lengths += sum(srv.cached_length(u) + 3 for u in group)
        srv.extend([(u, history(3, 60 + u)) for u in group],
                   srv._sess_kb(5))
    assert metrics.JIT_COMPILES.value() == before
    assert metrics.SESS_STATE_BYTES.value(dir="read") - read \
        == 7 * 3 * 4 * (512 + 288)
    assert metrics.SESS_ROWS_READ.value(kind="attn") - rows == 3 * lengths
    mine = [r for r in device_telemetry.recorder().snapshot(limit=1 << 20)
            if r["ts"] >= t0 and r["lane"] == "sess"]
    assert len(mine) == 3 and {r["aot"] for r in mine} == {"hit"}
    srv.close()


def test_the_programs_keep_the_names_the_trace_is_read_by():
    """The hybrid cell's readers find the lane's device time under
    ``jit_hyb_extend`` (``benchmark/drivers/http_sess_hybrid.py``), and
    its scopes under the names ``benchmark/layer_metrics`` reads."""
    params, theta, cfg = build()
    srv = server(params, theta, {0: history(9, 0)})
    S_ = srv._s_bucket(16)
    extend = srv._bb.extend_program(srv, srv._sess_kb(5), S_)
    names = (extend.__name__, srv._bb.prefill_program(srv, S_).__name__)
    assert names == ("hyb_extend", "hyb_prefill")
    with srv._store_lock:
        args = (srv._theta, srv._X, srv._seen_bits, srv._pool, srv._Y,
                np.tile(srv._pad_row(8, S_), (1, 1)))
    text = extend.lower(*args).as_text(debug_info=True)
    for scope in ("hyb/embed", "hyb/attn", "hyb/ssd/proj", "hyb/ssd/conv",
                  "hyb/ssd/scan/recurrent", "hyb/ssd/out", "hyb/mlp",
                  "hyb/head"):
        assert scope in text, scope
    assert "hyb/ssd/scan/chunked" not in text
    prefill = srv._bb.prefill_program(srv, S_)
    ints = np.zeros(srv._ints_width(srv._chunk, S_), np.int32)
    with srv._store_lock:
        text = prefill.lower(srv._theta, srv._X, srv._pool, ints).as_text(
            debug_info=True)
    assert "hyb/ssd/scan/chunked" in text
    srv.close()


def test_engine_json_selects_the_block():
    from predictionio_tpu.controller.engine import params_from_dict

    got = params_from_dict(S.SeqRecParams, {
        "block": "falcon_h1", "rank": 5120, "nHeads": 20, "nKvHeads": 4,
        "headDim": 128, "nLayers": 6, "norm": "rmsnorm", "normEps": 1e-5,
        "positions": "rope", "ropeTheta": 1e11, "tied": False,
        "vocabRows": 261120, "intermediateSize": 21504,
        "mambaNHeads": 32, "mambaDHead": 128, "mambaDState": 256,
        "mambaNGroups": 2, "mambaDConv": 4, "mambaChunkSize": 128,
        "attentionOutMultiplier": 0.0375,
        "keyMultiplier": 0.011048543456039804,
        "embeddingMultiplier": 5.656854249492381,
        "lmHeadMultiplier": 0.0078125, "ssmInMultiplier": 0.25,
        "ssmMultipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                           0.5, 0.3535533905932738],
        "ssmOutMultiplier": 0.08838834764831845,
        "mlpMultipliers": [0.1767766952966369, 0.011160714285714284],
        "computeDtype": "bfloat16", "numSteps": 0, "seededWeights": True})
    want = S.SeqRecParams(**S.FALCON_H1_34B, n_layers=6,
                          compute_dtype="bfloat16")
    assert S.block_spec(got) == S.block_spec(want)
    assert isinstance(sessions.backbone_of(want), FalconH1Backbone)
    with pytest.raises(ValueError, match="falcon_h1"):
        sessions.backbone_of(S.SeqRecParams(block="olmoe"))


# -- through the template: deploy and /queries.json ------------------------------------

@pytest.fixture()
def mem_storage():
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.storage import StorageConfig

    storage.reset(StorageConfig(
        sources={"M": {"type": "memory"}},
        repositories={"METADATA": "M", "EVENTDATA": "M", "MODELDATA": "M"}))
    yield
    storage.reset()


def test_pio_train_deploy_and_session_queries(mem_storage, monkeypatch):
    """Events -> ``run_train`` (numSteps 0, seededWeights) ->
    ``QueryServer`` (``build_deployment``, ``SessionTopK`` with the
    Falcon-H1 backbone, warm-up with the resident sessions, the
    batching dispatcher's ``sess`` lane) -> session queries over
    ``/queries.json``, answered as the reference answers from the
    lane's own weights: no side script."""
    import datetime as dt
    import http.client

    from predictionio_tpu.controller import ComputeContext, EngineParams
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.templates.sequentialrec import (
        DataSourceParams,
        SeqPreparatorParams,
        engine_factory,
    )
    from predictionio_tpu.workflow import QueryServer, ServerConfig, run_train
    from predictionio_tpu.workflow.create_workflow import (
        WorkflowConfig,
        new_engine_instance,
    )

    monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)

    def view(user, item, minute):
        return Event(event="view", entity_type="user", entity_id=user,
                     target_entity_type="item", target_entity_id=item,
                     event_time=t0 + dt.timedelta(minutes=minute))

    aid = storage.get_metadata_apps().insert(App(0, "hybapp"))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    events = []
    for u in range(6):
        start = int(rng.integers(0, 30))
        events += [view(f"u{u}", f"i{(start + j) % 40}", j)
                   for j in range(int(rng.integers(5, 30)))]
    le.insert_batch(events, aid)
    algo = S.SeqRecParams(**{**TOY, "max_seq_len": 64}, session_audit=4)
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="hybapp")),
        preparator_params=("", SeqPreparatorParams(max_seq_len=64)),
        algorithm_params_list=[("seqrec", algo)])
    factory = "predictionio_tpu.templates.sequentialrec:engine_factory"
    assert run_train(engine_factory(), params, new_engine_instance(
        WorkflowConfig(engine_factory=factory), params),
        ctx=ComputeContext()) is not None
    srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0)).start(
        undeploy_stale=False)
    try:
        def post(body):
            conn = http.client.HTTPConnection(*srv.address, timeout=60)
            conn.request("POST", "/queries.json", body=json.dumps(body),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read().decode())
            conn.close()
            return resp.status, out

        model = srv._deployment.models[0]
        lane = model.device_server()
        assert isinstance(lane, SessionTopK)
        assert isinstance(lane._bb, FalconH1Backbone)
        assert lane._sess_batcher is not None
        report = lane.session_report()
        assert report["sessions"] == 6
        assert [k["held"] > 0 for k in report["kinds"]] == [True, True]
        assert report["kinds"][1]["held"] == 6
        u3 = model.user_map["u3"]
        before = lane.session_events(u3)
        status, out = post({"user": "u3", "items": ["i1", "i2", "i3"],
                            "num": 6})
        assert status == 200 and len(out["itemScores"]) == 6
        after = lane.session_events(u3)
        assert after.tolist() == before.tolist() + [
            model.item_map[i] for i in ("i1", "i2", "i3")]
        seen = {model.item_map.decode([i])[0] for i in after}
        assert not seen & {s["item"] for s in out["itemScores"]}
        theta = {k: np.asarray(v, np.float32)
                 for k, v in lane.theta.items()}
        got = lane.audits(u3)[-1]
        want = full(theta, cfg_of(lane._spec, len(model.item_map)), after)
        np.testing.assert_allclose(got["scores"], want["scores"][0],
                                   atol=SCORE_ATOL)
        # the same prefix again (no events) is the same answer, and the
        # slot is as it was
        slot = lane.session_state(u3)
        assert post({"user": "u3", "num": 6})[1] == out
        np.testing.assert_array_equal(lane.session_state(u3)["state"],
                                      slot["state"])
    finally:
        srv.stop()


# -- the benchmark's configuration against the catalog row ------------------------------

# the ``config`` of the catalog's row ``Falcon-H1-34B-Instruct`` (the
# model-configs guide's architectures.jsonl), key for key
CATALOG_CONFIG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}


def test_the_cells_configuration_is_the_catalog_rows_but_for_two_keys():
    """``benchmark/configs/seqrec-falconh1.json`` holds every key of
    the catalog row's ``config`` under the same name; ``reduced`` names
    the depth and the vocabulary's slice, each with the published value
    and the deployment; every departure is listed under ``assumed``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "seqrec-falconh1.json")) as f:
        c = json.load(f)
    differs = [k for k, v in CATALOG_CONFIG.items() if c.get(k, "?") != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["vocab_size"]) == (6, 65280)
    for key, published in (("num_hidden_layers", "72"),
                           ("vocab_size", "261,120")):
        assert published in c["reduced"][key], key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Falcon-H1-34B-Instruct")
        assert row["config"] == CATALOG_CONFIG
        assert row["source_url"] == c["source"]
    for key in ("projection layout", "ssm multipliers", "gated norm",
                "state precision", "scan parameters", "convolution bias",
                "rotation", "head multiplier", "vocabulary", "weights",
                "histories"):
        assert key in c["assumed"], key
    published = S.SeqRecParams(**S.FALCON_H1_34B, n_layers=6)
    assert (published.rank, published.n_heads, published.n_kv_heads,
            published.head_dim, published.intermediate_size,
            published.mamba_n_heads * published.mamba_d_head,
            published.mamba_n_heads,
            published.mamba_d_head, published.mamba_d_state,
            published.mamba_n_groups, published.mamba_d_conv,
            published.mamba_chunk_size, False,
            published.rope_theta, published.norm_eps,
            published.attention_in_multiplier,
            published.attention_out_multiplier, published.key_multiplier,
            published.embedding_multiplier, published.lm_head_multiplier,
            published.ssm_in_multiplier, list(published.ssm_multipliers),
            published.ssm_out_multiplier, list(published.mlp_multipliers),
            published.vocab_rows) == tuple(
        CATALOG_CONFIG[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "mamba_d_ssm", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_chunk_size", "mamba_norm_before_gate",
            "rope_theta", "rms_norm_eps", "attention_in_multiplier",
            "attention_out_multiplier", "key_multiplier",
            "embedding_multiplier", "lm_head_multiplier",
            "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
            "mlp_multipliers", "vocab_size"))

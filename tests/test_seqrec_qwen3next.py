"""Qwen3-Next's block (``qwen3_next``) on the sequence lane: three
Gated DeltaNet layers in four (a constant-size recurrent state and a
convolution's tail a session) beside one gated softmax-attention layer
(key and value rows in blocks), the held share of 512 routed experts
beside a gated shared one, and the session lane that serves it from ONE
SLOT a session beside the blocks, under one manager. Everything at toy
widths on the CPU, seeded weights (the norms' weights perturbed, so
that a zero-centred weight read as a plain one shows), against the
float32 reference ``ops/qwen3next_reference.py``, which advances the
rule one position at a time.

A cache block is 4 rows, a chunk of the chunked form 4 positions and a
prefill chunk 32 tokens, so the sessions below end inside chunks,
cross chunk and block borders inside one query's events and are
prefilled in several chunks.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import moe, qwen3next
from predictionio_tpu.ops import qwen3next_reference as ref
from predictionio_tpu.ops import seqrec as S
from predictionio_tpu.ops import sessions
from predictionio_tpu.ops.sessions import Qwen3NextBackbone, SessionTopK

N_ITEMS = 50
TOY = dict(
    block="qwen3_next", rank=32, n_heads=4, n_kv_heads=2, head_dim=16,
    partial_rotary_factor=0.25, n_layers=8, norm="rmsnorm", norm_eps=1e-6,
    positions="rope", rope_theta=1e7, tied=False, n_experts=8,
    experts_held=4, expert_share=1, expert_width=16, experts_per_token=3,
    norm_topk_prob=True, shared_expert_width=16, linear_key_heads=2,
    linear_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel=4, full_attention_interval=4, num_steps=0,
    seeded_weights=True, max_seq_len=128, seed=3)
NORMS = ("ln1_g", "ln2_g", "qn_g", "kn_g", "ln_f_g", "gn_g")
# float32 on the CPU, the lane's chunked prefill against a recurrence
# one position at a time: rounding alone (other orders of the same
# float32 sums through 8 layers); the largest read is 6e-5
F32_ATOL = 2e-4
# bfloat16 operands in every projection, expert and the head, bfloat16
# key/value rows and tails (the states stay float32). At a width of 32
# a router's 3 of 8 flip on a near-tie at some EARLIER position in one
# layer or another (the reference is given the lane's picks at the
# audited position alone), and a flipped expert moves that position's
# stream by tenths, which every later state and score inherits: the
# scores read 0.22 of their spread and the deeper layers' states 0.25 a
# head here. What no flip reaches is the FIRST layer's state (its input
# is the table's rows): 2^-9 roundings of q, k, v, b and a, read 0.005
BF16_SCORE = 0.6
BF16_STATE = 0.012


def build(**over):
    params = S.SeqRecParams(**{**TOY, **over})
    theta = S.init_theta(N_ITEMS, params)
    rng = np.random.default_rng(7)
    for k in theta:
        if k.endswith(NORMS):
            theta[k] = theta[k] + 0.2 * rng.normal(
                size=theta[k].shape).astype(np.float32)
    return params, theta, cfg_of(qwen3next.lin_spec(params))


def cfg_of(spec, n_items=N_ITEMS):
    return dict(n_layers=spec.n_layers, interval=spec.interval,
                n_heads=spec.n_heads, n_kv=spec.n_kv,
                head_dim=spec.head_dim, rot_dim=spec.rot_dim,
                k_heads=spec.k_heads, v_heads=spec.v_heads,
                k_dim=spec.k_dim, v_dim=spec.v_dim, conv=spec.conv,
                per_token=spec.per_token, first=spec.first,
                norm_eps=spec.norm_eps, rope_theta=spec.rope_theta,
                n_items=n_items)


def history(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, N_ITEMS, n).astype(np.int32)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(sessions, "SESS_BLOCK", 4)
    monkeypatch.setattr(sessions, "LIN_CHUNK", 32)
    monkeypatch.setattr(qwen3next, "GDN_CHUNK", 4)
    yield
    # a planted failure's traceback holds its lane in a cycle: collect
    # it here, so that no lane of this file is still "live" in another
    # file's /stats.json (the registry of live stores is a WeakSet)
    import gc

    gc.collect()


def server(params, theta, histories, **kw) -> SessionTopK:
    st = qwen3next.serving_theta(theta, qwen3next.lin_spec(params))
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
    layer's residual stream, the key and value rows written, every
    DeltaNet layer's state and tail."""
    got, slot = srv.audits(uid)[-1], srv.session_state(uid)
    want = full(theta, cfg, events)
    assert got["length"] == slot["length"] == len(events)
    for key in ("scores", "layers", "k", "v"):
        np.testing.assert_allclose(
            got[key], want[key][0] if key == "scores" else want[key][:, 0],
            atol=atol, err_msg=key)
    for key in ("state", "tail"):
        np.testing.assert_allclose(
            slot[key], want["states"][len(events) - 1][key], atol=atol,
            err_msg=key)
    return got, want


# -- the rule: chunked form against the recurrence ------------------------------------

def rule_inputs(T: int, seed: int = 0, heads: int = 3, dk: int = 8,
                dv: int = 6):
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(T, heads, dk)).astype(np.float32)
            for _ in range(2))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, heads, dv)).astype(np.float32)
    g = -rng.uniform(0, 2, size=(T, heads)).astype(np.float32)
    beta = rng.uniform(0, 1, size=(T, heads)).astype(np.float32)
    S0 = rng.normal(size=(heads, dk, dv)).astype(np.float32)
    return S0, q, k, v, g, beta


@pytest.mark.parametrize("chunk", [1, 4, 8, 16])
def test_chunked_form_is_the_recurrence(chunk):
    S0, *xs = rule_inputs(16, seed=chunk)
    s_want, o_want = qwen3next.delta_recurrent(S0, *xs)
    s_got, o_got = qwen3next.delta_chunked(S0, *xs, chunk=chunk)
    np.testing.assert_allclose(s_got, s_want, atol=2e-6)
    np.testing.assert_allclose(o_got, o_want, atol=2e-6)


@pytest.mark.parametrize("ends", [(5,), (3, 9), (4, 4, 7), (16, 1)],
                         ids=["5", "3+9", "4+4+7", "16+1"])
def test_chunks_with_ragged_ends_carry_state_and_tail(ends):
    """A history cut into prefill chunks that end INSIDE a chunk of the
    chunked form (padding behind the valid rows) and across its
    borders: state and tail carried from call to call give what one
    pass of the recurrence gives."""
    params, theta, _ = build()
    spec = qwen3next.lin_spec(params)
    th = {k: jnp.asarray(v) for k, v in theta.items()}
    n = sum(ends)
    h = jnp.asarray(np.random.default_rng(1).normal(
        size=(n, spec.width)), jnp.float32)
    zero = (jnp.zeros((spec.v_heads, spec.k_dim, spec.v_dim)),
            jnp.zeros((spec.conv - 1, spec.conv_width)))
    want, s_want, t_want = qwen3next._gdn_mixer(
        th, 0, h, *zero, n, spec, qwen3next.delta_recurrent)
    (state, tail), at, outs = zero, 0, []
    for m in ends:
        C = -(-m // 4) * 4 + 4         # whole chunks, one of padding
        rows = jnp.zeros((C, spec.width)).at[:m].set(h[at:at + m])
        y, state, tail = qwen3next.gdn_chunk(th, 0, rows, state, tail, m,
                                             spec)
        outs.append(y[:m])
        at += m
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=1e-5)
    np.testing.assert_allclose(state, s_want, atol=1e-5)
    np.testing.assert_array_equal(tail, t_want)


def test_padded_rows_leave_state_and_tail_bit_identical():
    """``gdn_step`` over a group of which one query brings no row and
    one three of eight: the first's state and tail come back bit for
    bit, the second's are those of its three rows alone."""
    params, theta, _ = build()
    spec = qwen3next.lin_spec(params)
    th = {k: jnp.asarray(v) for k, v in theta.items()}
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 8, spec.width)), jnp.float32)
    state = jnp.asarray(rng.normal(
        size=(2, spec.v_heads, spec.k_dim, spec.v_dim)), jnp.float32)
    tail = jnp.asarray(rng.normal(
        size=(2, spec.conv - 1, spec.conv_width)), jnp.float32)
    _, s1, t1 = qwen3next.gdn_step(th, 1, h, state, tail,
                                   jnp.asarray([0, 3]), spec)
    np.testing.assert_array_equal(s1[0], state[0])
    np.testing.assert_array_equal(t1[0], tail[0])
    _, s3, t3 = qwen3next._gdn_mixer(th, 1, h[1, :3], state[1], tail[1], 3,
                                     spec, qwen3next.delta_recurrent)
    np.testing.assert_array_equal(s1[1], s3)
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
    np.testing.assert_allclose(scores, want["scores"], atol=5e-4)


def test_the_interval_names_the_kinds_and_what_a_slot_holds():
    params, theta, _ = build()
    spec = qwen3next.lin_spec(params)
    assert spec.pattern == (0, 0, 0, 1, 0, 0, 0, 1)
    full_kind, linear = spec.kinds
    assert full_kind == ("full", (3, 7), None, ())
    assert linear[:3] == ("linear", (0, 1, 2, 4, 5, 6), None)
    assert [(n, s, d) for n, s, d, _ in linear[3]] == [
        ("state", (4, 8, 8), "float32"), ("tail", (3, 64), "float32")]
    assert [spec.index_in_kind(i) for i in range(8)] == [
        0, 1, 2, 0, 3, 4, 5, 1]
    assert (spec.rot_dim, spec.first, spec.held) == (4, 4, 4)
    assert theta["l0_w_qkvz"].shape == (32, 64 + 32)
    assert theta["l3_wq"].shape == (32, 2 * 64)
    assert theta["l0_we_gate"].shape == (4, 32, 16)
    assert theta["l0_router"].shape == (32, 8)
    assert "l3_a_log" not in theta and "l0_wq" not in theta
    whole = qwen3next.lin_spec(S.SeqRecParams(
        **S.QWEN3_NEXT_80B_A3B, n_layers=48, experts_held=128))
    assert whole.kinds[0][1] == tuple(range(3, 48, 4))
    assert (whole.group, whole.rot_dim, whole.conv_width,
            whole.kv_width) == (8, 64, 8192, 512)
    assert [s for _, s, _, _ in whole.state_shapes] == [
        (32, 128, 128), (3, 8192)]


def test_the_decays_parameters_are_drawn_in_the_familys_ranges():
    params, _, _ = build(linear_value_heads=64, linear_key_heads=2)
    theta = S.init_theta(N_ITEMS, params)
    rate = np.exp(theta["l0_a_log"])
    step = np.log1p(np.exp(theta["l0_dt_bias"]))     # softplus
    assert 0 < rate.min() and rate.max() <= 16 and rate.std() > 2
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert np.log(step).std() > 0.8        # log-uniform over two decades
    served = qwen3next.draw_serving_theta(N_ITEMS, params)
    # (the same keys; a jitted log is an ulp off the eager one)
    np.testing.assert_allclose(served["l0_a_log"], theta["l0_a_log"],
                               rtol=1e-5)


@pytest.mark.parametrize("over, match", [
    (dict(norm="layernorm"), "norm rmsnorm"),
    (dict(positions="learned"), "positions rope"),
    (dict(tied=True), "untied"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(linear_conv_kernel=0), "needs linear_conv_kernel"),
    (dict(n_heads=6, n_kv_heads=4), "evenly"),
    (dict(linear_value_heads=3), "value heads"),
    (dict(n_layers=3), "whole period"),
    (dict(expert_share=2), "past the router"),
], ids=["layernorm", "learned", "tied", "no-renorm", "no-conv", "heads",
        "value-heads", "no-period", "share"])
def test_the_block_refuses_every_combination_but_its_own(over, match):
    with pytest.raises(ValueError, match=match):
        S.block_spec(S.SeqRecParams(**{**TOY, **over}))


def test_train_seqrec_refuses_the_block():
    params = S.SeqRecParams(**{**TOY, "num_steps": 3})
    bucket = S.bucket_sequences([history(9, 1), history(7, 2)], max_len=16)
    with pytest.raises(ValueError, match="not trained here"):
        S.train_seqrec(bucket, N_ITEMS, params)


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """8 experts over 4 chips: each share's routed part from its own
    slice of the weights, summed, plus the shared expert counted ONCE,
    is the layer with every expert held, which is the dense form."""
    params, theta, _ = build(experts_held=0, expert_share=0)
    th = {k: jnp.asarray(v) for k, v in theta.items()}
    whole = qwen3next.lin_spec(params)
    assert (whole.held, whole.first) == (8, 0)
    h2 = jnp.asarray(np.random.default_rng(3).normal(size=(12, 32)),
                     jnp.float32)
    valid = jnp.arange(12) < 10
    routed, shared, picks, gates, *_ = qwen3next.experts(th, 2, h2, valid,
                                                         whole)
    parts, found = 0.0, 0
    for share in range(4):
        spec = qwen3next.lin_spec(S.SeqRecParams(
            **{**TOY, "experts_held": 2, "expert_share": share}))
        mine = dict(th, **{f"l2_{n}": th[f"l2_{n}"][2 * share:2 * share + 2]
                           for n in ("we_gate", "we_up", "we_down")})
        y, sh, e, w, touched, here, sg = qwen3next.experts(mine, 2, h2,
                                                           valid, spec)
        np.testing.assert_array_equal(e, picks)
        np.testing.assert_allclose(sh, shared, atol=1e-6)
        parts, found = parts + y, found + int(here)
    assert found == 10 * 3          # every valid row's picks, once
    np.testing.assert_allclose(parts, routed, atol=1e-5)
    dense = moe.moe_ffn_dense(
        h2, th["l2_router"], th["l2_we_gate"], th["l2_we_up"],
        th["l2_we_down"], k=3, renorm=True)
    np.testing.assert_allclose(routed[:10], dense[:10], atol=1e-5)
    assert float(jnp.abs(routed[10:]).max()) == 0.0     # padding: nowhere
    assert float(jnp.abs(shared).max()) > 1e-3


# -- the session lane -------------------------------------------------------------------

@pytest.mark.parametrize("stored, steps", [
    (0, (3, 8, 1)), (21, (5, 11)), (70, (1, 2, 8))],
    ids=["from-nothing", "inside-a-chunk", "three-prefill-chunks"])
def test_prefill_then_extensions_match_the_full_forward(stored, steps):
    """A stored history prefilled (the chunked form, several chunks),
    then queries of 1-11 events (more than 8: steps in order) through
    slots and caches: scores, streams, rows and STATES are the
    reference's full forward's."""
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
    assert report["kinds"][1]["held"] == 1 and report["kinds"][1]["slotBytes"] \
        == 6 * 4 * (4 * 8 * 8 + 3 * 64)
    srv.close()


@pytest.mark.parametrize("n_new", [1, 3, 8])
def test_each_layer_alone_from_the_lanes_own_inputs_and_memory(n_new):
    """LOCAL: the reference's one-layer functions given what the LANE
    fed a layer (every new row's stream, audited) and what the lane
    REMEMBERS (its slot read before the query, the key and value rows
    it holds, read back through the session's block list) give the
    lane's own mixer outputs, the slot behind the query, what the
    experts added and the scores: nothing upstream is in any of these
    comparisons, so float32 reads rounding at every layer."""
    params, theta, cfg = build()
    events = history(70, 1).tolist()
    srv = server(params, theta, {0: np.asarray(events, np.int32)})
    srv.sess_topk(0, history(2, 3), 5)
    events += history(2, 3).tolist()
    before = srv.session_state(0)
    new = history(n_new, 9)
    srv.sess_topk(0, new, 5)
    events += new.tolist()
    got, behind, held = srv.audits(0)[-1], srv.session_state(0), \
        srv.session_rows(0)
    n, pos = len(events), [len(events) - 1]
    assert int(got["new"][0]) == n_new and before["length"] == n - n_new
    assert held["length"] == n and held["k"].shape == (2, n, 2 * 16)
    rows = got["rows"][:, :n_new]
    x_rows = np.concatenate([theta["item_emb"][new][None], rows[:-1]])
    np.testing.assert_allclose(rows[:, -1], got["layers"], atol=0)
    a = [0, 0]
    for i in range(8):
        full_ = int(ref.is_full(cfg, i))
        j, x_last = a[full_], x_rows[i][-1]
        a[full_] += 1
        if full_:
            y = np.asarray(ref.attn_local(theta, cfg, i, x_last[None], pos,
                                          held["k"][j], held["v"][j]))[0]
        else:
            ys, state, tail = ref.gdn_local(
                theta, cfg, i, x_rows[i], before["state"][j],
                before["tail"][j], n - n_new)
            y = ys[-1]
            np.testing.assert_allclose(behind["state"][j], state,
                                       atol=F32_ATOL, err_msg=f"state {i}")
            np.testing.assert_allclose(behind["tail"][j], tail,
                                       atol=F32_ATOL, err_msg=f"tail {i}")
        np.testing.assert_allclose(got["mid"][i] - x_last, y, atol=F32_ATOL,
                                   err_msg=f"mixer {i}")
        np.testing.assert_allclose(
            got["layers"][i] - got["mid"][i], ref.moe_local(
                theta, cfg, i, got["mid"][i][None], got["picks"][i][None])[0],
            atol=F32_ATOL, err_msg=f"experts {i}")
    np.testing.assert_allclose(
        got["scores"], ref.head_local(theta, cfg, got["layers"][-1][None])[0],
        atol=F32_ATOL)
    srv.close()


def test_bfloat16_lane_against_the_float32_reference():
    """The served precision: bfloat16 operands, rows and tails, float32
    states. The scores stay within ``BF16_SCORE`` of the reference's
    (in units of their spread), and the states within 5% a head."""
    params, theta, cfg = build(compute_dtype="bfloat16")
    spec = qwen3next.lin_spec(params)
    st = qwen3next.serving_theta(theta, spec)
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
    want = full(served, cfg, events,
                given={len(events) - 1: got["picks"]})
    err = np.abs(got["scores"] - want["scores"][0]).max() \
        / want["scores"][0].std()
    assert err < BF16_SCORE
    s_want = want["states"][len(events) - 1]["state"]
    head = np.linalg.norm((slot["state"] - s_want).reshape(6, 4, -1), axis=-1) \
        / np.linalg.norm(s_want.reshape(6, 4, -1), axis=-1)
    assert head[0].max() < BF16_STATE and head.max() < 4 * BF16_SCORE
    srv.close()


def readings(got, slot, want, n):
    """The check's readings at toy size: scores, streams, rows, states
    (worst head, relative) and tails."""
    s_want = want["states"][n - 1]
    heads = np.asarray(s_want["state"]).reshape(6, 4, -1)
    got_heads = np.asarray(slot["state"]).reshape(6, 4, -1)
    rel = lambda a, b: float(np.linalg.norm(a - b)        # noqa: E731
                             / (np.linalg.norm(b) + 1e-30))
    return {
        "score_err": float(np.abs(got["scores"] - want["scores"][0]).max()
                           / want["scores"][0].std()),
        "layer_err": max(rel(got["layers"][i], want["layers"][i, 0])
                         for i in range(8)),
        "cache_err": max(rel(np.concatenate([got["k"][j], got["v"][j]]),
                             np.concatenate([want["k"][j, 0],
                                             want["v"][j, 0]]))
                         for j in range(2)),
        "state_err": float((np.linalg.norm(got_heads - heads, axis=-1)
                            / (np.linalg.norm(heads, axis=-1) + 1e-30)).max()),
        "tail_err": rel(slot["tail"], s_want["tail"])}


# a control, and the reading that has to catch it
CAUGHT_BY = {"state_bf16": "state_err", "no_decay": "state_err",
             "beta_one": "state_err", "tail_dropped": "state_err",
             "no_attn_gate": "layer_err", "no_shared_gate": "layer_err",
             "rope_all": "cache_err", "attn_block_lost": "layer_err",
             "deep_no_decay": "state_err", "no_routed": "layer_err",
             "final_norm_plain": "score_err"}


@pytest.mark.parametrize("control", (None,) + ref.CONTROLS)
def test_every_control_of_the_reference_fails_the_comparison(control):
    """The lane against the reference degraded by one control: the
    sound pass reads rounding; each control moves the reading named for
    it by a hundred times that or more."""
    params, theta, cfg = build()
    events = history(70, 1).tolist()
    srv = server(params, theta, {0: np.asarray(events, np.int32)})
    new = history(3, 9)
    srv.sess_topk(0, new, 5)
    events += new.tolist()
    got, slot = srv.audits(0)[-1], srv.session_state(0)
    want = full(theta, cfg, events, control=control, tail_chunk=32)
    r = readings(got, slot, want, len(events))
    srv.close()
    if control is None:
        assert max(r.values()) < 1e-4, r
    else:
        assert r[CAUGHT_BY[control]] > 3e-3, (control, r)
    assert set(CAUGHT_BY) == set(ref.CONTROLS)


def test_two_queries_of_one_user_in_one_group_are_ordered():
    """Rule (i): both land in one group of the lane; a dispatch
    overwrites the user's slot, so they ride in separate waves, the
    first answering for its own prefix and the second for both."""
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
                               atol=F32_ATOL)
    agrees(srv, theta, cfg, 0, hist[0].tolist() + a.tolist() + b.tolist())
    agrees(srv, theta, cfg, 1, hist[1].tolist() + c.tolist())
    srv.close()


def test_eviction_and_re_prefill_with_slots_and_blocks():
    """Rule (iv): a pool too small for all sessions: the session
    touched longest ago leaves BOTH kinds at once (its blocks and its
    slot), its next touch prefills it again from the host's events,
    slot included, and it answers as before; the gauges count rows over
    the block kind and slots apart."""
    from predictionio_tpu.utils import metrics

    params, theta, cfg = build()
    hist = {u: history(18 + 4 * u, u) for u in range(4)}
    srv = server(params, theta, hist, pool_tokens=64)
    # 16 blocks of 4 for 26 the histories need: slots for 4 x 16 / 26
    assert srv._kind_blocks == [17, 4]
    assert [a.shape[0] for a in srv._pool["k"]] == [17, 17]
    assert [a.shape for a in srv._pool["state"]] == [(4, 4, 8, 8)] * 6
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
        assert all(len(s.held[1]) == 1 for s in live)
        for k in (0, 1):    # no block or slot in two hands, none lost
            mine = [b for s in live for b in s.held[k]] + srv._frees[k]
            assert sorted(mine) == list(range(1, srv._kind_blocks[k]))
        assert metrics.SESS_CACHE_TOKENS.value() == 4 * held[0]
        assert metrics.SESS_STATE_SLOTS.value() == held[1] == len(live)
    assert metrics.SESS_EVICTIONS.value() > evicted
    assert metrics.SESS_STATE_CAPACITY.value() == 3
    assert metrics.SESS_STATE_SLOT_BYTES.value() == 6 * 4 * (256 + 192)
    report = srv.session_report()
    assert [k["name"] for k in report["kinds"]] == ["full", "linear"]
    assert report["capacityTokens"] == 4 * 16
    memory = srv.memory_report()["components"]
    assert memory["sessionStates"]["bytes"] == 6 * 4 * 256 * 4
    assert memory["sessionConvTails"]["dtype"] == "float32"
    srv.close()


def test_a_freed_slot_is_poisoned_and_never_read():
    """A slot is handed out as it was left: NaN written over every free
    slot (slot 0, which padded rows write, and the one a released
    session gave back among them) and garbage over every free block
    (finite: off the TPU the gathered form multiplies a masked row by
    0) move no answer of a session that then takes one of them."""
    params, theta, cfg = build()
    hist = {0: history(10, 1), 1: history(30, 2)}
    srv = server(params, theta, hist)
    srv.sess_topk(1, history(2, 3), 5)
    srv.release(1)
    with srv._store_lock:
        poisoned = {}
        for name, arrays in srv._pool.items():
            kind = 1 if name in ("state", "tail") else 0
            free = np.zeros(arrays[0].shape[0], bool)
            free[srv._frees[kind] + [0]] = True
            poisoned[name] = tuple(jnp.where(
                free.reshape((-1,) + (1,) * (a.ndim - 1)),
                jnp.nan if kind else 1e4, a) for a in arrays)
        srv._pool = poisoned
    events = {0: hist[0].tolist(), 1: hist[1].tolist()
              + history(2, 3).tolist()}
    for u, seed in ((1, 4), (0, 5), (1, 6)):
        new = history(3, seed)
        srv.sess_topk(u, new, 5)
        events[u] += new.tolist()
        agrees(srv, theta, cfg, u, events[u])
    srv.close()


@pytest.mark.parametrize("fails_in", ["_book_counters", "_unpack"])
def test_a_dispatch_that_fails_after_its_program_leaves_no_state_ahead(
        fails_in, monkeypatch):
    """Rule (iii): the program's arrays are swapped in BEFORE the
    session's length is booked. A dispatch that fails in between has
    advanced the slot by events the session does not hold; the lane
    then forgets the dispatch's sessions, so the other order (a state
    ahead of its length) is never served: the next query prefills from
    the host's events and answers as if the failed one had never
    been."""
    params, theta, cfg = build()
    hist = {0: history(21, 1)}
    srv = server(params, theta, hist)
    srv.sess_topk(0, history(2, 2), 5)
    events = hist[0].tolist() + history(2, 2).tolist()
    slot_before = srv.session_state(0)

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
    assert srv.cached_length(0) == 0 and srv.session_state(0) is None
    held_events = srv.session_events(0).tolist()
    # (the events are the host's: those the failed query had booked
    # before it failed, or none of them)
    assert held_events[:len(events)] == events
    new = history(3, 4)
    srv.sess_topk(0, new, 5)
    agrees(srv, theta, cfg, 0, held_events + new.tolist())
    assert srv.session_state(0)["length"] == len(held_events) + 3
    assert np.abs(slot_before["state"]).max() > 0
    srv.close()


def test_a_lane_of_block_kinds_alone_keeps_its_sessions_after_a_failure(
        monkeypatch):
    """Cache rows past a session's length are read by nobody: the
    failure rule is the slot kinds' alone."""
    from predictionio_tpu.ops import smallthinker

    params = S.SeqRecParams(
        block="smallthinker", rank=64, n_heads=4, n_kv_heads=2, head_dim=16,
        n_layers=4, norm="rmsnorm", positions="rope", tied=False,
        n_experts=8, expert_width=32, experts_per_token=2,
        norm_topk_prob=True, sliding_window_size=8,
        sliding_window_layout=(0, 1, 1, 1), num_steps=0, seeded_weights=True,
        max_seq_len=128, seed=3)
    st = smallthinker.serving_theta(S.init_theta(N_ITEMS, params),
                                    smallthinker.swa_spec(params))
    srv = SessionTopK(st["out_emb"][:N_ITEMS], st, params, n_users=1,
                      histories={0: history(9, 1)}, microbatch=False)
    srv.sess_topk(0, history(2, 2), 5)
    monkeypatch.setattr(sessions, "_unpack", lambda *a: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        srv.sess_topk(0, history(2, 3), 5)
    # (the failure came after the lengths were booked: the rows ARE
    # cached, and the session goes on from them)
    assert srv.cached_length(0) == 13 and not srv._slotted
    monkeypatch.undo()
    monkeypatch.setattr(sessions, "SESS_BLOCK", 4)
    assert len(srv.sess_topk(0, history(1, 4), 5)[0]) == 5
    srv.close()


def test_the_layouts_of_the_other_session_cells_are_unchanged():
    """Cells 6, 7 and 8: one kind that keeps all, and a global beside a
    window kind, lay a row of ``ints`` out as they always did; a slot
    kind adds ONE id behind the block kinds' parts."""
    one = sessions.one_kind(6)
    layout, width = sessions.kind_layout(one, 8, 8192, 256)
    assert layout == ((11, -1, 19, 32),) and width == 3 + 2 * 8 + 32
    assert sessions.kind_layout(one, 4, 32768, 256) == (
        ((7, -1, 11, 128),), 3 + 2 * 4 + 128)
    two = (sessions.LayerKind("global", (0, 4), None),
           sessions.LayerKind("window", (1, 2, 3, 5, 6, 7), 4096))
    layout, width = sessions.kind_layout(two, 8, 16384, 256)
    assert layout == ((11, -1, 19, 64), (83, 91, 92, 18))
    assert width == 92 + 18
    assert sessions.kind_layout(two, 2048, 16384, 256)[0][1][3] == 25
    assert sessions.LayerKind("global", (0,), None).state == ()
    spec = qwen3next.lin_spec(S.SeqRecParams(
        **S.QWEN3_NEXT_80B_A3B, n_layers=8, experts_held=128))
    mine = tuple(sessions.LayerKind(*k) for k in spec.kinds)
    layout, width = sessions.kind_layout(mine, 8, 65536, 256)
    assert layout == ((11, -1, 19, 256), (-1, -1, 275, 1))
    assert width == 276
    row = np.zeros(width, np.int32)
    assert row[275] == 0            # a padded row names slot 0


def test_ladder_is_complete_after_warm_up():
    """``warmup()`` compiles every program the lane can dispatch and
    prefills the stored sessions; queries of every group size then
    compile nothing and every dispatch is an ``aot`` hit."""
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
    made = metrics.SESS_PICKS_MADE.value()
    found = metrics.SESS_LOCAL_PICKS.value()
    t0 = time.time()
    for group in ([0], [1, 2], [0, 1, 2, 3]):
        srv.extend([(u, history(3, 60 + u)) for u in group],
                   srv._sess_kb(5))
    assert metrics.JIT_COMPILES.value() == before
    assert metrics.SESS_STATE_BYTES.value(dir="read") - read \
        == 7 * 6 * 4 * (256 + 192)
    # 7 queries of 3 events: 3 picks an event and layer, half the
    # router's outputs held here
    assert metrics.SESS_PICKS_MADE.value() - made == 7 * 3 * 3 * 8
    assert 0 < metrics.SESS_LOCAL_PICKS.value() - found < 7 * 3 * 3 * 8
    mine = [r for r in device_telemetry.recorder().snapshot(limit=1 << 20)
            if r["ts"] >= t0 and r["lane"] == "sess"]
    assert len(mine) == 3 and {r["aot"] for r in mine} == {"hit"}
    srv.close()


def test_the_programs_keep_the_names_the_trace_is_read_by():
    """The long-session cell's readers find the lane's device time
    under ``jit_lin_extend`` (``benchmark/drivers/http_sess_long.py``)."""
    params, theta, cfg = build()
    srv = server(params, theta, {0: history(9, 0)})
    S_ = srv._s_bucket(16)
    names = (srv._bb.extend_program(srv, srv._sess_kb(5), S_).__name__,
             srv._bb.prefill_program(srv, S_).__name__)
    assert names == ("lin_extend", "lin_prefill")
    srv.close()


def test_engine_json_selects_the_block():
    from predictionio_tpu.controller.engine import params_from_dict

    got = params_from_dict(S.SeqRecParams, {
        "block": "qwen3_next", "rank": 2048, "nHeads": 16, "nKvHeads": 2,
        "headDim": 256, "nLayers": 8, "norm": "rmsnorm", "normEps": 1e-6,
        "positions": "rope", "ropeTheta": 10000000.0,
        "partialRotaryFactor": 0.25, "tied": False, "vocabRows": 151936,
        "nExperts": 512, "expertsHeld": 128, "expertWidth": 512,
        "expertsPerToken": 10, "normTopkProb": True,
        "sharedExpertWidth": 512, "linearKeyHeads": 16,
        "linearValueHeads": 32, "linearKeyHeadDim": 128,
        "linearValueHeadDim": 128, "linearConvKernel": 4,
        "fullAttentionInterval": 4, "computeDtype": "bfloat16",
        "numSteps": 0, "seededWeights": True})
    want = S.SeqRecParams(**S.QWEN3_NEXT_80B_A3B, n_layers=8,
                          experts_held=128, compute_dtype="bfloat16")
    assert S.block_spec(got) == S.block_spec(want)
    spec = S.block_spec(got).lin
    assert (spec.kv_width, spec.group, spec.held, spec.pattern) \
        == (512, 8, 128, (0, 0, 0, 1, 0, 0, 0, 1))
    assert isinstance(sessions.backbone_of(want), Qwen3NextBackbone)


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
    Qwen3-Next backbone, warm-up with the resident sessions, the
    batching dispatcher's ``sess`` lane) -> session queries over
    ``/queries.json`` in cell 6's form, answered as the reference
    answers from the lane's own weights."""
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

    aid = storage.get_metadata_apps().insert(App(0, "linapp"))
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
        data_source_params=("", DataSourceParams(app_name="linapp")),
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
        assert isinstance(lane._bb, Qwen3NextBackbone)
        assert lane._sess_batcher is not None
        report = lane.session_report()
        assert report["sessions"] == 6 and report["kinds"][1]["held"] == 6
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
                                   atol=F32_ATOL)
        # the same prefix again (no events) is the same answer, and the
        # slot is as it was
        slot = lane.session_state(u3)
        assert post({"user": "u3", "num": 6})[1] == out
        np.testing.assert_array_equal(lane.session_state(u3)["state"],
                                      slot["state"])
    finally:
        srv.stop()


# -- the benchmark's configuration against the catalog row ------------------------------

# the ``config`` of the catalog's row ``Qwen3-Next-80B-A3B-Instruct``
# (the model-configs guide's architectures.jsonl), key for key
CATALOG_CONFIG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_cells_configuration_is_the_catalog_rows_but_for_three_keys():
    """``benchmark/configs/seqrec-qwen3next.json`` holds every number of
    the catalog row's ``config`` under the same key; ``reduced`` names
    the depth, the experts held and the vocabulary's slice, each with
    the published value and the deployment; every departure is listed
    under ``assumed``."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "seqrec-qwen3next.json")) as f:
        c = json.load(f)
    differs = [k for k, v in CATALOG_CONFIG.items() if c.get(k, "?") != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (8, 128, 37984)
    for key, published in (("num_hidden_layers", "48"),
                           ("num_experts", "512"),
                           ("vocab_size", "151,936")):
        assert published in c["reduced"][key], key
    assert "four chips share each layer" in c["reduced"]["num_experts"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == CATALOG_CONFIG
        assert row["source_url"] == c["source"]
    for key in ("projection layout", "norm weights", "l2 norms",
                "state precision", "decay parameters",
                "no multi-token prediction", "vocabulary", "weights",
                "histories", "rotation"):
        assert key in c["assumed"], key
    published = S.SeqRecParams(**S.QWEN3_NEXT_80B_A3B, n_layers=8)
    assert (published.rank, published.n_heads, published.n_kv_heads,
            published.head_dim, published.expert_width, published.n_experts,
            published.experts_per_token, published.shared_expert_width,
            published.linear_key_heads, published.linear_value_heads,
            published.linear_key_head_dim, published.linear_value_head_dim,
            published.linear_conv_kernel, published.full_attention_interval,
            published.partial_rotary_factor, published.rope_theta,
            published.norm_eps) == tuple(
        CATALOG_CONFIG[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "shared_expert_intermediate_size",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "full_attention_interval",
            "partial_rotary_factor", "rope_theta", "rms_norm_eps"))

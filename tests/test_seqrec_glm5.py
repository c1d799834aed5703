"""GLM-5's block (``glm_moe_dsa``) on the sequence lane: latent
attention with the learned sparse indexer, the sigmoid-routed expert
layer of which a chip holds a share, and the session lane that serves
it from per-user caches. Everything at toy widths on the CPU, seeded
weights, against the float32 reference ``ops/glm_reference.py``.

``index_topk`` is 16 here, so histories of 10 events sit under the
indexer's cut and histories of 40 well over it; a cache block is 4
events, so every extension below crosses block boundaries.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import glm_reference as ref
from predictionio_tpu.ops import mla, moe
from predictionio_tpu.ops import seqrec as S
from predictionio_tpu.ops.sessions import SESS_EVENTS, SessionTopK

N_ITEMS = 50
TOY = dict(
    block="glm_moe_dsa", rank=32, n_heads=4, norm="rmsnorm", norm_eps=1e-5,
    positions="rope", rope_theta=1e4, tied=False, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    index_n_heads=8, index_head_dim=8, index_topk=16, dense_width=48,
    n_experts=8, expert_width=16, experts_per_token=2, n_shared_experts=1,
    routed_scaling_factor=2.5, n_layers=3, n_dense_layers=1,
    experts_held=4, expert_share=1, seed=3)


def build(**over):
    params = S.SeqRecParams(**{**TOY, **over})
    spec = S.block_spec(params)
    theta = S.init_theta_device(N_ITEMS, params)
    cfg = dict(dataclasses.asdict(spec.glm), n_items=N_ITEMS)
    return params, spec, theta, cfg


def history(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, N_ITEMS, n).astype(np.int32)


def given_of(answer, pos: int):
    return {pos: {"selected": answer["selected"], "picks": answer["picks"]}}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 4 cache rows (the lane's are 256): toy histories
    then cross block boundaries."""
    from predictionio_tpu.ops import sessions

    monkeypatch.setattr(sessions, "SESS_BLOCK", 4)


def server(params, theta, histories, **kw) -> SessionTopK:
    st = mla.serving_theta(theta, mla.glm_spec(params))
    return SessionTopK(st["out_emb"][:N_ITEMS], st, params,
                       n_users=max(histories, default=0) + 1,
                       histories=histories, **{"audit": 4, **kw})


def last_answer(srv: SessionTopK, uid: int):
    return srv.audits(uid)[-1]


# -- the block against the reference ---------------------------------------------

@pytest.mark.parametrize("layers,dense", [(1, 1), (1, 0), (3, 1)],
                         ids=["dense-layer", "expert-layer", "1+2-layers"])
def test_full_forward_matches_reference(layers, dense):
    """``encoder_forward`` with the block (expanded keys and values)
    against the reference, every position of a history longer than
    the indexer's cut."""
    _, spec, theta, cfg = build(n_layers=layers, n_dense_layers=dense)
    ids = history(40)
    want = ref.forward(theta, ids, cfg, at=range(40))["hidden"]
    got, _ = S.encoder_forward(theta, ids[None], np.ones((1, 40), np.int32),
                               spec=spec)
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_reference_in_blocks_is_the_reference():
    _, _, theta, cfg = build()
    ids = history(40)
    whole = ref.forward(theta, ids, cfg, at=range(40))
    cut = ref.forward(theta, ids, cfg, at=range(40), q_block=16,
                      head_group=2)
    np.testing.assert_allclose(cut["hidden"], whole["hidden"], atol=1e-5)


def test_expanded_and_absorbed_attention_agree():
    """One layer's attention over the same selection both ways: keys
    and values expanded from the latents, and ``W_kvb`` absorbed into
    the query and the output over the latents as cached."""
    _, spec, theta, _ = build(n_layers=1)
    g = spec.glm
    x = jax.random.normal(jax.random.PRNGKey(1), (12, g.width))
    pos = jnp.arange(12)
    p = mla.project(theta, 0, mla.rms_norm(x, theta["l0_ln1_g"], 1e-5), pos,
                    g)
    causal = pos[:, None] >= pos[None, :]
    kv = (p["ckv"] @ theta["l0_wkv_b"]).reshape(12, g.n_heads, -1)
    s = (jnp.einsum("thd,shd->hts", p["q_nope"], kv[..., :g.d_nope])
         + jnp.einsum("thd,sd->hts", p["q_rope"], p["kr"])) * g.scale
    a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    expanded = jnp.einsum("hts,shd->thd", a, kv[..., g.d_nope:]).reshape(
        12, -1) @ theta["l0_wo"]
    lat, _ = mla.cache_rows(p, g, jnp.float32)
    qf = mla.absorbed_query(theta, 0, p, g)
    s2 = jnp.einsum("thc,sc->hts", qf, lat) * g.scale
    np.testing.assert_allclose(s2, s, atol=1e-5)
    a2 = jax.nn.softmax(jnp.where(causal[None], s2, -jnp.inf), axis=-1)
    absorbed = mla.absorbed_output(
        theta, 0, jnp.einsum("hts,sc->thc", a2, lat), g)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)
    assert lat.shape[1] == g.lat_width == 128   # 16 + 4, whole lane tiles


# -- the router and the shares -------------------------------------------------------

def test_router_bias_moves_the_choice_and_not_the_weight():
    h = jax.random.normal(jax.random.PRNGKey(0), (6, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 8)) / 6
    zero = jnp.zeros(8)
    scores, e0, g0 = moe.route_sigmoid(h, w, zero, 2, 2.5)
    # renormalised over the chosen, then scaled
    np.testing.assert_allclose(jnp.sum(g0, -1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        g0, 2.5 * jnp.take_along_axis(scores, e0, -1)
        / jnp.sum(jnp.take_along_axis(scores, e0, -1), -1, keepdims=True),
        rtol=1e-6)
    # a bias for expert 5 puts it among every token's picks; its
    # weight is still its own sigmoid score over the chosen scores
    bias = zero.at[5].set(10.0)
    _, e1, g1 = moe.route_sigmoid(h, w, bias, 2, 2.5)
    assert bool(jnp.all(jnp.any(e1 == 5, axis=-1)))
    chosen = jnp.take_along_axis(scores, e1, -1)
    np.testing.assert_allclose(
        g1, 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True), rtol=1e-6)
    assert float(jnp.max(g1)) <= 2.5


@pytest.mark.parametrize("held", [8, 4, 2], ids=lambda h: f"{8 // h}-shares")
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The routed parts of all shares plus the shared expert ONCE are
    the layer a chip holding all 8 experts computes, which is the
    plain every-expert-on-every-token form."""
    params, spec, theta, _ = build(n_layers=1, n_dense_layers=0,
                                   experts_held=8, expert_share=0)
    g = spec.glm
    h = jax.random.normal(jax.random.PRNGKey(2), (10, g.width))
    uncut, (experts, local, _) = mla.feed_forward(theta, 0, h, g)
    assert bool(jnp.all(local))
    _, _, weights = moe.route_sigmoid(h, theta["l0_router"],
                                      theta["l0_router_b"], 2, 2.5)

    def gated(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    shared = gated(h, theta["l0_ws_gate"], theta["l0_ws_up"],
                   theta["l0_ws_down"])
    plain = shared
    for e in range(8):
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), -1)
        plain = plain + gate[:, None] * gated(
            h, theta["l0_we_gate"][e], theta["l0_we_up"][e],
            theta["l0_we_down"][e])
    np.testing.assert_allclose(uncut, plain, atol=2e-5)
    total = shared
    picks = 0
    for share in range(8 // held):
        lo = share * held
        part, here, _ = moe.moe_ffn_share(
            h, experts, weights, theta["l0_we_gate"][lo:lo + held],
            theta["l0_we_up"][lo:lo + held],
            theta["l0_we_down"][lo:lo + held], first=lo)
        total = total + part
        picks += int(jnp.sum(here))
    assert picks == experts.size       # every pick lives on one share
    np.testing.assert_allclose(total, uncut, atol=2e-5)


# -- prefill and extension through the block cache -----------------------------------

@pytest.mark.parametrize("stored,steps", [
    (10, [1]), (10, [3, 1]), (10, [SESS_EVENTS, 5]), (40, [1]),
    (40, [3, 4, 1]), (0, [2, 3]), (14, [3])],
    ids=["under-cut-1", "under-cut-3+1", "crossing-the-cut", "over-cut-1",
         "over-cut-3+4+1", "no-history", "onto-the-cut"])
def test_prefill_then_extension_matches_the_full_forward(stored, steps):
    """A session prefilled from ``stored`` events, then extended by
    ``steps``: after every step the lane's scores for ALL items are
    those of the reference's full forward pass over the whole history
    so far, the reference taking the program's own cuts where its
    scores tie (none is further than 1e-5 from the reference's own)."""
    params, _, theta, cfg = build()
    events = history(stored + sum(steps), seed=stored + 1)
    srv = server(params, theta, {0: events[:stored]}, microbatch=False)
    at = stored
    for n in steps:
        new = events[at:at + n]
        at += n
        idx, scores = srv.sess_topk(0, new, 5)
        got = last_answer(srv, 0)
        assert got["length"] == at
        want = ref.forward(theta, events[:at], cfg,
                           given=given_of(got, at - 1))
        assert max(want["cuts"][at - 1].values()) < 1e-5
        # ... and every layer's state, the gates and the router's input
        # of the query's last event are the reference's too
        np.testing.assert_allclose(got["layers"], want["layers"][:, 0],
                                   atol=5e-5)
        mine = want["audit"][at - 1]
        np.testing.assert_allclose(got["gates"], mine["gates"], atol=1e-5)
        np.testing.assert_allclose(got["h2"], mine["h2"], atol=5e-5)
        # ... and the cache rows it wrote for that event
        wide = mine["lat"].shape[-1]
        np.testing.assert_allclose(got["lat"][:, :wide], mine["lat"],
                                   atol=5e-5)
        np.testing.assert_allclose(got["ik"], mine["ik"], atol=5e-5)
        np.testing.assert_allclose(got["scores"], want["scores"][0],
                                   atol=5e-5)
        # the answer: the best unseen items, by those scores
        seen = np.zeros(N_ITEMS, bool)
        seen[events[:at]] = True
        order = np.argsort(-np.where(seen, -np.inf, got["scores"]),
                           kind="stable")[:5]
        assert idx.tolist() == order.tolist()
        np.testing.assert_allclose(scores, got["scores"][order], atol=1e-6)
    np.testing.assert_array_equal(srv.session_events(0), events[:at])
    srv.close()


def test_prefill_chunks_match_one_pass():
    """A history of five chunks (chunk = ``index_topk`` = 16) against
    the reference: the last chunk's hidden state, left in the user's
    row of the store."""
    params, _, theta, cfg = build()
    events = history(70, seed=9)
    srv = server(params, theta, {0: events}, microbatch=False)
    srv.sess_topk(0, [], 5)
    want = ref.forward(theta, events, cfg)["hidden"][0]
    np.testing.assert_allclose(srv.last_hidden(0), want, atol=2e-5)
    srv.close()


# -- the cache manager ------------------------------------------------------------------

def test_cache_manager_allocates_grows_evicts_and_keeps_sessions_apart():
    params, _, theta, cfg = build()
    hist = {0: history(9, 1), 1: history(21, 2), 2: history(30, 3)}
    # 16 blocks of 4: the three histories need 3 + 6 + 8 = 17
    srv = server(params, theta, hist, pool_tokens=64, microbatch=False)
    g = srv._spec
    assert srv._pool["lat"][0].shape == (17, 4, g.lat_width)     # block 0: spare
    assert srv._pool["ik"][0].shape == (17, 4, g.idx_dim)        # two kinds, one table
    srv.sess_topk(0, [], 5)
    srv.sess_topk(1, [], 5)
    rep = srv.session_report()
    assert rep["sessions"] == 2 and rep["cacheTokens"] == (3 + 6) * 4
    b0, b1 = set(srv._sessions[0].blocks), set(srv._sessions[1].blocks)
    assert not b0 & b1 and 0 not in b0 | b1
    # a session grows a block at a time, and never into another's
    srv.sess_topk(0, history(4, 7), 5)
    assert len(srv._sessions[0].blocks) == 4
    assert not set(srv._sessions[0].blocks) & b1
    # the third does not fit: the one touched longest ago (1) goes
    from predictionio_tpu.utils import metrics

    before = metrics.SESS_EVICTIONS.value()
    srv.sess_topk(2, [], 5)
    assert metrics.SESS_EVICTIONS.value() == before + 1
    assert sorted(srv._sessions) == [0, 2]
    # ... and comes back by prefill, evicting in its turn, with the
    # same answer as a pool that never lost it
    idx, scores = srv.sess_topk(1, history(2, 8), 5)
    full = np.concatenate([hist[1], history(2, 8)])
    got = last_answer(srv, 1)
    want = ref.forward(theta, full, cfg, given=given_of(got, len(full) - 1))
    np.testing.assert_allclose(got["scores"], want["scores"][0], atol=5e-5)
    # no row of another session is ever read: poison every block that
    # is not session 1's and ask again
    mine = np.asarray(srv._sessions[1].blocks)
    poison = np.ones(17, bool)
    poison[mine] = False
    with srv._store_lock:
        srv._pool = {name: tuple(jnp.where(poison[:, None, None], 1e4, a)
                                 for a in rows)
                     for name, rows in srv._pool.items()}
    srv.sess_topk(1, [], 5)
    srv.sess_topk(1, history(1, 11), 5)
    full = np.concatenate([full, history(1, 11)])
    got = last_answer(srv, 1)
    want = ref.forward(theta, full, cfg, given=given_of(got, len(full) - 1))
    np.testing.assert_allclose(got["scores"], want["scores"][0], atol=5e-5)
    with pytest.raises(ValueError, match="past the lane's longest"):
        srv.open_session(0, history(600))
    srv.close()


@pytest.mark.parametrize("keep", [0, 2], ids=["no-audit", "audit-2"])
def test_audits_are_kept_only_when_asked_and_only_of_watched_users(keep):
    """A lane built without ``audit`` compiles programs that return
    none and keeps nothing; with ``audit=2`` it keeps its latest two
    dispatches', of the watched users' alone once some are watched.
    ``encode`` leaves no session, no block and no user row behind."""
    params, _, theta, cfg = build()
    hist = {0: history(9, 1), 1: history(21, 2)}
    srv = server(params, theta, hist, audit=keep, microbatch=False)
    for u in (0, 1, 0, 1):
        srv.sess_topk(u, history(2, 30 + u), 5)
    if not keep:
        assert srv.audits(0) == [] and srv.audits(1) == []
    else:
        # the latest two dispatches: one of each user
        assert [a["length"] for a in srv.audits(0)] == [13]
        assert [a["length"] for a in srv.audits(1)] == [25]
        srv.watch([1])
        assert srv.audits(1) == []
        for u in (1, 0, 0, 0):
            srv.sess_topk(u, history(1, 40 + u), 5)
        assert [a["length"] for a in srv.audits(1)] == [26]
        assert srv.audits(0) == []
    free, rows = len(srv._free), np.asarray(srv._X, np.float32).copy()
    h = srv.encode(history(30, 5))
    want = ref.forward(theta, history(30, 5), cfg)["hidden"][0]
    np.testing.assert_allclose(h, want, atol=2e-5)
    assert len(srv._free) == free and sorted(srv._sessions) == [0, 1]
    np.testing.assert_array_equal(np.asarray(srv._X, np.float32), rows)
    srv.close()
    assert srv._pool == {"lat": (), "ik": ()}


def test_ladder_is_complete_after_warm_up():
    """``warmup()`` compiles every program the lane can dispatch and
    prefills the stored sessions; after it a window of queries of every
    group size, event count and cached length compiles nothing."""
    from predictionio_tpu.utils import device_telemetry, metrics

    metrics.install_jit_compile_listener()
    params, _, theta, _ = build()
    hist = {u: history(5 + 9 * u, u) for u in range(6)}
    srv = server(params, theta, hist)
    plan = srv.aot_plan()
    sess = [e for e in plan if e[0] == "sess"]
    pre = [e for e in plan if e[0] == "sesspre"]
    assert {e[2] for e in sess} == {1, 4, 8}
    # from 8 selections of 16 to twice the longest history's bucket
    assert {e[3] for e in sess} == {e[2] for e in pre} == {128, 256}
    srv.warmup()
    assert srv.session_report()["sessions"] == 6
    import concurrent.futures as cf
    import time

    compiles = metrics.JIT_COMPILES.value()
    t0 = time.time()

    with cf.ThreadPoolExecutor(8) as pool:
        for n_users in (1, 2, 3, 6):
            futs = [pool.submit(srv.sess_topk, u, history(1 + u, 20 + u), 10)
                    for u in range(n_users)]
            for f in futs:
                assert len(f.result()[0]) == 10
    assert metrics.JIT_COMPILES.value() == compiles
    recs = [r for r in device_telemetry.recorder().snapshot(limit=1 << 20)
            if r["ts"] >= t0 and r["lane"] == "sess"]
    assert recs and all(r["aot"] == "hit" for r in recs)
    assert srv.stats()["sess"]["batchedQueries"] == 12
    srv.close()


def test_token_rows_are_counted_valid_and_padded():
    """``pio_sess_token_rows_total``: a dispatch is ``query bucket x
    SESS_EVENTS`` token rows, those with a new event and the padded
    ones the loop that cuts and attends never runs;
    ``session_report()`` says what share was skipped."""
    from predictionio_tpu.utils import metrics

    params, _, theta, _ = build()
    srv = server(params, theta, {u: history(10, u) for u in range(5)},
                 microbatch=False)
    before = {k: metrics.SESS_TOKEN_ROWS.value(kind=k)
              for k in ("valid", "padded")}
    # buckets 1, 4 (two queries, one without events) and 8 (five)
    srv.extend([(0, history(3, 7))], 8)
    srv.extend([(1, history(SESS_EVENTS, 8)), (2, history(0))], 8)
    srv.extend([(u, history(1 + u, 9)) for u in range(5)], 8)
    valid, total = 3 + SESS_EVENTS + 15, (1 + 4 + 8) * SESS_EVENTS
    got = {k: metrics.SESS_TOKEN_ROWS.value(kind=k) - before[k]
           for k in before}
    assert got == {"valid": valid, "padded": total - valid}
    report = srv.session_report()
    assert report["tokenRows"] == {"valid": valid, "padded": total - valid}
    assert report["skippedRowShare"] == pytest.approx(1 - valid / total)
    srv.close()


def test_two_queries_of_one_user_in_one_group_are_ordered():
    """Both land in one group of the lane; the first answers for its
    own prefix, the second for both, as two groups would."""
    from predictionio_tpu.ops.serving import _Pending
    from predictionio_tpu.ops.sessions import _dispatch_sess_group

    params, _, theta, _ = build()
    hist = {0: history(12, 1), 1: history(7, 2)}
    a, b, c = history(2, 5), history(3, 6), history(1, 7)

    def run(groups):
        srv = server(params, theta, hist, microbatch=False)
        out = []
        for payloads in groups:
            group = [_Pending(p, 5, 0.0, i, 0.0)
                     for i, p in enumerate(payloads)]
            for it in group:
                it.future.set_running_or_notify_cancel()
            _dispatch_sess_group(srv, group)
            for it in group:
                res, row = it.future.result()
                out.append(res.render(row, 5))
        events = srv.session_events(0)
        srv.close()
        return out, events

    one, ev1 = run([[(0, a), (1, c), (0, b)]])
    two, ev2 = run([[(0, a)], [(1, c)], [(0, b)]])
    np.testing.assert_array_equal(ev1, np.concatenate([hist[0], a, b]))
    np.testing.assert_array_equal(ev1, ev2)
    for (i1, s1), (i2, s2) in zip(one, two):
        assert i1.tolist() == i2.tolist()
        np.testing.assert_allclose(s1, s2, atol=1e-5)
    # the first query's answer does not know the second's events
    assert one[0][0].tolist() != one[2][0].tolist() \
        or not np.allclose(one[0][1], one[2][1])


# -- through the template: train, deploy, /queries.json, fold-in -----------------------

README_ENGINE_JSON = {
    "block": "glm_moe_dsa", "rank": 6144, "nHeads": 64, "nLayers": 6,
    "nDenseLayers": 1, "norm": "rmsnorm", "normEps": 1e-5,
    "positions": "rope", "ropeTheta": 1000000.0, "tied": False,
    "qLoraRank": 2048, "kvLoraRank": 512, "qkNopeHeadDim": 192,
    "qkRopeHeadDim": 64, "vHeadDim": 256, "indexNHeads": 32,
    "indexHeadDim": 128, "indexTopk": 2048, "denseWidth": 12288,
    "nExperts": 256, "expertWidth": 2048, "expertsPerToken": 8,
    "nSharedExperts": 1, "routedScalingFactor": 2.5, "expertsHeld": 16,
    "computeDtype": "bfloat16", "numSteps": 0, "seededWeights": True}


def test_a_prefill_on_the_way_is_a_dispatch_of_its_own_in_the_tiling():
    """A query of a user without a session, through the dispatcher:
    the prefill is a record of the dispatcher thread before the
    extend's, no stage holds another dispatch (what is left of either
    gap is never negative), the lane's bookkeeping is a named stage,
    and the query's life is on the record that answered it."""
    import time

    from predictionio_tpu.utils import device_telemetry

    params, _, theta, _ = build()
    rec = device_telemetry.recorder()
    srv = server(params, theta, {0: history(12, 1)}, microbatch=None)
    rec.reset()
    srv.sess_topk(0, history(3, 5), 5)
    time.sleep(0.05)            # the last record's stages land
    recs = rec.snapshot(10)[::-1]
    srv.close()
    assert [r["lane"] for r in recs] == ["sesspre", "sess"]
    assert len({r["dispatcher"] for r in recs}) == 1
    pre, ext = recs
    assert ext["otherUs"] >= 0 and ext["bookUs"] > 0
    # the extend's gap runs from the prefill's ready: its named parts
    # fit inside it, so none of them holds the prefill's own program
    assert sum(ext[f] for f in ("gapIdleUs", "gapWindowUs", "pickUs",
                                "formUs", "lockWaitUs", "otherUs")) \
        <= ext["gapUs"] + 1
    (life,) = ext["lives"]
    assert "lives" not in pre
    assert life["rounds"] == 1 and life["betweenUs"] == 0.0
    assert life["ridingUs"] >= pre["hostUs"] + ext["hostUs"]
    direct = server(params, theta, {0: history(12, 1)}, microbatch=False)
    rec.reset()
    direct.sess_topk(0, history(3, 5), 5)
    assert not any("lives" in r for r in rec.snapshot(10))
    direct.close()


def test_no_steps_without_seeded_weights_is_refused():
    """``numSteps: 0`` persists a model without weights only when the
    parameters say the seeded initial weights are to be served, and
    such a model is served only then."""
    from predictionio_tpu.controller import ComputeContext
    from predictionio_tpu.data.bimap import StringIndexBiMap
    from predictionio_tpu.templates.sequentialrec.engine import (
        PreparedSequences,
        SeqRecAlgorithm,
        SeqRecModel,
    )

    users = StringIndexBiMap.from_distinct(np.asarray(["u0", "u1"], object))
    items = StringIndexBiMap.from_distinct(
        np.asarray([f"i{i}" for i in range(N_ITEMS)], object))
    seqs = [history(5, 1), history(3, 2)]
    seen = {u: np.unique(q) for u, q in enumerate(seqs)}
    pd = PreparedSequences(users, items, None, seen, 16, seqs)
    params = S.SeqRecParams(**TOY, num_steps=0)
    with pytest.raises(ValueError, match="seededWeights"):
        SeqRecAlgorithm(params).train(ComputeContext(), pd)
    model = SeqRecModel(None, None, users, items, seen, {}, params, 16,
                        dict(enumerate(seqs)))
    with pytest.raises(ValueError, match="seededWeights"):
        model._make_server()
    seeded = SeqRecAlgorithm(dataclasses.replace(
        params, seeded_weights=True)).train(ComputeContext(), pd)
    assert seeded.theta == {} and sorted(seeded.histories) == [0, 1]


def test_engine_json_selects_the_block():
    import os

    from predictionio_tpu.controller.engine import params_from_dict

    got = params_from_dict(S.SeqRecParams, README_ENGINE_JSON)
    want = S.SeqRecParams(**S.GLM_5, n_layers=6, n_dense_layers=1,
                          experts_held=16, compute_dtype="bfloat16")
    assert S.block_spec(got) == S.block_spec(want)
    g = S.block_spec(got).glm
    assert (g.lat_width, g.held, g.first, g.n_experts) == (640, 16, 0, 256)
    readme = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "README.md")).read()
    block = readme[readme.index('{"algorithms": [{"name": "seqrec", '
                                '"params": {"block": "glm_moe_dsa"'):]
    block = json.loads(block[:block.index("```")])
    assert block["algorithms"][0]["params"] == README_ENGINE_JSON


@pytest.fixture()
def mem_storage():
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.storage import StorageConfig

    storage.reset(StorageConfig(
        sources={"M": {"type": "memory"}},
        repositories={"METADATA": "M", "EVENTDATA": "M", "MODELDATA": "M"}))
    yield
    storage.reset()


@pytest.mark.parametrize("num_steps", [0, 30], ids=["seeded", "trained"])
def test_pio_train_deploy_query_and_fold_in(mem_storage, monkeypatch,
                                            num_steps):
    """Events -> ``run_train`` -> ``QueryServer`` (``build_deployment``,
    ``SessionTopK``, warm-up with the resident sessions) -> session
    queries over ``/queries.json`` -> new events folded in by APPENDING
    to the session."""
    import datetime as dt
    import http.client
    import time

    from predictionio_tpu.controller import ComputeContext, EngineParams
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.templates.sequentialrec import (
        DataSourceParams,
        SeqPreparatorParams,
        engine_factory,
    )
    from predictionio_tpu.utils import device_telemetry
    from predictionio_tpu.workflow import QueryServer, ServerConfig, run_train
    from predictionio_tpu.workflow.create_workflow import (
        WorkflowConfig,
        new_engine_instance,
    )

    t_test = time.time()
    monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
    monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)

    def view(user, item, minute):
        return Event(event="view", entity_type="user", entity_id=user,
                     target_entity_type="item", target_entity_id=item,
                     event_time=t0 + dt.timedelta(minutes=minute))

    aid = storage.get_metadata_apps().insert(App(0, "glmapp"))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    events = []
    for u in range(12):
        start = int(rng.integers(0, 30))
        events += [view(f"u{u}", f"i{(start + j) % 30}", j)
                   for j in range(int(rng.integers(4, 24)))]
    le.insert_batch(events, aid)
    algo = S.SeqRecParams(**{**TOY, "max_seq_len": 32}, num_steps=num_steps,
                          seeded_weights=not num_steps, session_audit=2,
                          batch_size=4, n_negatives=8, learning_rate=0.01)
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="glmapp")),
        preparator_params=("", SeqPreparatorParams(max_seq_len=32)),
        algorithm_params_list=[("seqrec", algo)])
    factory = "predictionio_tpu.templates.sequentialrec:engine_factory"
    assert run_train(engine_factory(), params, new_engine_instance(
        WorkflowConfig(engine_factory=factory), params),
        ctx=ComputeContext()) is not None
    srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                   foldin=True)).start(undeploy_stale=False)
    try:
        def post(path, body):
            conn = http.client.HTTPConnection(*srv.address, timeout=60)
            conn.request("POST", path, body=json.dumps(body),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read().decode())
            conn.close()
            return resp.status, out

        def get(path):
            conn = http.client.HTTPConnection(*srv.address, timeout=60)
            conn.request("GET", path)
            out = json.loads(conn.getresponse().read().decode())
            conn.close()
            return out

        model = srv._deployment.models[0]
        lane = model.device_server()
        assert isinstance(lane, SessionTopK)
        assert bool(model.theta) == bool(num_steps)
        # warm-up built every stored session
        assert lane.session_report()["sessions"] == 12
        u3 = model.user_map["u3"]
        before = lane.session_events(u3)
        status, body = post("/queries.json", {"user": "u3", "num": 5})
        assert status == 200 and len(body["itemScores"]) == 5
        status, body2 = post("/queries.json", {
            "user": "u3", "items": ["i7", "i8", "nope"], "num": 5})
        assert status == 200 and len(body2["itemScores"]) == 5
        after = lane.session_events(u3)
        assert after.tolist() == before.tolist() + [
            model.item_map["i7"], model.item_map["i8"]]
        seen = {model.item_map.decode(np.asarray([i]))[0] for i in after}
        assert not seen & {s["item"] for s in body2["itemScores"]}
        # the answer is the reference's, on this model's own weights
        theta = {k: np.asarray(v, np.float32)
                 for k, v in lane.theta.items()}
        cfg = dict(dataclasses.asdict(lane._spec), n_items=30)
        got = last_answer(lane, u3)
        want = ref.forward(theta, after, cfg,
                           given=given_of(got, len(after) - 1))
        np.testing.assert_allclose(got["scores"], want["scores"][0],
                                   atol=1e-4)
        assert post("/queries.json", {"user": "newcomer", "items": ["i1"]}
                    )[1]["itemScores"] == []
        # item-only queries are the inherited similarity lane's
        assert len(post("/queries.json", {"items": ["i1"], "num": 3}
                        )[1]["itemScores"]) == 3
        stats = get("/stats.json")
        lanes = {b["batcher"]: b for b in stats["batchers"]}
        assert lanes["pio-microbatch-sess"]["batchedQueries"] >= 2
        store = stats["device"]["stores"][0]["store"]
        assert store["sessions"]["sessions"] == 12
        assert {"backbone", "sessionLatents", "sessionIndexKeys"} <= set(
            store["components"])
        recs = device_telemetry.recorder().snapshot(limit=1 << 20)
        assert [r for r in recs if r["lane"].startswith("sess")
                and r["ts"] >= t_test and r["aot"] != "hit"] == []
        # fold-in: two new events of a known user APPEND to the session
        le.insert_batch([view("u3", "i11", 100), view("u3", "i12", 101)],
                        aid)
        want_tail = [model.item_map["i11"], model.item_map["i12"]]
        deadline = time.time() + 30
        while time.time() < deadline:
            if lane.session_events(u3)[-2:].tolist() == want_tail:
                break
            time.sleep(0.1)
        assert lane.session_events(u3).tolist() == after.tolist() + want_tail
        status, body3 = post("/queries.json", {"user": "u3", "num": 5})
        assert "i11" not in {s["item"] for s in body3["itemScores"]}
        # two NEW users in one store capacity: each is folded in from
        # their own events (no row of the store is lent to both), and
        # their first query opens their session
        fresh = {"n1": ["i1", "i2", "i3"], "n2": ["i20", "i21", "i22",
                                                  "i23"]}
        le.insert_batch([view(u, i, 200 + j) for u, items in fresh.items()
                         for j, i in enumerate(items)], aid)
        deadline = time.time() + 120
        while time.time() < deadline and not all(
                u in model.user_map for u in fresh):
            time.sleep(0.1)
        for u, items in fresh.items():
            ux = model.user_map[u]
            mine = np.asarray([model.item_map[i] for i in items], np.int32)
            want = ref.forward(theta, mine, cfg)["hidden"][0]
            np.testing.assert_allclose(lane.last_hidden(ux), want,
                                       atol=1e-4)
            status, body4 = post("/queries.json", {"user": u, "num": 5})
            assert status == 200 and len(body4["itemScores"]) == 5
            assert lane.session_events(ux).tolist() == mine.tolist()
            assert not set(items) & {s["item"]
                                     for s in body4["itemScores"]}
    finally:
        srv.stop()

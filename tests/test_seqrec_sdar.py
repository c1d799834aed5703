"""SDAR's block (``sdar_moe``) on the sequence lane: grouped-query
attention with QK norms under the block-causal mask, the renormalised
expert layer, and the slate lane that serves it from per-user key/value
caches by diffusion over blocks, through a dispatcher that carries an
unfinished query to its next round. Everything at toy widths on the
CPU, seeded weights, against the float32 reference
``ops/sdar_reference.py``.

A block is 4 positions and a cache block 8 rows, so the histories below
cross both kinds of boundary, and every length ``mod 4`` occurs.
"""

import concurrent.futures as cf
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import attention, moe, sdar
from predictionio_tpu.ops import sdar_reference as ref
from predictionio_tpu.ops import seqrec as S
from predictionio_tpu.ops import serving, slates
from predictionio_tpu.ops.sessions import SessionTopK

N_ITEMS = 50            # item rows 0..49; the mask token's row is 50
TOY = dict(
    block="sdar_moe", rank=32, n_heads=4, n_kv_heads=2, head_dim=8,
    n_layers=2, norm="rmsnorm", norm_eps=1e-6, positions="rope",
    rope_theta=1e4, tied=False, n_experts=8, expert_width=16,
    experts_per_token=2, norm_topk_prob=True, num_steps=0,
    seeded_weights=True, max_seq_len=64, seed=3)


def build(**over):
    params = S.SeqRecParams(**{**TOY, **over})
    theta = S.init_theta(N_ITEMS, params)
    spec = sdar.sdar_spec(params)
    cfg = dict(n_layers=spec.n_layers, n_heads=spec.n_heads, n_kv=spec.n_kv,
               head_dim=spec.head_dim, norm_eps=spec.norm_eps,
               rope_theta=spec.rope_theta, block_len=spec.block_len,
               per_token=spec.per_token, steps=spec.steps,
               remasking=spec.remasking, threshold=spec.threshold,
               mask_id=spec.mask_row(N_ITEMS + 1), n_items=N_ITEMS)
    return params, theta, cfg


def history(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, N_ITEMS, n).astype(np.int32)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    from predictionio_tpu.ops import sessions

    monkeypatch.setattr(sessions, "SESS_BLOCK", 8)


def server(params, theta, histories, **kw) -> SessionTopK:
    st = sdar.serving_theta(theta, sdar.sdar_spec(params))
    return SessionTopK(st["out_emb"][:N_ITEMS], st, params,
                       n_users=max(histories, default=0) + 1,
                       histories=histories,
                       **{"audit": 16, "microbatch": False, **kw})


def cached_rows(srv: SessionTopK, uid: int):
    """``uid``'s committed key and value rows ``[layers, length,
    width]``, read out of the pool through its block table."""
    sess = srv._sessions[uid]
    rows = srv._phys(sess, np.arange(sess.length))
    return {n: np.stack([np.asarray(a).reshape(-1, a.shape[-1])[rows]
                         for a in srv._pool[n]]) for n in ("k", "v")}


# -- the block against the reference ---------------------------------------------

def test_the_mask_token_gets_a_row_of_its_own():
    params, theta, cfg = build()
    assert theta["item_emb"].shape == theta["out_emb"].shape == (51, 32)
    assert cfg["mask_id"] == 50
    named = S.SeqRecParams(**{**TOY, "mask_token": 7, "vocab_rows": 60})
    assert S.table_rows(N_ITEMS, named) == 60
    assert sdar.sdar_spec(named).mask_row(60) == 7


@pytest.mark.parametrize("n", [16, 23], ids=["whole-blocks", "with-tail"])
def test_full_forward_matches_reference(n):
    """``encoder_forward`` with the block (the whole forward under the
    block-causal mask, mask tokens in the unfinished block) against the
    reference, every position."""
    params, theta, cfg = build()
    ids = history(n)
    ids[-2:] = cfg["mask_id"]
    got, _ = S.encoder_forward(
        {k: jnp.asarray(v) for k, v in theta.items()}, ids[None],
        np.ones((1, n), np.int32), spec=S.block_spec(params))
    want = ref.forward(theta, ids, np.arange(n), cfg)["hidden"]
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_reference_continued_at_a_block_boundary_is_the_reference():
    _, theta, cfg = build()
    ids = history(23)
    whole = ref.forward(theta, ids, np.arange(23), cfg)
    cut = ref.forward(theta, ids, np.arange(23), cfg, q_block=8)
    for k in ("hidden", "k", "v", "gates"):
        np.testing.assert_allclose(cut[k], whole[k], atol=1e-5)


def test_gqa_is_mha_with_the_kv_heads_repeated():
    """The layer's attention (4 query heads on 2 key/value heads, whole
    row visible: one block) is ``mha_reference`` with each key/value
    head repeated for its group."""
    params, theta, cfg = build(block_length=16)
    spec = sdar.sdar_spec(params)
    th = {k: jnp.asarray(v) for k, v in theta.items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    pos = jnp.arange(16)[None]
    h = sdar.rms_norm(x, th["l0_ln1_g"], spec.norm_eps)
    q, k, v = sdar.project(th, 0, h[0], pos[0], spec)
    rep = lambda a: jnp.repeat(a, spec.group, axis=1)  # noqa: E731
    want = attention.mha_reference(
        q.transpose(1, 0, 2)[None], rep(k).transpose(1, 0, 2)[None],
        rep(v).transpose(1, 0, 2)[None], scale=spec.scale)
    want = x + (want[0].transpose(1, 0, 2).reshape(16, -1)
                @ th["l0_wo"])[None]
    # the layer up to its expert half: zero experts
    th0 = dict(th, l0_we_down=jnp.zeros_like(th["l0_we_down"]))
    got = sdar.sdar_layer(th0, 0, x, jnp.ones((1, 16), jnp.int32), pos,
                          spec)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("renorm", [False, True])
def test_route_with_and_without_renormalisation(renorm):
    """``moe.route`` against ``seqrec_reference.experts``'s routing
    (not renormalised), and the renormalised gates sum to one."""
    from predictionio_tpu.ops import seqrec_reference as olmoe_ref

    rng = np.random.default_rng(1)
    h = rng.normal(size=(12, 32)).astype(np.float32)
    w = rng.normal(size=(32, 8)).astype(np.float32) / 5
    _, probs, experts, weights = moe.route(h, w, 3, renorm=renorm)
    want_p = jax.nn.softmax(
        jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST), axis=-1)
    top_p, top_e = jax.lax.top_k(want_p, 3)
    np.testing.assert_array_equal(experts, top_e)
    if renorm:
        np.testing.assert_allclose(np.sum(weights, -1), 1.0, atol=1e-6)
        np.testing.assert_allclose(
            weights, top_p / np.sum(top_p, -1, keepdims=True), atol=1e-6)
    else:
        np.testing.assert_allclose(weights, top_p, atol=1e-6)
        e = [rng.normal(size=s).astype(np.float32) / 4
             for s in ((8, 32, 16), (8, 32, 16), (8, 16, 32))]
        y, _ = moe.moe_ffn(jnp.asarray(h), w, *e, k=3)
        np.testing.assert_allclose(
            y, olmoe_ref.experts(jnp.asarray(h), w, *e, 3)[0], atol=2e-5)


def test_paged_attention_kernel_against_its_gathered_form():
    """The Pallas kernel (interpret mode) reads each sequence's blocks
    where they lie and returns the online-softmax parts the gathered
    form returns; an empty cache gives no weight."""
    rng = np.random.default_rng(2)
    B, KV, RG, d, bs, nb = 3, 2, 8, 128, 16, 4
    pool_k = jnp.asarray(rng.normal(size=(9, bs, KV * d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(9, bs, KV * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, KV, RG, d)), jnp.float32)
    table = jnp.asarray([[3, 1, 7, 0], [2, 5, 0, 0], [0, 0, 0, 0]],
                        jnp.int32)
    length = jnp.asarray([55, 17, 0], jnp.int32)
    want = attention.paged_gqa_attention_xla(
        q, pool_k, pool_v, table, length, scale=0.1)
    got = attention.paged_gqa_attention(
        q, pool_k, pool_v, table, length, scale=0.1, interpret=True)
    for g, w in zip(got, want):
        # (the parts, normalised: the two forms may keep another maximum)
        np.testing.assert_allclose(g[2], w[2], atol=1e-5)
    norm = lambda p: p[0] / jnp.maximum(p[2], 1e-30)[..., None]  # noqa: E731
    np.testing.assert_allclose(norm(got)[:2], norm(want)[:2], atol=2e-5)
    np.testing.assert_allclose(got[1][:2], want[1][:2], atol=1e-5)
    assert float(jnp.max(got[2][2])) == 0.0


# -- the unmasking rules -------------------------------------------------------------

def both_rules(conf, tok, masked, quota, **over):
    """The device's rule and the reference's on one block."""
    params, _, cfg = build(**over)
    spec = sdar.sdar_spec(params)
    got = sdar.unmask_step(
        jnp.asarray([conf], jnp.float32), jnp.asarray([tok], jnp.int32),
        jnp.asarray([masked]), jnp.asarray([quota], jnp.int32), spec=spec)
    want = ref.unmask(conf, tok, masked, quota, spec.remasking,
                      spec.threshold)
    assert np.asarray(got)[0].tolist() == want.tolist()
    return want.tolist()


@pytest.mark.parametrize("conf,want", [
    ([0.2, 0.5, 0.3, 0.1], [False, True, False, False]),      # at least one
    ([0.95, 0.5, 0.92, 0.1], [True, False, True, False]),     # two confident
    ([0.95, 0.99, 0.92, 0.91], [True, True, True, True]),     # the block
], ids=["one", "two", "all-four"])
def test_dynamic_rule_unmasks_what_is_confident(conf, want):
    assert both_rules(conf, [1, 2, 3, 4], [True] * 4, 1,
                      remasking="low_confidence_dynamic") == want


@pytest.mark.parametrize("steps,quota,want", [
    (4, 1, [False, True, False, False]),
    (2, 2, [False, True, True, False]),
], ids=["4-steps", "2-steps"])
def test_static_rule_unmasks_its_quota(steps, quota, want):
    assert both_rules([0.2, 0.5, 0.3, 0.1], [1, 2, 3, 4], [True] * 4, quota,
                      denoising_steps=steps) == want


def test_rules_skip_unmasked_rows_and_never_repeat_an_item():
    # row 1 is unmasked already; rows 0 and 2 pick the same item: the
    # more confident takes it, the other waits for the next pass
    assert both_rules([0.6, 0.9, 0.7, 0.1], [5, 9, 5, 4],
                      [True, False, True, True], 2, denoising_steps=2) \
        == [False, False, True, True]
    assert both_rules([0.95, 0.9, 0.97, 0.1], [5, 9, 5, 4],
                      [True, False, True, True], 1,
                      remasking="low_confidence_dynamic") \
        == [False, False, True, False]


# -- the lane against the reference --------------------------------------------------

def check_round(a, theta, cfg, events):
    """One audited round against the reference: every pass's logits at
    every row, and the commit pass's cache rows."""
    B = cfg["block_len"]
    n = a["len0"]
    assert n % B == 0 and a["pos0"] >= n
    out = ref.forward(theta, events[:n], np.arange(n), cfg)
    past = ref.extend_past({"k": out["k"][:, :0], "v": out["v"][:, :0],
                            "pos": out["pos"][:0]}, out)
    seq = np.concatenate([a["tail"], np.asarray(a["taken"], np.int32)])
    for p in range(n, a["pos0"], B):
        blk = ref.forward(theta, seq[p - n:p - n + B], p + np.arange(B), cfg,
                          past)
        past = ref.extend_past(past, blk)
    rows = a["rows"]
    pos = a["pos0"] + np.arange(rows)
    for ps in a["passes"]:
        out = ref.forward(theta, ps["ids"][:rows], pos, cfg, past)
        np.testing.assert_allclose(
            ps["logits"][:rows], ref.logits_of(theta, out["hidden"])[
                :, :N_ITEMS], atol=5e-5)
    out = ref.forward(theta, a["tokens"][:rows], pos, cfg, past)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            a[name][:, :rows], out[name].reshape(cfg["n_layers"], rows, -1),
            atol=2e-5)
    np.testing.assert_array_equal(a["picks"][:, :rows], out["picks"])
    np.testing.assert_allclose(a["gates"][:, :rows], out["gates"], atol=1e-5)


@pytest.mark.parametrize("n_hist,new,num", [(13, 3, 6), (16, 0, 9),
                                            (22, 8, 5)],
                         ids=["tail-1-to-0", "no-tail", "tail-2-to-2"])
def test_prefill_commit_and_every_pass_match_reference(n_hist, new, num):
    """Prefill, then a query's new events committed, then every pass
    of its slate: the lane's logits and cache rows against the
    reference's full forward, and the slate against ``generate``."""
    params, theta, cfg = build()
    hist = {0: history(n_hist, 1)}
    srv = server(params, theta, hist)
    events = np.concatenate([hist[0], history(new, 2)])
    idx, conf = srv.sess_topk(0, events[n_hist:], num)
    want = ref.generate(theta, events, num, [], cfg)
    assert idx.tolist() == want["slate"].tolist()
    np.testing.assert_allclose(conf, want["conf"], atol=1e-5)
    assert len(set(idx.tolist())) == num and not set(idx) & set(events)
    assert ((conf > 0) & (conf <= 1)).all()
    n = len(events) // 4 * 4
    assert srv.cached_length(0) == n
    out = ref.forward(theta, events[:n], np.arange(n), cfg)
    got = cached_rows(srv, 0)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            got[name], out[name].reshape(cfg["n_layers"], n, -1), atol=2e-5)
    audits = srv.audits(0)
    rounds = [a for a in audits if a["kind"] == "round"]
    assert len(rounds) == -(-(len(events) - n + num) // 4)
    assert [a["round"] for a in rounds] == list(range(len(rounds)))
    for a in rounds:
        check_round(a, theta, cfg, events)
    commits = [a for a in audits if a["kind"] == "events"]
    assert sum(a["tokens"] for a in commits) == n - n_hist // 4 * 4
    for a in commits:
        at = a["len0"]
        np.testing.assert_allclose(
            a["k"][:, :a["tokens"]], out["k"][:, at:at + a["tokens"]].reshape(
                cfg["n_layers"], a["tokens"], -1), atol=2e-5)
    srv.close()


def test_tail_invariant_over_several_queries():
    """A history extended by 1-8 events over several queries leaves
    the same committed rows as the same events prefilled at once, and
    a length that is not whole blocks commits nothing of its last
    block."""
    params, theta, _ = build()
    hist = history(10, 4)
    srv = server(params, theta, {0: hist})
    srv._ensure_session(0, busy=())
    assert srv.cached_length(0) == 8 and srv.session_report()[
        "tailTokens"] == 2
    events = hist
    for j, m in enumerate([1, 8, 3, 2, 5, 4, 7, 6]):
        new = history(m, 10 + j)
        srv.sess_topk(0, new, 1 + j % 3)
        events = np.concatenate([events, new])
        assert srv.cached_length(0) == len(events) // 4 * 4
        np.testing.assert_array_equal(srv.session_events(0), events)
    assert srv.session_report()["scratchBlocks"] == 0
    once = server(params, theta, {0: events})
    once._ensure_session(0, busy=())
    assert once.cached_length(0) == srv.cached_length(0) == 44
    a, b = cached_rows(srv, 0), cached_rows(once, 0)
    for name in ("k", "v"):
        np.testing.assert_allclose(a[name], b[name], atol=2e-5)
    srv.close()
    once.close()


def test_both_rules_generate_the_references_slate():
    for over in (dict(remasking="low_confidence_dynamic",
                      confidence_threshold=0.08),
                 dict(denoising_steps=2)):
        params, theta, cfg = build(**over)
        hist = {0: history(14, 5)}
        srv = server(params, theta, hist)
        idx, conf = srv.sess_topk(0, [], 8)
        want = ref.generate(theta, hist[0], 8, [], cfg)
        assert idx.tolist() == want["slate"].tolist()
        np.testing.assert_allclose(conf, want["conf"], atol=1e-5)
        passes = [len(a["passes"]) for a in srv.audits(0)
                  if a["kind"] == "round"]
        assert passes == [len(b["passes"]) for b in want["blocks"]]
        assert max(passes) < 4      # more than one position a pass
        srv.close()


def test_ladder_is_complete_and_queries_share_rounds():
    """``warmup()`` compiles every program the lane can dispatch and
    prefills the stored sessions; after it concurrent queries of
    several users (and two of one user) compile nothing, share round
    dispatches, and each gets the slate it would get alone."""
    from predictionio_tpu.utils import metrics

    metrics.install_jit_compile_listener()
    params, theta, cfg = build()
    hist = {u: history(9 + 7 * u, u) for u in range(5)}
    srv = server(params, theta, hist, microbatch=None)
    plan = srv.aot_plan()
    assert {e[0] for e in plan} >= {"sess", "sessev", "sesspre"}
    assert {e[3] for e in plan if e[0] == "sess"} \
        == {e[2] for e in plan if e[0] == "sesspre"} == {64, 128}
    srv.warmup()
    assert srv.session_report()["sessions"] == 5
    compiles = metrics.JIT_COMPILES.value()
    rounds0 = metrics.SLATE_ROUNDS.value()
    carried0 = metrics.SLATE_CARRIED.value()
    asks = [(u, history(1 + u, 30 + u), 4 + 3 * u) for u in range(5)]
    asks.append((0, history(2, 40), 7))
    with cf.ThreadPoolExecutor(8) as pool:
        futs = [pool.submit(srv.sess_topk, *a) for a in asks]
        got = [f.result() for f in futs]
    assert metrics.JIT_COMPILES.value() == compiles
    for (u, new, num), (idx, conf) in zip(asks[1:5], got[1:5]):
        want = ref.generate(theta, np.concatenate([hist[u], new]), num, [],
                            cfg)
        assert idx.tolist() == want["slate"].tolist()
    # user 0's two queries: one after the other, in either order
    ev = srv.session_events(0)[len(hist[0]):].tolist()
    a, b = asks[0][1].tolist(), asks[5][1].tolist()
    assert ev in (a + b, b + a)
    stats = srv.stats()["sess"]
    rounds = metrics.SLATE_ROUNDS.value() - rounds0
    # users 1-4: ceil((tail + slate) / 4) rounds each; user 0's two
    # queries as many as their order makes
    assert rounds >= sum(-(-((len(hist[u]) + len(n)) % 4 + k) // 4)
                         for u, n, k in asks[1:5]) + 2 + 2
    assert stats["dispatches"] < rounds       # rounds shared dispatches
    assert metrics.SLATE_CARRIED.value() > carried0
    assert srv.session_report()["scratchBlocks"] == 0
    srv.close()


# -- the dispatcher's carry-over ------------------------------------------------------

class _FakeServer:
    pass


def _lanes(rounds_needed):
    """A dispatcher with a lane whose queries take ``payload`` rounds
    (handed back until done) and a plain one-dispatch lane; a log of
    what each dispatch held."""
    srv = _FakeServer()
    log = []
    srv.round_started = threading.Event()

    def slow(_, group):
        log.append(("slow", [it.payload["name"] for it in group]))
        srv.round_started.set()
        time.sleep(0.05)
        back = []
        for it in group:
            it.payload["left"] -= 1
            if it.payload["left"]:
                back.append(it)
        done = [it for it in group if not it.payload["left"]]
        serving._deliver(done, np.zeros((len(done), 1), np.int32),
                         np.ones((len(done), 1), np.float32))
        return back or None

    def quick(_, group):
        log.append(("quick", [it.payload for it in group]))
        serving._deliver(group, np.zeros((len(group), 1), np.int32),
                         np.ones((len(group), 1), np.float32))

    d = serving.BatchDispatcher(srv, window=0.02)
    return srv, d, d.add_lane("slow", 2, slow), d.add_lane("quick", 8,
                                                           quick), log


def test_carried_queries_come_first_and_finish_at_their_own_end():
    srv, d, slow, quick, log = _lanes(None)
    done = {}

    def ask(name, left):
        fut = slow.submit_async({"name": name, "left": left}, 1)
        fut.add_done_callback(
            lambda f: done.setdefault(name, (len(log), time.monotonic())))
        return fut

    a, b = ask("a", 3), ask("b", 1)     # a full group: dispatched at once
    assert srv.round_started.wait(5)    # inside the first round
    c = ask("c", 1)
    for f in (a, b, c):
        f.result(timeout=5)
    d.close()
    groups = [g for lane, g in log if lane == "slow"]
    assert groups[0] == ["a", "b"]
    # a is handed back and rides FIRST, beside the new arrival
    assert groups[1] == ["a", "c"] and groups[2] == ["a"]
    # each is delivered at its own last round
    assert done["b"][0] == 1 and done["c"][0] == 2 and done["a"][0] == 3
    st = slow.stats()
    assert st["dispatches"] == 3 and st["batchedQueries"] == 5
    assert st["queueDepth"] == 0


def test_another_lane_is_served_between_two_rounds():
    srv, d, slow, quick, log = _lanes(None)
    a = slow.submit_async({"name": "a", "left": 4}, 1)
    assert srv.round_started.wait(5)    # a's first round is running
    q = quick.submit_async("q", 1, window=0.0)
    q.result(timeout=5)
    a.result(timeout=5)
    d.close()
    order = [lane for lane, _ in log]
    # the quick lane's query waited one round, not all four
    assert order == ["slow", "quick", "slow", "slow", "slow"]


def test_every_other_lane_gets_one_turn_between_two_rounds():
    """Two plain lanes with queries waiting while a long query runs:
    each is dispatched once between two rounds, through the
    dispatcher's one loop (no dispatch inside another)."""
    srv, d, slow, quick, log = _lanes(None)
    depth, deepest = [0], [0]

    def other(_, group):
        depth[0] += 1
        deepest[0] = max(deepest[0], depth[0])
        log.append(("other", [it.payload for it in group]))
        serving._deliver(group, np.zeros((len(group), 1), np.int32),
                         np.ones((len(group), 1), np.float32))
        depth[0] -= 1

    third = d.add_lane("other", 8, other)
    plain = slow.dispatch_fn

    def nested(srv_, group):
        depth[0] += 1
        deepest[0] = max(deepest[0], depth[0])
        try:
            return plain(srv_, group)
        finally:
            depth[0] -= 1

    slow.dispatch_fn = nested
    a = slow.submit_async({"name": "a", "left": 4}, 1)
    assert srv.round_started.wait(5)    # a's first round is running
    futs = [quick.submit_async("q", 1, window=0.0),
            third.submit_async("o", 1, window=0.0)]
    for f in futs + [a]:
        f.result(timeout=5)
    d.close()
    # both waited one round, not all four; no dispatch inside another
    assert [lane for lane, _ in log] == ["slow", "quick", "other", "slow",
                                         "slow", "slow"]
    assert deepest[0] == 1 and not d._owed


def test_the_waiter_holds_its_own_future_however_fast_the_lane_is():
    """A lane that hands a query back wraps the queued item's future;
    the waiter must hold the future it was given at enqueue, even when
    the first round ends before ``submit_async`` returns."""
    srv = _FakeServer()
    d = serving.BatchDispatcher(srv, window=0.0)
    left = {}

    def twice(_, group):
        back = [it for it in group if left.setdefault(it.payload, 2) > 1]
        for it in group:
            left[it.payload] -= 1
        done = [it for it in group if it not in back]
        serving._deliver(done, np.zeros((len(done), 1), np.int32),
                         np.ones((len(done), 1), np.float32))
        return back or None

    lane = d.add_lane("twice", 1, twice)
    real_set = d._wake.set

    def set_and_wait():         # let the dispatcher run a round first
        real_set()
        time.sleep(0.02)

    d._wake.set = set_and_wait
    futs = [lane.submit_async(i, 1) for i in range(3)]
    d._wake.set = real_set
    assert all(type(f) is cf.Future for f in futs)
    for f in futs:
        f.result(timeout=5)
    d.close()


def test_a_failing_round_fails_its_group_and_nothing_else():
    srv = _FakeServer()
    calls = []

    def boom(_, group):
        calls.append(len(group))
        if len(calls) == 2:
            raise RuntimeError("round failed")
        return list(group)

    d = serving.BatchDispatcher(srv, window=0.001)
    lane = d.add_lane("boom", 4, boom)
    fut = lane.submit_async("x", 1)
    with pytest.raises(RuntimeError, match="round failed"):
        fut.result(timeout=5)
    d.close()
    assert calls == [1, 1]


def test_a_lane_that_hands_nothing_back_records_the_same_stamps():
    """The ``users`` lane of a plain store, before and after a slate
    lane exists in the process: its dispatch records carry the same
    keys (no new stage) and the dispatcher wraps no future."""
    from predictionio_tpu.utils import device_telemetry

    rng = np.random.default_rng(0)
    srv = serving.DeviceTopK(rng.normal(size=(6, 8)).astype(np.float32),
                             rng.normal(size=(20, 8)).astype(np.float32),
                             {0: np.asarray([1, 2])})
    device_telemetry.recorder().clear() if hasattr(
        device_telemetry.recorder(), "clear") else None
    srv.user_topk(1, 3)
    with cf.ThreadPoolExecutor(4) as pool:
        list(pool.map(lambda u: srv.user_topk(u, 3), range(4)))
    recs = [r for r in device_telemetry.recorder().snapshot()
            if r.get("lane") == "users"] if hasattr(
        device_telemetry.recorder(), "snapshot") else []
    for r in recs:
        assert not {"passes", "carried", "tokensUnmasked"} & set(r)
    lane = srv._dispatcher._lanes[0]
    fut = lane.submit_async(2, 3)
    fut.result(timeout=5)
    assert type(fut) is cf.Future and not srv._dispatcher._owed
    srv.close()


def test_a_slates_rounds_are_its_life_and_the_lanes_stages_tile_them():
    """One query through the dispatcher on a lane that has prefilled
    nothing: the prefill, the events' commit and every round are
    dispatches of the ONE dispatcher thread, each record's gap is named
    stages plus a remainder that is never negative, and the query's
    life counts its rounds; a direct caller leaves no life."""
    from predictionio_tpu.utils import device_telemetry

    params, theta, cfg = build()
    hist = {0: history(13, 1)}
    rec = device_telemetry.recorder()
    srv = server(params, theta, hist, microbatch=None)
    rec.reset()
    idx, _ = srv.sess_topk(0, history(5, 2), 10)
    time.sleep(0.05)            # the last record's stages land
    recs = rec.snapshot(100)[::-1]
    srv.close()
    assert len(idx) == 10
    assert len({r["dispatcher"] for r in recs}) == 1
    lanes = [r["lane"] for r in recs]
    # 13 + 5 events: 16 committed (12 by the prefill, 4 by the query),
    # a tail of 2 and a slate of 10 in blocks of 4: three rounds
    assert lanes == ["sesspre", "sessev", "sess", "sess", "sess"]
    for r in recs[1:]:
        assert r["otherUs"] >= 0 and r["bookUs"] > 0 and r["pickUs"] >= 0
        named = sum(r[f] for f in ("gapIdleUs", "gapWindowUs", "pickUs",
                                   "formUs", "lockWaitUs", "otherUs"))
        assert named <= r["gapUs"] + 1
    (life,) = recs[-1]["lives"]
    assert not any("lives" in r for r in recs[:-1])
    assert life["rounds"] == 3 and life["betweenUs"] > 0
    assert life["ridingUs"] >= sum(r["hostUs"] for r in recs)
    # the oldest's age at the last round holds the earlier rounds
    assert recs[-1]["queueWaitUs"] > life["firstWaitUs"] \
        + sum(r["hostUs"] for r in recs[:-1])
    direct = server(params, theta, hist)
    rec.reset()
    direct.sess_topk(0, history(5, 2), 10)
    assert [r["lane"] for r in rec.snapshot(100)[::-1]] == lanes
    assert not any("lives" in r or "otherUs" in r
                   for r in rec.snapshot(100))
    direct.close()


# -- training is refused ---------------------------------------------------------------

def test_train_seqrec_refuses_the_block():
    params = S.SeqRecParams(**{**TOY, "num_steps": 3})
    bucket = S.bucket_sequences([history(9, 1), history(7, 2)], max_len=16)
    with pytest.raises(ValueError, match="masked-block diffusion"):
        S.train_seqrec(bucket, N_ITEMS, params)


README_ENGINE_JSON = {
    "block": "sdar_moe", "rank": 2048, "nHeads": 32, "nKvHeads": 4,
    "headDim": 128, "nLayers": 6, "norm": "rmsnorm", "normEps": 1e-6,
    "positions": "rope", "ropeTheta": 1000000.0, "tied": False,
    "vocabRows": 151936, "nExperts": 128, "expertWidth": 768,
    "expertsPerToken": 8, "normTopkProb": True, "maskToken": 151669,
    "blockLength": 4, "denoisingSteps": 4,
    "remasking": "low_confidence_static", "confidenceThreshold": 0.9,
    "computeDtype": "bfloat16", "sessionPoolTokens": 360448,
    "numSteps": 0, "seededWeights": True}


def test_engine_json_selects_the_block():
    import os

    from predictionio_tpu.controller.engine import params_from_dict

    got = params_from_dict(S.SeqRecParams, README_ENGINE_JSON)
    want = S.SeqRecParams(**S.SDAR_30B_A3B, n_layers=6,
                          compute_dtype="bfloat16")
    assert S.block_spec(got) == S.block_spec(want)
    spec = S.block_spec(got).sdar
    assert (spec.kv_width, spec.group, spec.block_len, spec.steps) \
        == (512, 8, 4, 4)
    readme = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "README.md")).read()
    block = readme[readme.index('{"algorithms": [{"name": "seqrec", '
                                '"params": {"block": "sdar_moe"'):]
    block = json.loads(block[:block.index("```")])
    assert block["algorithms"][0]["params"] == README_ENGINE_JSON


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


def test_pio_train_deploy_and_slate_queries(mem_storage, monkeypatch):
    """Events -> ``run_train`` (numSteps 0, seededWeights: nothing is
    trained) -> ``QueryServer`` (``build_deployment``, ``SessionTopK``
    with the slate backbone, warm-up with the resident sessions) ->
    slate queries over ``/queries.json``: ``num`` is the slate's
    length, concurrent queries share rounds, the flight records name
    the rounds."""
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

    t_test = time.time()
    monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)

    def view(user, item, minute):
        return Event(event="view", entity_type="user", entity_id=user,
                     target_entity_type="item", target_entity_id=item,
                     event_time=t0 + dt.timedelta(minutes=minute))

    aid = storage.get_metadata_apps().insert(App(0, "sdarapp"))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    events = []
    for u in range(8):
        start = int(rng.integers(0, 30))
        events += [view(f"u{u}", f"i{(start + j) % 40}", j)
                   for j in range(int(rng.integers(5, 24)))]
    le.insert_batch(events, aid)
    algo = S.SeqRecParams(**{**TOY, "max_seq_len": 32}, session_audit=4)
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="sdarapp")),
        preparator_params=("", SeqPreparatorParams(max_seq_len=32)),
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

        def get(path):
            conn = http.client.HTTPConnection(*srv.address, timeout=60)
            conn.request("GET", path)
            out = json.loads(conn.getresponse().read().decode())
            conn.close()
            return out

        model = srv._deployment.models[0]
        lane = model.device_server()
        assert isinstance(lane, SessionTopK)
        assert isinstance(lane._bb, slates.SdarBackbone)
        assert lane.session_report()["sessions"] == 8
        u3 = model.user_map["u3"]
        before = lane.session_events(u3)
        status, out = post({"user": "u3", "items": ["i1", "i2", "i3"],
                            "num": 6})
        assert status == 200 and len(out["itemScores"]) == 6
        items = [s["item"] for s in out["itemScores"]]
        assert len(set(items)) == 6
        assert all(0 < s["score"] <= 1 for s in out["itemScores"])
        after = lane.session_events(u3)
        assert after.tolist() == before.tolist() + [
            model.item_map[i] for i in ("i1", "i2", "i3")]
        seen = {model.item_map.decode([i])[0] for i in after}
        assert not seen & set(items)
        # the same slate the reference generates from the lane's own
        # weights for the events the lane holds
        theta = {k: np.asarray(v, np.float32)
                 for k, v in lane.theta.items()}
        n_rows = theta["item_emb"].shape[0]
        theta["out_emb"] = np.concatenate(
            [theta["out_emb"], np.zeros((n_rows - len(theta["out_emb"]),
                                         32), np.float32)])
        spec = lane._spec
        cfg = dict(n_layers=spec.n_layers, n_heads=spec.n_heads,
                   n_kv=spec.n_kv, head_dim=spec.head_dim,
                   norm_eps=spec.norm_eps, rope_theta=spec.rope_theta,
                   block_len=4, per_token=spec.per_token, steps=4,
                   remasking=spec.remasking, threshold=spec.threshold,
                   mask_id=spec.mask_row(n_rows), n_items=lane.n_items)
        want = ref.generate(theta, after, 6, [], cfg)
        assert [model.item_map[i] for i in items] == want["slate"].tolist()
        # concurrent slates of several users share round dispatches
        with cf.ThreadPoolExecutor(6) as pool:
            outs = list(pool.map(
                lambda u: post({"user": f"u{u}", "items": [f"i{u}"],
                                "num": 4 + u}), range(6)))
        assert all(s == 200 and len(o["itemScores"]) == 4 + u
                   for u, (s, o) in enumerate(outs))
        recs = get("/dispatches.json?limit=200")["dispatches"]
        # (the recorder is the process's: this test's records only)
        rounds = [r for r in recs if r["lane"] == "sess"
                  and r["ts"] >= t_test]
        assert rounds and all(r["aot"] == "hit" for r in rounds)
        assert all({"passes", "tokensUnmasked", "carried", "lengthBucket"}
                   <= set(r) for r in rounds)
        assert any(r["batch"] > 1 for r in rounds)
        stats = get("/stats.json")
        assert any(b["batcher"] == "pio-microbatch-sess"
                   for b in stats["batchers"])
        sessions = stats["device"]["stores"][0]["store"]["sessions"]
        assert sessions["blockLength"] == 4 and sessions["scratchBlocks"] == 0
        # a slate longer than the lane decodes is this query's error
        status, _ = post({"user": "u1", "num": 40})
        assert status != 200
        status, out = post({"user": "u1", "num": 3})
        assert status == 200 and len(out["itemScores"]) == 3
    finally:
        srv.stop()

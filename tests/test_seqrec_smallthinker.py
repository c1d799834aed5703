"""SmallThinker's block (``smallthinker``) on the sequence lane: one
global NoPE layer in four beside three rotary window layers, groups of
query heads on shared key/value heads (7 on 1 among them), ReGLU
experts routed from the ATTENTION's input, and the session lane that
serves it from per-user key/value caches with a block table a LAYER
KIND. Everything at toy widths on the CPU, seeded weights, against the
float32 reference ``ops/smallthinker_reference.py``.

A cache block is 4 rows and the window 8 positions (two periods of the
layout: 8 layers), so the sessions below sit under the window, cross it
inside one query's events and grow several windows long.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import attention, moe, smallthinker
from predictionio_tpu.ops import seqrec as S
from predictionio_tpu.ops import smallthinker_reference as ref
from predictionio_tpu.ops import sessions
from predictionio_tpu.ops.sessions import SessionTopK, SmallThinkerBackbone

N_ITEMS = 50
WINDOW = 8
TOY = dict(
    block="smallthinker", rank=64, n_heads=4, n_kv_heads=2, head_dim=16,
    n_layers=8, norm="rmsnorm", norm_eps=1e-6, positions="rope",
    rope_theta=1.5e6, tied=False, n_experts=8, expert_width=32,
    experts_per_token=2, norm_topk_prob=True, sliding_window_size=WINDOW,
    sliding_window_layout=(0, 1, 1, 1) * 2, num_steps=0,
    seeded_weights=True, max_seq_len=128, seed=3)
HEADS = {"4-on-2": {}, "7-on-1": dict(n_heads=7, n_kv_heads=1, rank=56)}


def build(**over):
    params = S.SeqRecParams(**{**TOY, **over})
    theta = S.init_theta(N_ITEMS, params)
    spec = smallthinker.swa_spec(params)
    cfg = dict(n_layers=spec.n_layers, n_heads=spec.n_heads, n_kv=spec.n_kv,
               head_dim=spec.head_dim, window=spec.window,
               pattern=spec.pattern, per_token=spec.per_token,
               norm_eps=spec.norm_eps, rope_theta=spec.rope_theta,
               n_items=N_ITEMS)
    return params, theta, cfg


def history(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, N_ITEMS, n).astype(np.int32)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(sessions, "SESS_BLOCK", 4)


def server(params, theta, histories, **kw) -> SessionTopK:
    st = smallthinker.serving_theta(theta, smallthinker.swa_spec(params))
    return SessionTopK(st["out_emb"][:N_ITEMS], st, params,
                       n_users=max(histories, default=0) + 1,
                       histories=histories,
                       **{"audit": 16, "microbatch": False, **kw})


def agrees(srv, theta, cfg, uid, events, atol=3e-5):
    """The lane's latest answer for ``uid`` against the reference's
    full forward over ``events``: every item's score, every layer's
    residual stream, the first position each layer read."""
    got = srv.audits(uid)[-1]
    want = ref.forward(theta, np.asarray(events), cfg, at=[len(events) - 1],
                       q_block=16)
    assert got["length"] == len(events)
    np.testing.assert_allclose(got["scores"], want["scores"][0], atol=atol)
    np.testing.assert_allclose(got["layers"], want["layers"][:, 0],
                               atol=atol)
    np.testing.assert_array_equal(got["first"], want["first"][:, 0])
    return got, want


# -- the block against the reference ---------------------------------------------

@pytest.mark.parametrize("heads", HEADS)
def test_full_forward_matches_reference(heads):
    params, theta, cfg = build(**HEADS[heads])
    ids = history(40)
    got, _ = S.encoder_forward(
        {k: jnp.asarray(v) for k, v in theta.items()}, ids[None],
        np.ones((1, 40), np.int32), spec=S.block_spec(params))
    want = ref.forward(theta, ids, cfg, q_block=8)
    with jax.default_matmul_precision("highest"):
        scores = got[0] @ jnp.asarray(theta["out_emb"][:N_ITEMS]).T
    np.testing.assert_allclose(scores, want["scores"], atol=3e-5)


def test_the_pattern_names_the_kinds_and_the_parameters_have_no_qk_norm():
    params, theta, _ = build()
    spec = smallthinker.swa_spec(params)
    assert spec.kinds == (("global", (0, 4), None),
                          ("window", (1, 2, 3, 5, 6, 7), WINDOW))
    assert [spec.kind_of(i) for i in range(8)] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert not [k for k in theta if k.endswith(("qn_g", "kn_g"))]
    assert theta["l0_router"].shape == (64, 8)
    whole = S.SeqRecParams(**S.SMALLTHINKER_21B_A3B, n_layers=52)
    assert smallthinker.swa_spec(whole).kinds[0][1] == tuple(range(0, 52, 4))
    assert smallthinker.swa_spec(whole).group == 7


@pytest.mark.parametrize("over, match", [
    (dict(norm="layernorm"), "norm rmsnorm"),
    (dict(positions="learned"), "positions rope"),
    (dict(tied=True), "untied"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(sliding_window_layout=(0, 1, 1)), "names 3 of 8"),
    (dict(sliding_window_layout=(0, 1, 2, 1) * 2), "kinds"),
    (dict(sliding_window_size=0), "needs sliding_window_size"),
    (dict(n_heads=6, n_kv_heads=4), "evenly"),
], ids=["layernorm", "learned", "tied", "no-renorm", "short-layout",
        "bad-layout", "no-window", "heads"])
def test_the_block_refuses_every_combination_but_its_own(over, match):
    with pytest.raises(ValueError, match=match):
        S.block_spec(S.SeqRecParams(**{**TOY, **over}))


def test_train_seqrec_refuses_the_block():
    params = S.SeqRecParams(**{**TOY, "num_steps": 3})
    bucket = S.bucket_sequences([history(9, 1), history(7, 2)], max_len=16)
    with pytest.raises(ValueError, match="not trained here"):
        S.train_seqrec(bucket, N_ITEMS, params)


@pytest.mark.parametrize("route_from", ["attention-input", "h2-control"])
def test_the_router_reads_the_attentions_input(route_from):
    """Picks and weights of every layer against a float64 softmax over
    the PICKED logits of the attention's input ``h``; a control that
    routes from ``h2`` (the expert layer's own input) is caught."""
    params, theta, cfg = build()
    ids = history(20)
    out = ref.forward(theta, ids, cfg, q_block=8, control=None
                      if route_from == "attention-input"
                      else "router_after_attention")
    worst = 0.0
    for i in range(cfg["n_layers"]):
        logits = np.asarray(out["h"][i], np.float64) \
            @ np.asarray(theta[f"l{i}_router"], np.float64)
        mine = np.argsort(-logits, axis=-1)[:, :2]
        same = np.sort(mine, -1) == np.sort(out["picks"][i], -1)
        z = np.take_along_axis(logits, out["picks"][i], -1)
        want = np.exp(z - z.max(-1, keepdims=True))
        want /= want.sum(-1, keepdims=True)
        worst = max(worst, float(np.abs(out["gates"][i] - want).max()),
                    float(1 - same.mean()))
    if route_from == "attention-input":
        assert worst < 1e-5
    else:
        assert worst > 1e-2


def test_relu_is_the_experts_activation_and_silu_is_not():
    """``moe_ffn_share`` with ``activation="relu"`` is the dense form
    with it, routed from another input than the experts'; SiLU in its
    place moves the result."""
    rng = np.random.default_rng(1)
    h2, h = (jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
             for _ in range(2))
    w = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32)
    e = [jnp.asarray(rng.normal(size=s) / 4, jnp.float32)
         for s in ((6, 16, 8), (6, 16, 8), (6, 8, 16))]
    _, _, picks, weights = moe.route(h, w, 2, renorm=True)
    got, local, gs = moe.moe_ffn_share(h2, picks, weights, *e, first=0,
                                       activation="relu")
    want = moe.moe_ffn_dense(h2, w, *e, k=2, renorm=True,
                             activation="relu", router_input=h)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert bool(local.all()) and int(gs.sum()) == 24
    silu, _, _ = moe.moe_ffn_share(h2, picks, weights, *e, first=0)
    assert float(jnp.abs(silu - want).max()) > 1e-2


# -- the paged kernel with bounds -------------------------------------------------

@pytest.mark.parametrize("rows", [1, 2, 8], ids=["RG7", "RG14", "RG56"])
def test_paged_attention_with_bounds(rows):
    """Groups of 7 query heads: ``RG`` = 7, 14, 56 rows a key/value
    head. The Pallas kernel (interpret mode) with a table that starts
    at ``base`` and a first visible position a row, against its
    gathered form and against ``mha_reference`` under the window mask
    over the sequence's rows laid out plainly."""
    rng = np.random.default_rng(rows)
    B, KV, G, d, bs, nb, W = 3, 2, 7, 128, 16, 4, 24
    RG = rows * G
    pool_k = jnp.asarray(rng.normal(size=(9, bs, KV * d)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(9, bs, KV * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, KV, RG, d)), jnp.float32)
    table = jnp.asarray([[3, 1, 7, 0], [2, 5, 6, 8], [0, 0, 0, 0]],
                        jnp.int32)
    base = jnp.asarray([32, 0, 0], jnp.int32)
    length = jnp.asarray([71, 60, 0], jnp.int32)
    # token row r of sequence b sits at length[b] + r
    pos = length[:, None] + jnp.arange(rows)[None, :]
    first = jnp.repeat(jnp.maximum(pos - W + 1, 0), G, axis=1)
    kw = dict(scale=0.1, base=base, first=first)
    want = attention.paged_gqa_attention_xla(q, pool_k, pool_v, table,
                                             length, **kw)
    got = attention.paged_gqa_attention(q, pool_k, pool_v, table, length,
                                        interpret=True, **kw)
    assert got[0].shape == (B, KV, RG, d) and got[1].shape == (B, KV, RG)
    norm = lambda p: p[0] / jnp.maximum(p[2], 1e-30)[..., None]  # noqa: E731
    np.testing.assert_allclose(got[2], want[2], atol=1e-5)
    np.testing.assert_allclose(norm(got)[:2], norm(want)[:2], atol=2e-5)
    assert float(jnp.max(got[2][2])) == 0.0
    for b in range(2):
        n, b0 = int(length[b]), int(base[b])
        flat = lambda a: a[table[b]].reshape(nb * bs, KV, d)[  # noqa: E731
            :n - b0].transpose(1, 0, 2)
        ks, vs = flat(pool_k), flat(pool_v)
        at = b0 + jnp.arange(n - b0)
        ok = at[None, :] >= first[b][:, None]                 # [RG, keys]
        s = jnp.einsum("krd,ksd->krs", q[b], ks) * 0.1
        a = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        plain = jnp.einsum("krs,ksd->krd", a, vs)
        np.testing.assert_allclose(norm(got)[b], plain, atol=2e-5)
        # and mha_reference over the visible keys of one row
        r = RG - 1
        vis = np.flatnonzero(np.asarray(ok[r]))
        one = attention.mha_reference(
            q[b][None, :, r:r + 1], ks[None][:, :, vis], vs[None][:, :, vis],
            scale=0.1)
        np.testing.assert_allclose(norm(got)[b][:, r], one[0, :, 0],
                                   atol=2e-5)


def test_paged_attention_without_bounds_is_as_it_was():
    """No ``first``: every cached row visible to every query row, and a
    group of 8 rows (no padding) gives the gathered form's parts."""
    rng = np.random.default_rng(2)
    B, KV, RG, d, bs = 2, 2, 8, 128, 16
    pool = [jnp.asarray(rng.normal(size=(9, bs, KV * d)), jnp.float32)
            for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(B, KV, RG, d)), jnp.float32)
    table = jnp.asarray([[3, 1, 7, 0], [2, 5, 0, 0]], jnp.int32)
    length = jnp.asarray([55, 17], jnp.int32)
    want = attention.paged_gqa_attention_xla(q, *pool, table, length,
                                             scale=0.1)
    got = attention.paged_gqa_attention(q, *pool, table, length, scale=0.1,
                                        interpret=True)
    np.testing.assert_allclose(got[2], want[2], atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)


# -- the lane against the reference ------------------------------------------------

@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("stored, steps", [
    (3, (1, 2, 1)),                 # under the window throughout
    (5, (2, 7, 3)),                 # crosses it inside one query's events
    (37, (8, 1, 8, 5, 8, 8)),       # several windows long, blocks released
], ids=["under", "crossing", "beyond"])
def test_prefill_then_extensions_match_the_full_forward(heads, stored, steps):
    """Prefill, then extensions through the caches: every extension's
    logits (all items' scores), every layer's residual stream and the
    first position each layer read agree with the reference's full
    forward pass over the whole history."""
    params, theta, cfg = build(**HEADS[heads])
    events = history(stored, 1).tolist()
    srv = server(params, theta, {0: np.asarray(events, np.int32)})
    assert isinstance(srv._bb, SmallThinkerBackbone)
    for j, n in enumerate(steps):
        new = history(n, 10 + j)
        srv.sess_topk(0, new, 5)
        events += new.tolist()
        agrees(srv, theta, cfg, 0, events)
    sess = srv._sessions[0]
    whole = -(-len(events) // 4)
    assert len(sess.held[0]) == whole and sess.first[0] == 0
    lo = max(0, len(events) - WINDOW + 1) // 4
    assert sess.first[1] == lo and len(sess.held[1]) == whole - lo
    srv.close()


def test_a_released_block_is_never_read():
    """A window layer's block that lies wholly before every later
    query's window goes back to the free list; garbage written over
    every free block of both kinds (the released ones among them) moves
    no answer."""
    from predictionio_tpu.utils import metrics

    params, theta, cfg = build()
    events = history(30, 1).tolist()
    released = metrics.SESS_BLOCKS_RELEASED.value()
    srv = server(params, theta, {0: np.asarray(events, np.int32)})
    for j in range(3):
        new = history(6, 20 + j)
        srv.sess_topk(0, new, 5)
        events += new.tolist()
    # the prefill of 30 events leaves the window's 7 positions before
    # the end: blocks 0-4 go; three queries of 6 events then move the
    # end over five more block boundaries
    assert metrics.SESS_BLOCKS_RELEASED.value() - released == 5 + 5
    sess = srv._sessions[0]
    assert len(sess.held[1]) < len(sess.held[0])
    with srv._store_lock:
        poisoned = {name: [] for name in srv._pool}
        for name, arrays in srv._pool.items():
            for i, a in enumerate(arrays):
                free = np.zeros(a.shape[0], bool)
                free[srv._frees[srv._layer_kind[i]]] = True
                poisoned[name].append(
                    jnp.where(free[:, None, None], 1e4, a))
        srv._pool = {k: tuple(v) for k, v in poisoned.items()}
    new = history(3, 40)
    srv.sess_topk(0, new, 5)
    events += new.tolist()
    agrees(srv, theta, cfg, 0, events)
    srv.close()


def test_two_queries_of_one_user_in_one_group_are_ordered():
    """Both land in one group of the lane; the first answers for its
    own prefix, the second for both, as two groups would (waves), with
    a session over the window beside one under it."""
    from predictionio_tpu.ops.serving import _Pending
    from predictionio_tpu.ops.sessions import _dispatch_sess_group

    params, theta, cfg = build()
    hist = {0: history(21, 1), 1: history(5, 2)}
    a, b, c = history(2, 5), history(3, 6), history(1, 7)

    def run(groups):
        srv = server(params, theta, hist)
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
        agrees(srv, theta, cfg, 0, events)
        srv.close()
        return out, events

    one, ev1 = run([[(0, a), (1, c), (0, b)]])
    two, ev2 = run([[(0, a)], [(1, c)], [(0, b)]])
    np.testing.assert_array_equal(ev1, np.concatenate([hist[0], a, b]))
    np.testing.assert_array_equal(ev1, ev2)
    for (i1, s1), (i2, s2) in zip(one, two):
        assert i1.tolist() == i2.tolist()
        np.testing.assert_allclose(s1, s2, atol=1e-5)


def test_eviction_and_re_prefill_with_both_kinds_of_table():
    """A pool too small for all sessions: the session touched longest
    ago leaves BOTH kinds at once, its next touch prefills it again,
    and the answers stay the reference's; the gauges count what is
    held over the kinds."""
    from predictionio_tpu.utils import metrics

    params, theta, cfg = build()
    hist = {u: history(18 + 4 * u, u) for u in range(4)}
    srv = server(params, theta, hist, pool_tokens=64)
    assert srv._kind_blocks[0] == 17 and 3 < srv._kind_blocks[1] < 17
    for name in ("k", "v"):
        assert [a.shape[0] for a in srv._pool[name]] == [
            srv._kind_blocks[k] for k in (0, 1, 1, 1, 0, 1, 1, 1)]
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
        for k in (0, 1):    # no block in two hands, none lost
            mine = [b for s in live for b in s.held[k]] + srv._frees[k]
            assert sorted(mine) == list(range(1, srv._kind_blocks[k]))
        assert metrics.SESS_CACHE_TOKENS.value() == pytest.approx(
            4 * (2 * held[0] + 6 * held[1]) / 8)
        assert metrics.SESS_KIND_TOKENS.value(kind="window") == 4 * held[1]
    assert metrics.SESS_EVICTIONS.value() > evicted
    report = srv.session_report()
    assert [k["name"] for k in report["kinds"]] == ["global", "window"]
    assert report["capacityTokens"] == int(
        4 * (2 * 16 + 6 * (srv._kind_blocks[1] - 1)) / 8)
    srv.close()


def test_a_session_past_the_models_positions_is_refused():
    """``max_position_embeddings``: an append that would take a session
    past it is refused with an error, and the session is as it was."""
    params, theta, cfg = build(max_seq_len=64)
    srv = server(params, theta, {0: history(60, 1)})
    assert srv._s_max == 64
    srv.sess_topk(0, history(4, 2), 5)
    with pytest.raises(ValueError, match="past the lane's longest"):
        srv.sess_topk(0, history(1, 3), 5)
    assert srv.cached_length(0) == 64
    srv.close()


def test_one_kind_that_keeps_all_has_the_layout_it_always_had():
    one = sessions.one_kind(6)
    layout, width = sessions.kind_layout(one, 8, 8192, 256)
    assert layout == ((11, -1, 19, 32),) and width == 3 + 2 * 8 + 32
    two = (sessions.LayerKind("global", (0, 4), None),
           sessions.LayerKind("window", (1, 2, 3, 5, 6, 7), 4096))
    layout, width = sessions.kind_layout(two, 8, 16384, 256)
    # the window kind: its 8 rows and the 4,095 before them span 18
    # blocks at most, wherever they fall
    assert layout == ((11, -1, 19, 64), (83, 91, 92, 18))
    assert width == 92 + 18
    assert sessions.kind_layout(two, 2048, 16384, 256)[0][1][3] == 25


def test_ladder_is_complete_after_warm_up():
    """``warmup()`` compiles every program the lane can dispatch and
    prefills the stored sessions; queries of every group size then
    compile nothing and every dispatch is an ``aot`` hit."""
    from predictionio_tpu.utils import device_telemetry, metrics

    metrics.install_jit_compile_listener()
    params, theta, cfg = build()
    hist = {u: history(6 + 9 * u, u) for u in range(4)}
    srv = server(params, theta, hist)
    srv.warmup(max_k=8)
    assert srv.session_report()["sessions"] == 4
    import time

    before = metrics.JIT_COMPILES.value()
    t0 = time.time()
    for group in ([0], [1, 2], [0, 1, 2, 3]):
        srv.extend([(u, history(3, 60 + u)) for u in group],
                   srv._sess_kb(5))
    assert metrics.JIT_COMPILES.value() == before
    mine = [r for r in device_telemetry.recorder().snapshot(limit=1 << 20)
            if r["ts"] >= t0 and r["lane"] == "sess"]
    assert len(mine) == 3 and {r["aot"] for r in mine} == {"hit"}
    srv.close()


def test_the_programs_keep_the_names_the_trace_is_read_by():
    """The device modules are ``jit_<the jitted function's name>``:
    the sequence-mixed cell's readers find the lane's device time under
    ``jit_swa_extend`` (``benchmark/drivers/http_sess_mixed.py``), and
    the backbone names its closures by ``program_prefix``."""
    params, theta, cfg = build()
    srv = server(params, theta, {0: history(9, 0)})
    S_ = srv._s_bucket(16)
    names = (srv._bb.extend_program(srv, srv._sess_kb(5), S_).__name__,
             srv._bb.prefill_program(srv, S_).__name__)
    assert names == ("swa_extend", "swa_prefill")
    srv.close()


def test_engine_json_selects_the_block():
    from predictionio_tpu.controller.engine import params_from_dict

    got = params_from_dict(S.SeqRecParams, {
        "block": "smallthinker", "rank": 2560, "nHeads": 28, "nKvHeads": 4,
        "headDim": 128, "nLayers": 8, "norm": "rmsnorm", "normEps": 1e-6,
        "positions": "rope", "ropeTheta": 1500000.0, "tied": False,
        "vocabRows": 151936, "nExperts": 64, "expertWidth": 768,
        "expertsPerToken": 6, "normTopkProb": True,
        "slidingWindowSize": 4096,
        "slidingWindowLayout": [0, 1, 1, 1] * 13, "maxSeqLen": 16384,
        "computeDtype": "bfloat16", "numSteps": 0, "seededWeights": True})
    want = S.SeqRecParams(**S.SMALLTHINKER_21B_A3B, n_layers=8,
                          compute_dtype="bfloat16")
    assert S.block_spec(got) == S.block_spec(want)
    spec = S.block_spec(got).swa
    assert (spec.kv_width, spec.group, spec.window, spec.pattern) \
        == (512, 7, 4096, (0, 1, 1, 1, 0, 1, 1, 1))
    with pytest.raises(ValueError, match="glm_moe_dsa, sdar_moe, "
                                         "smallthinker, qwen3_next and "
                                         "falcon_h1"):
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
    SmallThinker backbone, warm-up with the resident sessions) ->
    session queries over ``/queries.json`` in cell 6's form, answered
    as the reference answers from the lane's own weights."""
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

    aid = storage.get_metadata_apps().insert(App(0, "swaapp"))
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
        data_source_params=("", DataSourceParams(app_name="swaapp")),
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
        assert isinstance(lane._bb, SmallThinkerBackbone)
        assert lane.session_report()["sessions"] == 6
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
        spec = lane._spec
        cfg = dict(n_layers=spec.n_layers, n_heads=spec.n_heads,
                   n_kv=spec.n_kv, head_dim=spec.head_dim,
                   window=spec.window, pattern=spec.pattern,
                   per_token=spec.per_token, norm_eps=spec.norm_eps,
                   rope_theta=spec.rope_theta, n_items=len(model.item_map))
        got = lane.audits(u3)[-1]
        want = ref.forward(theta, after, cfg, at=[len(after) - 1],
                           q_block=16)
        np.testing.assert_allclose(got["scores"], want["scores"][0],
                                   atol=5e-5)
        # the same prefix again (no events) is the same answer
        assert post({"user": "u3", "num": 6})[1] == out
    finally:
        srv.stop()


# -- the benchmark's configuration against the catalog row ------------------------------

# the ``config`` of the catalog's row ``SmallThinker-21BA3B-Instruct``
# (the model-configs guide's architectures.jsonl), key for key
CATALOG_CONFIG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def test_the_cells_configuration_is_the_catalog_rows_but_for_the_depth():
    """``benchmark/configs/seqrec-smallthinker.json`` holds every number
    of the catalog row's ``config`` under the same key; ``reduced``
    names the depth alone, and the parameters the cell serves are the
    block's published ones."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "seqrec-smallthinker.json")) as f:
        c = json.load(f)
    differs = [k for k, v in CATALOG_CONFIG.items() if c.get(k, "?") != v]
    assert differs == ["num_hidden_layers"] == list(c["reduced"])
    assert c["num_hidden_layers"] == 8
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["config"] == CATALOG_CONFIG
        assert row["source_url"] == c["source"]
    for key in ("router before attention", "window convention", "rotation",
                "no secondary experts", "vocabulary", "weights",
                "histories", "max_position_embeddings"):
        assert key in c["assumed"], key
    published = S.SeqRecParams(**S.SMALLTHINKER_21B_A3B, n_layers=8)
    assert (published.rank, published.n_heads, published.n_kv_heads,
            published.head_dim, published.expert_width, published.n_experts,
            published.experts_per_token, published.sliding_window_size,
            published.vocab_rows, published.max_seq_len,
            published.rope_theta, published.norm_eps) == tuple(
        c[k] for k in ("hidden_size", "num_attention_heads",
                       "num_key_value_heads", "head_dim",
                       "moe_ffn_hidden_size", "moe_num_primary_experts",
                       "moe_num_active_primary_experts",
                       "sliding_window_size", "vocab_size",
                       "max_position_embeddings", "rope_theta",
                       "rms_norm_eps"))
    assert list(published.sliding_window_layout) \
        == c["sliding_window_layout"] == c["rope_layout"]

"""The OLMoE block as a backbone of the sequence lane, at tiny widths on
the CPU (hidden 64, 4 heads, 8 experts of width 32, 2 a token, rows of
64): the system against the plain float32 reference
(``ops/seqrec_reference.py``), the packed layout against the padded
one, the dropless dispatch against the dense masked form, and the
template's path to ``DeviceTopK`` and fold-in. The SASRec defaults are
held to values the code before this block-as-data change computed."""

import functools

import numpy as np
import pytest

from predictionio_tpu.ops import moe
from predictionio_tpu.ops import seqrec as S
from predictionio_tpu.ops import seqrec_reference as R

N_ITEMS = 100
TINY = dict(block="olmoe", rank=64, n_heads=4, head_dim=16, norm="rmsnorm",
            norm_eps=1e-5, positions="rope", tied=False, vocab_rows=128,
            n_experts=8, expert_width=32, experts_per_token=2, n_layers=2,
            max_seq_len=64)
CFG = dict(n_layers=2, n_heads=4, head_dim=16, norm_eps=1e-5,
           rope_theta=10000.0, experts_per_token=2, lb_coef=0.01,
           z_coef=0.001)

# float32 on both sides and the same mathematics, so what is left is
# the order of float32 sums: the system sums expert outputs per token
# over 2 experts and attention over blocks of a packed row, the
# reference over all 8 experts and the whole row; gradients add a
# second such pass. Measured here: 2e-6 on unit-scale hidden states,
# 1e-6 relative on gradients. 1e-4 leaves that room and is 40 times
# under what one bf16 matmul (2^-8) would add.
TOL = 1e-4


def _histories(seed=0, n=30, longest=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, N_ITEMS, size=k).astype(np.int32)
            for k in rng.integers(1, longest, size=n)]


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    params = S.SeqRecParams(**TINY, seed=5)
    theta = S.init_theta(N_ITEMS, params)
    rows = S.pack_sequences(_histories(), 64)
    ids, seg, pos = (jnp.asarray(x[:4]) for x in (rows.ids, rows.seg,
                                                  rows.pos))
    negs = jnp.asarray(np.random.default_rng(1).integers(
        0, N_ITEMS, size=16).astype(np.int32))
    spec = S.block_spec(params)
    micro = lambda x: x.reshape(2, 2, 64)  # noqa: E731
    loss, grads, targets, load, dropped = jax.jit(functools.partial(
        S.step_gradients, spec=spec))(theta, micro(ids), micro(seg),
                                      micro(pos), negs)
    want_loss, want_grads = R.step_loss_and_grads(
        {k: jnp.asarray(v) for k, v in theta.items()},
        [(ids[:2], seg[:2], pos[:2]), (ids[2:], seg[2:], pos[2:])],
        negs, CFG)
    return dict(params=params, spec=spec, theta=theta, rows=rows,
                batch=(ids, seg, pos), negs=negs, loss=float(loss),
                grads=grads, load=np.asarray(load), dropped=int(dropped),
                targets=float(targets), want_loss=float(want_loss),
                want_grads=want_grads)


def test_hidden_states_match_the_reference(tiny):
    import jax

    got, stats = jax.jit(functools.partial(
        S.encoder_forward, spec=tiny["spec"]))(tiny["theta"], *tiny["batch"])
    with jax.default_matmul_precision("highest"):
        want, routed = R.forward(tiny["theta"], *tiny["batch"], CFG)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    for st, r in zip(stats, routed):
        np.testing.assert_allclose(st["logits"], r["logits"], rtol=TOL,
                                   atol=TOL)


def test_step_loss_matches_the_reference(tiny):
    assert tiny["loss"] == pytest.approx(tiny["want_loss"], rel=TOL)
    assert tiny["dropped"] == 0
    # every (token, expert) pair of both layers and microbatches
    assert tiny["load"].sum() == 2 * 4 * 64 * 2


PARAM_NAMES = ["item_emb", "out_emb", "ln_f_g"] + [
    f"l{i}_{n}" for i in range(2)
    for n in ("wq", "wk", "wv", "wo", "qn_g", "kn_g", "ln1_g", "ln2_g",
              "router", "we_gate", "we_up", "we_down")]


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_every_gradient_matches_the_reference(tiny, name):
    got, want = np.asarray(tiny["grads"][name]), \
        np.asarray(tiny["want_grads"][name])
    assert np.abs(want).max() > 0
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.fixture(scope="module")
def stepped(tiny):
    """The training program itself (``_train_step_jit``: gradients over
    two microbatches, then Adam, the state donated) once from a seeded
    state with moments and steps behind it, and the reference's
    gradients through the reference's plain Adam from the same state."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    theta = {k: jnp.asarray(v) for k, v in tiny["theta"].items()}
    m = {k: jnp.asarray(rng.normal(size=v.shape) * 1e-3, jnp.float32)
         for k, v in theta.items()}
    v = {k: jnp.asarray(rng.uniform(1e-6, 2e-6, size=x.shape), jnp.float32)
         for k, x in theta.items()}
    lr, t0 = 1e-2, 7.0
    want = {k: R.adam_update(m[k], v[k], t0, tiny["want_grads"][k], lr)
            for k in theta}
    micro = lambda x: x.reshape(2, 2, 64)  # noqa: E731
    run = S._train_step_jit(tiny["spec"], lr, 0.0)
    state = (dict(theta), dict(m), dict(v), jnp.full((), t0, jnp.float32))
    (new, m1, v1, t1), out = run(
        jax.tree_util.tree_map(jnp.copy, state),
        *(micro(x) for x in tiny["batch"]), tiny["negs"])
    return dict(theta=theta, new=new, m=m1, v=v1, t=float(t1), out=out,
                want=want)


def test_the_step_program_reports_the_references_loss(stepped, tiny):
    assert float(stepped["out"]["loss"]) == pytest.approx(
        tiny["want_loss"], rel=TOL)
    assert float(stepped["out"]["targets"]) == tiny["targets"]
    assert stepped["t"] == 8.0


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_the_step_program_is_the_references_adam(stepped, name):
    """Every parameter's change and both new moments against Adam as
    Kingma & Ba write it, on the reference's gradients. The change is
    read off float32 parameters: a norm's gain of 1.0 keeps 6e-8 of a
    change of 1e-3, so 1e-3 of its size is the comparison's floor."""
    want_m, want_v, want_change = (np.asarray(x)
                                   for x in stepped["want"][name])
    got_change = np.asarray(stepped["theta"][name]) \
        - np.asarray(stepped["new"][name])
    assert np.abs(want_change).max() > 0
    np.testing.assert_allclose(stepped["m"][name], want_m, rtol=1e-4,
                               atol=1e-4 * np.abs(want_m).max())
    np.testing.assert_allclose(stepped["v"][name], want_v, rtol=1e-4)
    assert np.abs(got_change - want_change).max() \
        <= 1e-3 * np.abs(want_change).max()


def test_parameters_cover_the_published_block(tiny):
    assert sorted(tiny["theta"]) == sorted(PARAM_NAMES)
    assert tiny["theta"]["item_emb"].shape == (128, 64)   # vocab_rows
    assert tiny["theta"]["l0_we_gate"].shape == (8, 64, 32)
    assert "pos_emb" not in tiny["theta"]                 # rotary


def test_olmoe_1b_7b_parameter_count():
    import jax

    p = S.SeqRecParams(**S.OLMOE_1B_7B, n_layers=1)
    shapes = jax.eval_shape(lambda: S.init_theta_device(41140, p))
    count = {k: int(np.prod(v.shape)) for k, v in shapes.items()}
    assert count["item_emb"] == count["out_emb"] == 50304 * 2048
    layer = sum(v for k, v in count.items() if k.startswith("l0_"))
    assert layer == 419_569_664            # ISSUE 25's 419.6M a layer
    assert sum(count.values()) == 625_616_896


# -- packing ------------------------------------------------------------------

def test_first_fit_packing_layout():
    seqs = [np.arange(5), np.arange(4), np.arange(0), np.arange(3),
            np.arange(100, 112), np.arange(2)]
    rows = S.pack_sequences(seqs, 8)
    # 5 -> row 0; 4 -> row 1; 3 -> row 0 (first with room); 12 keeps its
    # last 8 -> row 2; 2 -> row 1
    assert rows.ids.shape == (3, 8)
    assert rows.seg.tolist() == [[1] * 5 + [2] * 3, [1] * 4 + [2] * 2 + [0] * 2,
                                 [1] * 8]
    assert rows.pos[0].tolist() == [0, 1, 2, 3, 4, 0, 1, 2]
    assert rows.ids[2].tolist() == list(range(104, 112))
    assert rows.users.tolist() == [0, 1, 3, 4, 5]     # the empty one dropped
    assert rows.last.tolist() == [4, 8 + 3, 7, 16 + 7, 8 + 5]
    assert rows.n_tokens == 22 and rows.pad_share == pytest.approx(2 / 24)
    # every row holds tokens: none can be drawn into a step empty
    assert (rows.seg != 0).any(axis=1).all()


def test_packed_rows_equal_the_same_histories_unpacked(tiny):
    seqs = _histories(seed=3)
    params, theta = tiny["params"], tiny["theta"]
    packed = S.encode_users(theta, S.pack_sequences(seqs, 64),
                            len(seqs), params)
    padded = S.encode_users(theta, S.bucket_sequences(seqs, max_len=64),
                            len(seqs), params)
    assert np.abs(packed).max() > 0.5
    np.testing.assert_allclose(packed, padded, rtol=TOL, atol=TOL)


def test_packed_targets_never_cross_a_history(tiny):
    import jax.numpy as jnp

    ids, seg, pos = tiny["batch"]
    terms = S.sampled_softmax_terms(tiny["theta"], ids, seg, pos,
                                    tiny["negs"], spec=tiny["spec"])
    seg = np.asarray(seg)
    inside = (seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] != 0)
    assert float(terms["targets"]) == inside.sum() < (seg != 0).sum()
    assert float(jnp.abs(terms["hidden"][seg == 0]).max()) == 0.0


# -- the expert layer ---------------------------------------------------------

def _expert_weights(seed=0, T=96, D=32, E=8, F=16):
    rng = np.random.default_rng(seed)
    f32 = lambda *s, scale=1.0: np.asarray(  # noqa: E731
        rng.normal(size=s) * scale, np.float32)
    return (f32(T, D), f32(D, E), f32(E, D, F, scale=0.2),
            f32(E, D, F, scale=0.2), f32(E, F, D, scale=0.25))


def _rigged(kind):
    h, w_r, *experts = _expert_weights()
    if kind == "one expert gets every token, others none":
        # every token's top 2 are experts 3 and 5: both get all 96
        # tokens, the other six get none
        w_r = np.zeros_like(w_r)
        h = np.abs(h) + 0.1
        w_r[:, 3], w_r[:, 5] = 1.0, 0.5
    return (h, w_r, *experts)


@pytest.mark.parametrize("kind", [
    "random routing", "one expert gets every token, others none"])
def test_dropless_dispatch_equals_the_dense_masked_form(kind):
    import jax
    import jax.numpy as jnp

    args = tuple(jnp.asarray(a) for a in _rigged(kind))
    got, stats = moe.moe_ffn(*args, k=2)
    want = moe.moe_ffn_dense(*args, k=2)
    sizes = np.asarray(stats["group_sizes"])
    assert sizes.sum() == 96 * 2 and int(stats["dropped"]) == 0
    if kind != "random routing":
        assert sizes.tolist() == [0, 0, 0, 96, 0, 96, 0, 0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    f = lambda *a: jnp.sum(jnp.sin(moe.moe_ffn(*a, k=2)[0]))  # noqa: E731
    fd = lambda *a: jnp.sum(jnp.sin(moe.moe_ffn_dense(*a, k=2)))  # noqa: E731
    which = (0, 2, 3, 4) if kind != "random routing" else (0, 1, 2, 3, 4)
    for g, gd in zip(jax.grad(f, argnums=which)(*args),
                     jax.grad(fd, argnums=which)(*args)):
        np.testing.assert_allclose(g, gd, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("case", [
    "random picks", "every pick is expert 3", "one pick a token",
    "a bin past the held experts"])
def test_dispatch_plan_is_the_stable_sort_its_inverse_and_the_counts(case):
    """The plan is made of sorts and a compare-and-sum (no scatter):
    held to numpy's stable argsort, the scattered inverse and
    ``bincount``; ``_place`` to the indexing it stands for."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    T, k, E = 48, (1 if case == "one pick a token" else 4), 8
    experts = rng.integers(0, E, size=(T, k))
    if case == "every pick is expert 3":
        experts[:] = 3
    if case == "a bin past the held experts":
        experts, E = np.where(experts < 5, experts, 5), 6
    plan = moe.dispatch_plan(jnp.asarray(experts, jnp.int32), E)
    flat = experts.reshape(-1)
    order = np.argsort(flat, kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(T * k)
    assert plan["order"].dtype == plan["inv"].dtype == jnp.int32
    assert np.array_equal(plan["order"], order)
    assert np.array_equal(plan["inv"], inv)
    assert np.array_equal(plan["token"], order // k)
    assert plan["group_sizes"].dtype == jnp.int32
    assert np.array_equal(plan["group_sizes"], np.bincount(flat, minlength=E))
    values = np.asarray(rng.normal(size=T * k), np.float32)
    assert np.array_equal(moe._place(jnp.asarray(values), plan["inv"]),
                          values[order])
    assert np.array_equal(moe._place(jnp.asarray(values), plan["order"]),
                          values[inv])


PERMUTATIONS = {
    "64 tokens, 8 picks": (64, 8, False),
    "32 tokens, 8 picks, a share's weightless last run": (32, 8, True),
    "16 tokens, 1 pick": (16, 1, False),
}


def _permutation(T, k, share):
    """A dispatch plan with its rows in expert order. ``share``: the
    plan of ``moe_ffn_share`` for experts 2..5 of 8: picks of the other
    four sort into a last run, whose rows are zero and whose weights
    are 0. Some held picks weigh 0 too (a router may say so)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(T * k)
    D, E = 40, 8
    experts = jnp.asarray(rng.integers(0, E, size=(T, k)), jnp.int32)
    weights = np.asarray(rng.random((T, k)), np.float32)
    weights[rng.random((T, k)) < 0.2] = 0.0
    ys = np.asarray(rng.normal(size=(T * k, D)), np.float32)
    if share:
        local = (experts >= 2) & (experts < 6)
        plan = moe.dispatch_plan(jnp.where(local, experts - 2, 4), 5)
        ys[int(plan["group_sizes"][:4].sum()):] = 0.0
        weights = np.where(local, weights, 0.0)
        assert 0 < int(plan["group_sizes"][4]) < T * k
    else:
        plan = moe.dispatch_plan(experts, E)
    x = np.asarray(rng.normal(size=(T, D)), np.float32)
    g = np.asarray(rng.normal(size=(T, D)), np.float32)
    return plan, jnp.asarray(ys), jnp.asarray(weights), jnp.asarray(x), \
        jnp.asarray(g)


@pytest.mark.parametrize("which", ["combine", "dispatch"])
@pytest.mark.parametrize("case", list(PERMUTATIONS))
def test_permutations_equal_the_plain_expressions(case, which):
    """The two custom VJPs alone (op by op: under jit LLVM contracts a
    multiply-add on the CPU and the last bit is the compiler's) against
    ``jax.vjp`` of what they stand for: the forward bit for bit, the
    gradients to float32 rounding."""
    import jax
    import jax.numpy as jnp

    T, k, share = PERMUTATIONS[case]
    plan, ys, weights, x, g = _permutation(T, k, share)
    order, inv, token = plan["order"], plan["inv"], plan["token"]
    if which == "dispatch":
        got, back = jax.vjp(lambda x: moe._dispatch(x, token, inv), x)
        want, plain = jax.vjp(lambda x: x[token], x)
        assert np.array_equal(got, want)
        d = jnp.asarray(np.random.default_rng(1).normal(size=got.shape),
                        jnp.float32)
        np.testing.assert_allclose(back(d)[0], plain(d)[0], rtol=1e-6,
                                   atol=1e-6)
        return
    got, back = jax.vjp(
        lambda ys, w: moe._combine(ys, w, order, inv), ys, weights)
    want, plain = jax.vjp(
        lambda ys, w: jnp.sum(ys[inv].reshape(T, k, -1) * w[..., None],
                              axis=1), ys, weights)
    assert got.dtype == jnp.float32 and np.array_equal(got, want)
    (d_ys, d_w), (p_ys, p_w) = back(g), plain(g)
    np.testing.assert_allclose(d_ys, p_ys, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(d_w, p_w, rtol=1e-5, atol=1e-5)
    # a pick that weighs 0 adds nothing forward, and its weight's
    # gradient is still <its row, g>: the router learns from it
    zero = np.asarray(weights) == 0.0
    dots = np.einsum("tkd,td->tk", np.asarray(ys)[np.asarray(inv)].reshape(
        T, k, -1).astype(np.float64), np.asarray(g, np.float64))
    assert zero.any() and np.abs(dots[zero]).max() > 0.1
    np.testing.assert_allclose(np.asarray(d_w)[zero], dots[zero], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(d_ys)[np.asarray(inv).reshape(T, k)[zero]].any()


def test_router_weights_are_not_renormalised():
    import jax
    import jax.numpy as jnp

    h, w_r, *_ = (jnp.asarray(a) for a in _expert_weights())
    logits, probs, experts, weights = moe.route(h, w_r, 2)
    want = np.sort(np.asarray(jax.nn.softmax(h @ w_r)), axis=1)[:, ::-1][:, :2]
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    # two of eight probabilities: their sum stays what the softmax gave
    # them (well under 1 for most tokens), it is not scaled back to 1
    assert float(jnp.min(jnp.sum(weights, axis=1))) < 0.9
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(probs), np.asarray(experts), axis=1),
        weights, rtol=1e-6)


def test_megablox_kernel_equals_ragged_dot_in_interpret_mode():
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    lhs = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 128, 128)) / 11, jnp.float32)
    sizes = jnp.asarray([100, 0, 156, 0], jnp.int32)
    want = moe.grouped_matmul(lhs, rhs, sizes, impl="ragged")
    got = moe.grouped_matmul(lhs, rhs, sizes, impl="megablox",
                             tiling=(128, 128, 128), interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_megablox_backward_equals_ragged_dots_in_interpret_mode():
    """The custom VJP of the kernels' path: the rows' gradient (``gmm``
    against the transposed weight) and the weight gradient (``tgmm``,
    in the master weight's dtype), with an expert that owns no row."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 128, 128)) / 11, jnp.float32)
    out_w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    sizes = jnp.asarray([100, 0, 28, 128], jnp.int32)

    def loss(lhs, rhs, **kw):
        return jnp.sum(moe.grouped_matmul(lhs, rhs, sizes, **kw) * out_w)

    want = jax.grad(loss, argnums=(0, 1))(lhs, rhs, impl="ragged")
    got = jax.grad(loss, argnums=(0, 1))(
        lhs, rhs, impl="megablox", tiling=(128, 128, 128), interpret=True)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-3)
    assert not np.asarray(got[1][1]).any()      # the expert with no row


def test_auxiliary_losses_of_a_uniform_router():
    import jax.numpy as jnp

    T, E, k = 64, 8, 2
    logits = jnp.zeros((T, E))
    probs = jnp.full((T, E), 1.0 / E)
    experts = jnp.asarray(np.stack([np.arange(T) % E,
                                    (np.arange(T) + 1) % E], axis=1))
    lb, z = moe.aux_losses(logits, probs, experts, jnp.ones(T))
    assert float(lb) == pytest.approx(k)          # E * sum (k/E) * (1/E)
    assert float(z) == pytest.approx(np.log(E) ** 2, rel=1e-5)


# -- attention ----------------------------------------------------------------

def test_qk_norm_is_over_the_whole_projection(tiny):
    """Scaling ONE head's query weights changes the other heads' queries
    when the norm runs over the whole projection (a per-head norm would
    leave them alone): the system follows the reference there too."""
    import jax

    theta = dict(tiny["theta"])
    wq = theta["l0_wq"].copy()
    wq[:, :16] *= 30.0
    theta["l0_wq"] = wq
    got, _ = S.encoder_forward(theta, *tiny["batch"], spec=tiny["spec"])
    with jax.default_matmul_precision("highest"):
        want, _ = R.forward(theta, *tiny["batch"], CFG)
        base, _ = R.forward(tiny["theta"], *tiny["batch"], CFG)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    assert float(np.abs(np.asarray(want) - np.asarray(base)).max()) > 0.05
    q = np.asarray(tiny["theta"]["item_emb"][:8] @ wq)
    whole = q / np.sqrt((q * q).mean(-1, keepdims=True) + 1e-5)
    heads = q.reshape(8, 4, 16)
    per_head = (heads / np.sqrt((heads * heads).mean(-1, keepdims=True)
                                + 1e-5)).reshape(8, 64)
    assert np.abs(whole - per_head)[:, 16:].max() > 0.5


def test_blocked_attention_kernel_equals_the_dense_form():
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import attention as A

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 256, 128)), jnp.float32)
               for _ in range(3))
    seg = np.zeros((2, 256), np.int32)
    seg[0, :100], seg[0, 100:230] = 1, 2
    seg[1, :7], seg[1, 7:50], seg[1, 50:] = 1, 2, 3
    seg = jnp.asarray(seg)
    flash = jax.jit(functools.partial(A.segment_attention_flash, block=128,
                                      interpret=True))
    want = A.segment_attention_dense(q, k, v, seg)
    np.testing.assert_allclose(flash(q, k, v, seg), want, rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(want[0, :, 230:]).max()) == 0.0     # the pad tail
    g = jax.grad(lambda q: jnp.sum(jnp.sin(flash(q, k, v, seg))))(q)
    gd = jax.grad(lambda q: jnp.sum(jnp.sin(
        A.segment_attention_dense(q, k, v, seg))))(q)
    np.testing.assert_allclose(g, gd, rtol=1e-4, atol=1e-5)


def test_one_segment_a_row_is_the_padded_attention():
    import jax.numpy as jnp

    from predictionio_tpu.ops import attention as A

    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(3, 2, 16, 8)), jnp.float32)
               for _ in range(3))
    mask = np.ones((3, 16), np.float32)
    mask[0, 9:], mask[2, 3:] = 0, 0
    want = A.mha_reference(q, k, v, causal=True, key_padding_mask=mask)
    got = A.segment_attention_dense(q, k, v, (mask > 0).astype(np.int32))
    keep = mask[:, None, :, None]
    np.testing.assert_array_equal(np.asarray(got) * keep,
                                  np.asarray(want) * keep)


# -- the block as data --------------------------------------------------------

@pytest.mark.parametrize("bad,match", [
    (dict(block="mamba"), "unknown block"),
    (dict(norm="batchnorm"), "unknown norm"),
    (dict(positions="alibi"), "unknown positions"),
    (dict(compute_dtype="float8"), "compute_dtype"),
    (dict(block="olmoe", n_experts=4, experts_per_token=8,
          expert_width=8), "olmoe block needs"),
    (dict(positions="rope", rank=12, n_heads=4), "even head_dim"),
    (dict(TINY, norm="layernorm"), "olmoe block takes"),
    (dict(TINY, positions="learned"), "olmoe block takes"),
    (dict(TINY, tied=True), "olmoe block takes"),
])
def test_block_spec_refuses_what_it_cannot_build(bad, match):
    with pytest.raises(ValueError, match=match):
        S.block_spec(S.SeqRecParams(**bad))


@pytest.mark.parametrize("over", [
    dict(norm="rmsnorm"), dict(positions="rope"), dict(tied=False),
    dict(norm="rmsnorm", positions="rope", tied=False, vocab_rows=64)])
def test_sasrec_block_with_other_parts_packs_like_it_pads(over):
    """norm, positions and tying are chosen apart from the block."""
    params = S.SeqRecParams(rank=16, n_layers=1, n_heads=2, max_seq_len=32,
                            seed=2, **over)
    theta = S.init_theta(40, params)
    seqs = [s % 40 for s in _histories(seed=4, n=12, longest=30)]
    packed = S.encode_users(theta, S.pack_sequences(seqs, 32),
                            len(seqs), params)
    padded = S.encode_users(theta, S.bucket_sequences(seqs, max_len=32),
                            len(seqs), params)
    np.testing.assert_allclose(packed, padded, rtol=1e-4, atol=1e-5)
    assert ("out_emb" in theta) == (not params.tied)
    assert ("pos_emb" in theta) == (params.positions == "learned")


def test_sasrec_defaults_compute_what_they_computed_before():
    """Values the trainer-independent part of the lane (initialisation,
    bucketing, encode) gave before the block became data: same draws,
    same operations."""
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 30, size=n).astype(np.int32)
            for n in (3, 8, 12, 16, 1, 5, 7, 20, 33)]
    params = S.SeqRecParams(rank=16, n_layers=2, n_heads=4, max_seq_len=32,
                            seed=3)
    theta = S.init_theta(30, params)
    assert float(theta["l1_w2"].sum()) == pytest.approx(
        2.3731114864349365, rel=1e-6)
    assert float(theta["pos_emb"].sum()) == pytest.approx(
        0.3942747712135315, rel=1e-6)
    U = S.encode_users(theta, S.bucket_sequences(seqs, max_len=32),
                       len(seqs), params)
    np.testing.assert_allclose(U[[0, 3, 8]][:, :4], [
        [-0.8325310945510864, 0.9638247489929199, -0.024703163653612137,
         1.4072527885437012],
        [-0.6188655495643616, 1.2217700481414795, 1.099649429321289,
         -2.295718193054199],
        [-0.3126746118068695, 0.42068061232566833, -0.32716426253318787,
         -0.7256815433502197]], rtol=1e-5, atol=1e-6)
    assert float(np.abs(U).sum()) == pytest.approx(115.85604858398438,
                                                   rel=1e-5)


# -- the trainer --------------------------------------------------------------

def _chains(n_users=48, n_items=40, seed=0):
    rng = np.random.default_rng(seed)
    return [((rng.integers(0, n_items) + np.arange(rng.integers(6, 30)))
             % n_items).astype(np.int32) for _ in range(n_users)]


@pytest.fixture(scope="module")
def trained():
    from predictionio_tpu.utils import metrics

    params = S.SeqRecParams(**{**TINY, "n_layers": 1, "vocab_rows": 64},
                            num_steps=60, batch_size=4, micro_rows=2,
                            n_negatives=16, learning_rate=0.01, seed=1,
                            encode_rows=2)
    seqs = _chains()
    rows = S.pack_sequences(seqs, 64)
    before = (metrics.SEQ_TRAIN_TARGETS.value(),
              metrics.SEQ_DROPPED_TOKENS.value())
    theta, losses = S.train_seqrec(rows, 40, params)
    counted = (metrics.SEQ_TRAIN_TARGETS.value() - before[0],
               metrics.SEQ_DROPPED_TOKENS.value() - before[1])
    return dict(params=params, seqs=seqs, rows=rows, theta=theta,
                losses=losses, counted=counted)


def test_packed_training_lowers_the_loss_and_counts_its_work(trained):
    from predictionio_tpu.utils import metrics

    losses = trained["losses"]
    assert len(losses) == 60 and np.isfinite(losses).all()
    assert losses[-10:].mean() < 0.7 * losses[:10].mean()
    targets, dropped = trained["counted"]
    assert dropped == 0 and 60 * 4 * 20 < targets < 60 * 4 * 64
    # 4 rows x 64 slots x 2 experts a token over 8 experts
    assert metrics.SEQ_EXPERT_LOAD.value(stat="mean") == 64.0
    assert metrics.SEQ_EXPERT_LOAD.value(stat="max") >= 64.0


def test_packed_training_is_deterministic_and_keeps_the_callers_theta(
        trained):
    params, rows = trained["params"], trained["rows"]
    short = S.SeqRecParams(**{**params.__dict__, "num_steps": 6})
    start = S.init_theta_device(40, short)
    a, la = S.train_seqrec(rows, 40, short, theta=start)
    b, lb = S.train_seqrec(rows, 40, short, theta=start)   # not donated
    np.testing.assert_array_equal(la, lb)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert np.abs(a["l0_we_up"] - np.asarray(start["l0_we_up"])).max() > 0


def test_microbatches_sum_to_the_whole_batch(tiny):
    """Cut in two microbatches or taken whole, a step's gradient is the
    same sum (the auxiliary losses apart: means over each microbatch's
    own tokens, so only the NLL part is compared, coefficients 0)."""
    import jax

    spec = S.block_spec(S.SeqRecParams(**TINY, lb_coef=0.0, z_coef=0.0))
    ids, seg, pos = tiny["batch"]
    step = jax.jit(functools.partial(S.step_gradients, spec=spec))
    whole = step(tiny["theta"], ids[None], seg[None], pos[None],
                 tiny["negs"])
    halves = step(tiny["theta"], *(x.reshape(2, 2, 64)
                                   for x in (ids, seg, pos)), tiny["negs"])
    assert float(whole[0]) == pytest.approx(float(halves[0]), rel=1e-5)
    for k in whole[1]:
        np.testing.assert_allclose(whole[1][k], halves[1][k], rtol=1e-3,
                                   atol=1e-6)


# -- the template's path ------------------------------------------------------

@pytest.fixture(scope="module")
def served(trained):
    from predictionio_tpu.core.context import workflow_context
    from predictionio_tpu.data.bimap import StringIndexBiMap
    from predictionio_tpu.templates.sequentialrec.engine import (
        PreparedSequences,
        SeqPreparatorParams,
        SeqRecAlgorithm,
        SequencePreparator,
    )

    seqs = trained["seqs"]
    users = StringIndexBiMap.from_distinct(
        np.asarray([f"u{i:03d}" for i in range(len(seqs))], dtype=object))
    items = StringIndexBiMap.from_distinct(
        np.asarray([f"i{i:03d}" for i in range(40)], dtype=object))
    prep = SequencePreparator(SeqPreparatorParams(
        max_seq_len=64, packed=True))
    pd = PreparedSequences(users, items, prep.layout(seqs),
                           {u: np.unique(s) for u, s in enumerate(seqs)}, 64)
    algo = SeqRecAlgorithm(S.SeqRecParams(
        **{**trained["params"].__dict__, "num_steps": 20}))
    model = algo.train(workflow_context(mode="train"), pd)
    return algo, pd, model


def test_untied_tables_reach_serving_as_the_output_table(served,
                                                         monkeypatch):
    from predictionio_tpu.ops.serving import DeviceTopK
    from predictionio_tpu.templates.sequentialrec.engine import Query

    monkeypatch.setenv("PIO_SERVING_BACKEND", "device")
    monkeypatch.setenv("PIO_SERVE_PRECISION", "fp32")
    algo, pd, model = served
    theta = model.theta
    assert model.item_vectors.shape == (40, 64)        # not vocab_rows
    np.testing.assert_array_equal(model.item_vectors, theta["out_emb"][:40])
    assert np.abs(theta["out_emb"][:40] - theta["item_emb"][:40]).max() > 0.1
    model._server = None
    assert isinstance(model.device_server(), DeviceTopK)
    res = algo.predict(model, Query(user="u007", num=5))
    u = pd.user_map["u007"]
    want = theta["out_emb"][:40] @ model.user_vectors[u]
    assert len(res.item_scores) == 5
    for s in res.item_scores:
        assert s.score == pytest.approx(float(want[pd.item_map[s.item]]),
                                        rel=1e-4, abs=1e-5)
    model._server = None


def test_user_vectors_are_the_references_last_hidden_states(served):
    algo, pd, model = served
    rows = pd.buckets
    _, _, want = R.user_vectors(
        model.theta, rows.ids, rows.seg, rows.pos,
        {**CFG, "n_layers": 1})
    got = model.user_vectors[rows.users]
    # user_vectors() walks rows then segments, as the packing numbered them
    order = np.lexsort((rows.last % 64, rows.last // 64))
    np.testing.assert_allclose(got[order], want, rtol=TOL, atol=TOL)


def test_fold_in_of_a_new_user_equals_the_references_last_state(served):
    import jax

    algo, pd, model = served
    history = np.asarray([3, 4, 5, 6, 7, 8, 9], np.int32)
    got = model.fold_in_rows([history, history[:3]], [None, None])
    for row, h in zip(got, (history, history[:3])):
        ids = h[None]
        with jax.default_matmul_precision("highest"):
            want, _ = R.forward(model.theta, ids, np.ones_like(ids),
                                np.arange(len(h))[None],
                                {**CFG, "n_layers": 1})
        np.testing.assert_allclose(row, np.asarray(want)[0, -1], rtol=TOL,
                                   atol=TOL)


def test_train_call_leaves_a_stage_summary(served):
    from predictionio_tpu.utils import tracing

    roots = tracing.trace_buffer().stage_summaries(root="seq.train")
    assert roots
    spans = roots[-1]["selfUs"]
    for name in ("seq.stage", "seq.steps", "seq.encode_users", "seq.fetch"):
        assert spans.get(name, 0) > 0, (name, sorted(spans))


# -- pio train -> pio deploy, the whole way -------------------------------------

README_ENGINE_JSON = {
    "block": "olmoe", "rank": 2048, "nHeads": 16, "headDim": 128,
    "nLayers": 1, "norm": "rmsnorm", "normEps": 1e-5, "positions": "rope",
    "ropeTheta": 10000, "tied": False, "vocabRows": 50304, "nExperts": 64,
    "expertWidth": 1024, "expertsPerToken": 8, "computeDtype": "bfloat16",
    "numSteps": 32, "batchSize": 8, "microRows": 2, "encodeRows": 4,
    "learningRate": 1e-4, "nNegatives": 64, "lbCoef": 0.01, "zCoef": 0.001,
    "seed": 7}


def test_engine_json_selects_the_backbone():
    """The README's ``engine.json`` of the backbone resolves to the
    published block (``OLMOE_1B_7B``), and stands in the README."""
    import json
    import os

    from predictionio_tpu.controller.engine import params_from_dict
    from predictionio_tpu.templates.sequentialrec.engine import (
        SeqPreparatorParams,
    )

    got = params_from_dict(S.SeqRecParams, README_ENGINE_JSON)
    want = S.SeqRecParams(**S.OLMOE_1B_7B, n_layers=1,
                          compute_dtype="bfloat16")
    assert S.block_spec(got) == S.block_spec(want)
    assert (got.batch_size, got.micro_rows, got.encode_rows) == (8, 2, 4)
    prep = params_from_dict(SeqPreparatorParams,
                            {"maxSeqLen": 4096, "packed": True})
    assert prep.packed and prep.max_seq_len == 4096
    readme = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "README.md")).read()
    block = readme[readme.index('{"preparator": {"params": {"maxSeqLen"'):]
    block = json.loads(block[:block.index("```")])
    assert block["algorithms"][0]["params"] == README_ENGINE_JSON


def test_pio_train_deploy_query_and_fold_in_with_the_backbone(
        mem_storage, monkeypatch):
    """Events -> ``run_train`` (``SequencePreparator`` packing,
    ``SeqRecAlgorithm.train``, ``SeqRecModel``) -> ``QueryServer``
    (``build_deployment``, ``DeviceTopK``) -> a query answered from the
    OUTPUT table -> a new user's events folded in through the same
    encoder, no retrain."""
    import datetime as dt
    import http.client
    import json

    from predictionio_tpu.controller import ComputeContext, EngineParams
    from predictionio_tpu.data import storage
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.base import App
    import time

    from predictionio_tpu.ops.serving import DeviceTopK
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
    monkeypatch.setenv("PIO_FOLDIN_INTERVAL", "0.2")
    t0 = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)

    def view(user, item, minute):
        return Event(event="view", entity_type="user", entity_id=user,
                     target_entity_type="item", target_entity_id=item,
                     event_time=t0 + dt.timedelta(minutes=minute))

    aid = storage.get_metadata_apps().insert(App(0, "olmoeapp"))
    le = storage.get_levents()
    le.init(aid)
    rng = np.random.default_rng(0)
    events = []
    for u in range(40):
        start = int(rng.integers(0, 30))
        events += [view(f"u{u}", f"i{(start + j) % 30}", j)
                   for j in range(int(rng.integers(4, 12)))]
    le.insert_batch(events, aid)
    params = EngineParams(
        data_source_params=("", DataSourceParams(app_name="olmoeapp")),
        preparator_params=("", SeqPreparatorParams(
            max_seq_len=32, packed=True)),
        algorithm_params_list=[("seqrec", S.SeqRecParams(
            **{**TINY, "n_layers": 1, "vocab_rows": 64, "max_seq_len": 32},
            num_steps=80, batch_size=4, micro_rows=2, encode_rows=2,
            n_negatives=16, learning_rate=0.01, seed=3))])
    factory = "predictionio_tpu.templates.sequentialrec:engine_factory"
    iid = run_train(engine_factory(), params, new_engine_instance(
        WorkflowConfig(engine_factory=factory), params),
        ctx=ComputeContext())
    assert iid is not None
    srv = QueryServer(ServerConfig(ip="127.0.0.1", port=0,
                                   foldin=True)).start(undeploy_stale=False)
    try:
        def query(user, num=5):
            conn = http.client.HTTPConnection(*srv.address, timeout=30)
            conn.request("POST", "/queries.json",
                         body=json.dumps({"user": user, "num": num}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read().decode())
            conn.close()
            return resp.status, body

        model = srv._deployment.models[0]
        assert isinstance(model.device_server(), DeviceTopK)
        status, body = query("u3")
        assert status == 200 and len(body["itemScores"]) == 5
        u = model.user_map["u3"]
        want = model.theta["out_emb"][:len(model.item_map)] \
            @ model.user_vectors[u]
        for s in body["itemScores"]:
            # the device store holds bf16 copies on an accelerator and
            # float32 ones here
            assert s["score"] == pytest.approx(
                float(want[model.item_map[s["item"]]]), rel=2e-2, abs=2e-2)
        assert query("newcomer")[1]["itemScores"] == []
        le.insert_batch([view("newcomer", f"i{7 + j}", 100 + j)
                         for j in range(5)], aid)
        deadline = time.time() + 30
        while time.time() < deadline:
            status, body = query("newcomer")
            assert status == 200
            if body["itemScores"]:
                break
            time.sleep(0.05)
        assert len(body["itemScores"]) == 5, "never folded in"
        seen = {f"i{7 + j}" for j in range(5)}
        assert not seen & {s["item"] for s in body["itemScores"]}
    finally:
        srv.stop()


def test_bf16_operands_keep_float32_masters_and_gradients(tiny):
    """``compute_dtype: bfloat16``: the matmul operands are cast once a
    step (not once a microbatch), every gradient still arrives in
    float32 on the master parameter, and agrees with the float32 one to
    what bf16 operands allow (a few percent of its scale; a missing or
    doubled gradient path would be off by its whole size)."""
    import jax

    spec = S.block_spec(S.SeqRecParams(**TINY, compute_dtype="bfloat16"))
    ids, seg, pos = (x.reshape(2, 2, 64) for x in tiny["batch"])
    loss, grads, *_ = jax.jit(functools.partial(
        S.step_gradients, spec=spec))(tiny["theta"], ids, seg, pos,
                                      tiny["negs"])
    low = S.low_precision_copies(tiny["theta"], spec)
    assert sorted(low) == sorted(
        f"l{i}_{n}" for i in range(2)
        for n in ("wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down"))
    assert all(str(v.dtype) == "bfloat16" for v in low.values())
    assert float(loss) == pytest.approx(tiny["want_loss"], rel=2e-2)
    for name, want in tiny["want_grads"].items():
        got = np.asarray(grads[name])
        assert got.dtype == np.float32
        scale = np.abs(np.asarray(want)).max()
        assert np.abs(got - np.asarray(want)).max() < 0.15 * scale, name

"""The seen bitmap's row width (ISSUE 24): one rule
(``ops.serving.seen_row_words``: whole 128-word lane tiles), the same
answers on every lane with the wider rows, and a deviceless v5e compile
of the real user-lane programs that holds no whole-bitmap copy.

The compiles need libtpu's compiler and no chip. The topology is
described inside ONE module-scoped fixture of this one file (only the
worker that is given this file loads the library); nothing runs, so
nothing here is a result or a time.
"""

import re

import numpy as np
import pytest

from predictionio_tpu.ops.serving import (
    DeviceTopK,
    bucket_size,
    seen_bitmap,
    seen_row_words,
)
from predictionio_tpu.ops.twostage import TwoStageTopK
from predictionio_tpu.parallel.als_sharding import (
    density_aware_item_layout,
)

# ---------------------------------------------------------------------------
# the width rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_pos,words", [
    (1, 128), (70, 128), (4096, 128), (4097, 256), (41216, 1408)])
def test_row_width_rule(n_pos, words):
    assert seen_row_words(n_pos) == words
    bits = seen_bitmap({0: np.asarray([0, n_pos - 1, n_pos, -1])}, 2,
                       n_pos)
    assert bits.shape == (2, words) and bits.dtype == np.int32
    u = bits.view(np.uint32)
    last = (n_pos - 1) >> 5
    assert (u[0, last] >> np.uint32((n_pos - 1) & 31)) & 1 == 1
    # the padding words lie past every real position and stay zero
    assert not u[:, last + 1:].any() and not u[1].any()


def test_row_width_never_under_the_bits_and_always_whole_tiles():
    for n_pos in list(range(1, 300)) + [4095, 8192, 8193, 26744, 27008,
                                        41140, 200_000, 384_546]:
        w = seen_row_words(n_pos)
        assert w >= -(-n_pos // 32) and w % 128 == 0
        assert w - (-(-n_pos // 32)) < 128   # at most 508 bytes a row


# ---------------------------------------------------------------------------
# the same answers on every lane: seen items in the last real position,
# and a fold-in patch of rows after growth
# ---------------------------------------------------------------------------

def _problem(n=20, m=41, r=8, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, r)).astype(np.float32)
    Y = rng.normal(size=(m, r)).astype(np.float32)
    U = rng.normal(size=(n, r)).astype(np.float32)
    E = rng.normal(size=(m, r)).astype(np.float32)
    seen = {u: rng.choice(m - 1, size=4, replace=False)
            for u in range(n)}
    # the catalog's last item sits in the last real bit of the bitmap,
    # next to the padding words: seen by the even users only
    for u in range(0, n, 2):
        seen[u] = np.append(seen[u], m - 1)
    return X, Y, U, E, seen


def _oracle(score_rows, item_rows, seen_u, k):
    s = (item_rows @ score_rows).astype(np.float32)
    s[np.asarray(seen_u, dtype=np.int64)] = -np.inf
    order = np.lexsort((np.arange(len(s)), -s))[:k]
    order = order[np.isfinite(s[order])]
    return order, s[order]


def _layout(seen, m, shards=4):
    counts = np.zeros(m, np.int64)
    for v in seen.values():
        np.add.at(counts, v, 1)
    return density_aware_item_layout(counts, shards)


LANES = ["xla", "fused", "sharded-xla", "sharded-fused",
         "two-xla", "two-fused", "two-sharded"]


@pytest.mark.parametrize("lane", LANES)
def test_lane_answers_with_wide_rows_growth_and_foldin(
        lane, monkeypatch, multichip_devices):
    two = lane.startswith("two")
    monkeypatch.setenv("PIO_SERVE_KERNEL",
                       "fused" if lane.endswith("fused") else "xla")
    X, Y, U, E, seen = _problem()
    n, m = X.shape[0], Y.shape[0]
    kw = {"microbatch": False}
    if "sharded" in lane:
        kw["item_layout"] = _layout(seen, m)
    if two:
        srv = TwoStageTopK(X, Y, U, E,
                           seen={u: v.copy() for u, v in seen.items()},
                           candidates=m, **kw)
    else:
        srv = DeviceTopK(X, Y,
                         {u: v.copy() for u, v in seen.items()}, **kw)
    one = srv.two_topk if two else srv.user_topk
    many = srv.twos_topk if two else srv.users_topk
    try:
        def check(users, Xs, Us, seen_now, k):
            bi, bs = many(np.asarray(users), k)
            for row, u in enumerate(users):
                want_i, want_s = _oracle(Us[u] if two else Xs[u],
                                         E if two else Y, seen_now[u], k)
                gi, gs = one(int(u), k)
                assert gi.tolist() == want_i.tolist(), (lane, u)
                np.testing.assert_allclose(gs, want_s, atol=1e-5)
                keep = np.isfinite(bs[row])
                assert bi[row][keep].tolist() == want_i.tolist()

        # k = the catalog: every unseen item comes back, every seen one
        # (the last real position among them) does not
        check(range(n), X, U, seen, m)
        idx, _ = one(0, m)
        assert m - 1 not in idx.tolist()
        idx, _ = one(1, m)
        assert m - 1 in idx.tolist()

        # growth, then a fold-in patch: new rows past the old capacity,
        # one of which has seen the last item, and an old user whose
        # replacement row now carries it too
        rng = np.random.default_rng(9)
        uids = np.asarray([n + 1, n + 7, 3])
        rows = rng.normal(size=(3, X.shape[1])).astype(np.float32)
        rows2 = rng.normal(size=(3, U.shape[1])).astype(np.float32)
        upd = {n + 1: np.asarray([0, m - 1]), n + 7: np.asarray([2]),
               3: np.asarray([5, m - 1])}
        srv.patch_users(uids, rows,
                        seen_items={u: v.copy() for u, v in upd.items()})
        if two:
            srv.patch_seq_users(uids, rows2)
        cap = srv.user_capacity
        assert cap >= n + 8
        Xg = np.zeros((cap, X.shape[1]), np.float32)
        Ug = np.zeros((cap, U.shape[1]), np.float32)
        Xg[:n], Ug[:n] = X, U
        Xg[uids], Ug[uids] = rows, rows2
        seen_now = {**seen, **upd}
        check([int(u) for u in uids] + [0, 1], Xg, Ug, seen_now, m)
        idx, _ = one(n + 1, m)
        assert m - 1 not in idx.tolist() and 0 not in idx.tolist()
        idx, _ = one(n + 7, m)
        assert m - 1 in idx.tolist() and 2 not in idx.tolist()

        # the grown store keeps the rule's width, and reports it
        n_pos = int(srv._Y.shape[0])
        assert srv._seen_bits.shape == (cap, seen_row_words(n_pos))
        rep = srv.memory_report()["components"]["seen"]
        assert rep["shape"] == [cap, seen_row_words(n_pos)]
        assert rep["bytes"] == cap * seen_row_words(n_pos) * 4
        host = np.asarray(srv._seen_bits).view(np.uint32)
        assert not host[:, -(-n_pos // 32):].any()
    finally:
        srv.close()


@pytest.mark.parametrize("kernel", ["xla", "fused"])
def test_catalog_over_one_lane_tile(kernel, monkeypatch):
    """4,100 items: 129 words of bits, so a row of 256; the items of
    the last word are masked and served like any other."""
    monkeypatch.setenv("PIO_SERVE_KERNEL", kernel)
    X, Y, _, _, seen = _problem(n=6, m=4100, r=8, seed=11)
    # the top of user 1's list, so that the mask has something to hide
    top = _oracle(X[1], Y, [], 4)[0]
    seen[1] = np.append(top[:2], [4099, 4096])
    srv = DeviceTopK(X, Y, seen, microbatch=False)
    try:
        assert srv._seen_bits.shape[1] == 256
        for u in range(6):
            want_i, want_s = _oracle(X[u], Y, seen[u], 16)
            gi, gs = srv.user_topk(u, 16)
            assert gi.tolist() == want_i.tolist()
            np.testing.assert_allclose(gs, want_s, atol=1e-5)
        bi, _ = srv.users_topk(np.arange(6), 16)
        assert bi[1].tolist() == _oracle(X[1], Y, seen[1], 16)[0].tolist()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the deviceless v5e compile guard: no program of the user lanes copies
# the bitmap
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


# users x items, store precision, rank: cell 2 / 4's store, chip_smoke's,
# cell 2's after one _reserve_users doubling, and the widest store
# served: cell 5's, the OLMoE block's 2,048-wide user and output tables
# (its [TM, 2048] item tile and [256, 2048] query block are what the
# kernel's tile size has to leave room for in VMEM)
SHAPES = {
    "rec-msd": (571_355, 41_140, "bf16", 64),
    "ml20m": (138_000, 27_000, "fp32", 64),
    "rec-msd-grown": (bucket_size(571_356, lo=571_355), 41_140, "bf16",
                      64),
    "seqrec-olmoe": (571_355, 41_140, "fp32", 2048),
    "seqrec-olmoe-bf16": (571_355, 41_140, "bf16", 2048),
}

COMPILES = [(shape, lane, b)
            for shape in ("rec-msd", "ml20m")
            for lane, buckets in (("fused", (1, 8, 32, 256)),
                                  ("xla", (1, 8, 32, 256)),
                                  ("two", (8, 16, 32, 256)))
            for b in buckets] + [("rec-msd-grown", lane, 8)
                                 for lane in ("fused", "xla", "two")] + [
                (shape, "fused", b)
                for shape in ("seqrec-olmoe", "seqrec-olmoe-bf16")
                for b in (1, 8, 256)]


def _real_program(lane, mode, n_items, bucket, monkeypatch):
    """The store's OWN program builder for one lane, taken from a toy
    store: the jitted closure is what a deploy at any size lowers, with
    the catalog size and "compiled, not interpreted" (which the store
    reads off the platform, and the platform here is the CPU) set as a
    v5e deploy of ``n_items`` has them."""
    monkeypatch.setenv("PIO_SERVE_KERNEL",
                       "xla" if lane == "xla" else "fused")
    monkeypatch.setenv("PIO_SERVE_PRECISION", mode)
    X, Y, U, E, seen = _problem()
    if lane == "two":
        srv = TwoStageTopK(X, Y, U, E, seen=seen, microbatch=False)
    else:
        srv = DeviceTopK(X, Y, seen, microbatch=False)
    srv.n_items, srv._interpret = n_items, False
    if lane == "two":
        return srv, srv._two_program(16, 128)
    if bucket == 1:
        return srv, srv._user_program(16)
    return srv, srv._batch_program(16, bucket)


def _kernel_scratch_rows(jaxpr):
    """(rows, lanes) of VMEM scratch of every Pallas call in a traced
    program: the rows of its ``[rows, lanes]`` 32-bit scratch buffers
    added up."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n = eqn.params["grid_mapping"].num_scratch_operands
            refs = [v.aval for v in eqn.params["jaxpr"].invars[-n:]]
            assert all(a.dtype.itemsize == 4 and len(a.shape) == 2
                       for a in refs)
            assert len({a.shape[1] for a in refs}) == 1
            found.append((sum(a.shape[0] for a in refs),
                          refs[0].shape[1]))
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                found.extend(_kernel_scratch_rows(sub))
    return found


def _assert_bitmap_read_in_place(compiled, n_rows, words):
    hlo = compiled.as_text()
    bitmap = rf"s32\[{n_rows},{words}\]"
    copies = re.findall(rf"= ({bitmap}\{{[^}}]*\}}) copy\(", hlo)
    assert not copies, f"the program re-lays the whole bitmap out: {copies}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n_rows * words * 4 / 10, \
        f"{temp / 1e9:.3f} GB of temporaries beside a " \
        f"{n_rows * words * 4 / 1e9:.3f} GB bitmap"
    params = re.findall(rf"{bitmap}(\{{[^}}]*\}}) parameter\(", hlo)
    assert params and not [p for p in params if not p.startswith("{1,0")], \
        f"the bitmap parameter is not row-major on the device: {params}"
    return hlo


@pytest.mark.parametrize("shape,lane,bucket", COMPILES)
def test_v5e_user_programs_do_not_copy_the_bitmap(shape, lane, bucket,
                                                  one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als_pallas

    n_users, n_items, mode, rank = SHAPES[shape]
    dt = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[mode]
    rows = n_items if lane == "xla" else \
        -(-n_items // als_pallas.TOPK_TILE_M) * als_pallas.TOPK_TILE_M
    words = seen_row_words(rows)

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    Xa, Ya = sds((n_users, rank), dt), sds((rows, rank), dt)
    sb = sds((n_users, words), jnp.int32)
    uids = sds(() if bucket == 1 else (bucket,), jnp.int32)
    srv, prog = _real_program(lane, mode, n_items, bucket, monkeypatch)
    try:
        args = (Xa, Ya, Ya, Xa, sb, uids) if lane == "two" \
            else (Xa, Ya, sb, uids)
        traced = prog.trace(*args)
        compiled = traced.lower().compile()
    finally:
        srv.close()
    hlo = _assert_bitmap_read_in_place(compiled, n_users, words)
    if lane != "xla":
        assert "tpu_custom_call" in hlo      # the Mosaic kernel is in it
        # the bounded merge (PR 29) holds the running list and one tile;
        # the K-round selection it replaced held the list and two
        # [K + TM] union buffers, and nothing may grow back past that
        k = 128 if lane == "two" else 16
        (rows, lanes), = _kernel_scratch_rows(traced.jaxpr.jaxpr)
        assert lanes == max(bucket, 8)
        assert rows <= 2 * k + 2 * (k + als_pallas.TOPK_TILE_M)


def test_v5e_sharded_user_program_does_not_copy_the_bitmap(
        v5e_2x2, multichip_devices, monkeypatch):
    """The sharded lane on the four described chips (item table split,
    bitmap replicated): each chip would copy its whole replica."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setenv("PIO_SERVE_KERNEL", "fused")
    monkeypatch.setenv("PIO_SERVE_PRECISION", "bf16")
    X, Y, _, _, seen = _problem()
    srv = DeviceTopK(X, Y, seen, microbatch=False,
                     item_layout=_layout(seen, Y.shape[0]))
    # the toy store's own program builder, pointed at the described mesh
    mesh = Mesh(np.asarray(v5e_2x2.devices), ("data",))
    srv._shard, srv._interpret = (mesh, "data", 4), False
    n_users, n_pos = 571_356, 41_140       # both divisible by 4 shards
    words = seen_row_words(n_pos)

    def sds(s, d, *spec):
        return jax.ShapeDtypeStruct(s, d,
                                    sharding=NamedSharding(mesh, P(*spec)))

    try:
        compiled = srv._sharded_user_program(16).lower(
            sds((n_users, 64), jnp.bfloat16, "data", None),
            sds((n_pos, 64), jnp.bfloat16, "data", None),
            sds((n_pos,), jnp.float32, "data"),
            sds((n_users, words), jnp.int32, None, None),
            sds((8,), jnp.int32, None)).compile()
    finally:
        srv.close()
    assert "tpu_custom_call" in _assert_bitmap_read_in_place(
        compiled, n_users, words)


# ---------------------------------------------------------------------------
# the ALS trainer's Pallas solve on the described chip (PR 26; kept in
# this file because the topology may be described in one file only)
# ---------------------------------------------------------------------------

# B = 300 is three grid steps, so the input block is double-buffered:
# at rank 96 the kernel then holds 13.8 MiB of VMEM and asks for it by
# name (until PR 49 kept L in the working block it was 18.3, which the
# chip refused under the compiler's default scoped limit); rank 10 is a
# sublane count off the multiple of 8
@pytest.mark.parametrize("rank", [8, 10, 64, 96])
def test_v5e_spd_solve_lowers_at_every_template_rank(rank, one_chip):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als_pallas

    assert rank <= als_pallas.SPD_MAX_RANK
    compiled = jax.jit(
        lambda A, b: als_pallas.spd_solve(A, b, interpret=False)).lower(
        jax.ShapeDtypeStruct((300, rank, rank), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((300, rank), jnp.float32,
                             sharding=one_chip)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()


# the assembly kernel beside it (PR 44): B = 300 is three solver blocks,
# the last a partial one; L = 1,100 is three chunks of a row, padded; at
# rank 96 the kernel holds 36 MiB of VMEM (its blocks of 8 MiB twice,
# the accumulator, the batch-minor output twice) and asks for them
@pytest.mark.parametrize("rank", [8, 10, 64, 96])
def test_v5e_assembly_lowers_at_every_template_rank(rank, one_chip):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als_pallas

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def equations(Y, cols, aw, bw, g0):
        return als_pallas.assemble_normal_equations(
            als_pallas.widen_table(Y), cols, aw, bw,
            als_pallas.widen_start(g0), interpret=False)

    compiled = jax.jit(equations).lower(
        sds((27_000, rank)), sds((300, 1100), jnp.int32), sds((300, 1100)),
        sds((300, 1100)), sds((rank, rank))).compile()
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert f"f32[{rank},{rank},384]" in hlo      # batch-minor, 3 blocks


# the padded bucket tables of cell rec-ml20m.train (138,000 x 27,000,
# 17.5M pairs): (rows, slots) a bucket, users then items
ML20M_BUCKETS = (
    ((8, 32), (30224, 64), (75128, 128), (23088, 256), (6840, 512),
     (1976, 1024), (560, 2048), (160, 4096), (40, 8192), (16, 16384),
     (8, 18480)),
    ((11272, 256), (9192, 512), (3808, 1024), (1608, 2048), (672, 4096),
     (280, 8192), (112, 16384), (48, 32768), (24, 65536), (8, 127144)))


def test_v5e_training_program_keeps_the_factors_in_vmem(one_chip,
                                                        monkeypatch):
    """The 5-iteration program of the training cell with the solver a
    TPU resolves: two Mosaic kernels a bucket (assembly, solve), and the
    user factors born in VMEM (``S(1)``) after the half-step's ONE
    scatter, so that every item-step gather reads them there. Scattered
    into bucket by bucket, between the kernels, they stayed in HBM and
    those gathers ran at a seventh of the rate (PERF.md section 6, PR
    26). Since PR 44 the table gathered from is the 128-lane one
    (``als_pallas.widen_table``), the assembly kernel reads each
    gathered block where the gather wrote it, and the solver reads the
    equations where the assembly wrote them: no ``copy`` of either."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als, als_pallas

    # spd_solve reads "compiled, not interpreted" off the platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert als._spd_solver_mode(64, sds((27_000, 64), jnp.float32)) \
        == "pallas"

    def side(buckets):
        return tuple((sds((b,), jnp.int32), sds((b, l), jnp.int32),
                      sds((b, l), jnp.float32), sds((b, l), jnp.float32))
                     for b, l in buckets)

    compiled = jax.jit(
        als._als_iterations_bucketed_impl,
        static_argnames=("lam", "alpha", "implicit", "num_iterations",
                         "slot_budget", "solver", "precision", "refine"),
        donate_argnums=(0, 1)).lower(
        sds((138_000, 64), jnp.float32), sds((27_000, 64), jnp.float32),
        side(ML20M_BUCKETS[0]), side(ML20M_BUCKETS[1]), lam=0.01,
        alpha=1.0, implicit=True, num_iterations=5, slot_budget=None,
        solver="pallas", precision="fp32", refine=False).compile()
    hlo = compiled.as_text()
    buckets = ML20M_BUCKETS[0] + ML20M_BUCKETS[1]
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    assert kernels == 42 and kernels <= 2 * len(buckets)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    # no gathered block is laid out anew, as written ([B, L, 64] until
    # PR 44: 20 of the 21 had a copy to {1,2,0}) or as padded to the
    # kernel's blocks ([B, Lp, 128]); every bucket takes the kernel
    copied = re.findall(r"= (f32\[\d+,\d+,\d+\])\{[^}]*\} copy\(", hlo)
    blocks = {f"f32[{b},{l},64]" for b, l in buckets} | {
        f"f32[{b},{als_pallas._assemble_blocks(b, l)[2]},"
        f"{als_pallas.ASM_LANES}]" for b, l in buckets}
    assert not blocks & set(copied), sorted(blocks & set(copied))
    # nor the equations between assembly and solve: A leaves the one
    # kernel batch-minor and whole blocks of systems wide
    systems = {f"f32[64,64,{-(-b // 128) * 128}]" for b, _ in buckets} | {
        f"f32[{b},64,64]" for b, _ in buckets}
    assert not systems & set(copied), sorted(systems & set(copied))
    defs = dict(re.findall(r"^\s*%(\S+) = (\S+)", hlo, re.M))
    for step, table, n in (("user_step", "f32[27000,128]", 11),
                           ("item_step", "f32[138000,128]", 10)):
        gathers = re.findall(
            r"^\s*%\S+ = f32\[\d+,128\]\S* fusion\(%(\S+), [^\n]*"
            rf"kind=kCustom[^\n]*{step}/gather", hlo, re.M)
        assert len(gathers) == n
        in_hbm = [t for t in gathers
                  if not (defs[t].startswith(table) and "S(1)" in defs[t])]
        assert not in_hbm, \
            f"{step} gathers read the factors in HBM: {in_hbm}"


@pytest.mark.parametrize("spec", [("data", None), (None, None)],
                         ids=["sharded", "replicated"])
def test_v5e_fold_in_against_a_store_on_four_chips_keeps_lanes(
        v5e_2x2, spec, monkeypatch):
    """``pio deploy --foldin on`` on four chips folds against the
    store's live item factors, which live on the whole mesh: the jit is
    a partitioned program, the compiler refuses a Mosaic call in one,
    and the resolver reads that off ``Y`` and names ``lanes``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from predictionio_tpu.ops import als

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("PIO_ALS_SOLVER", raising=False)
    mesh = Mesh(np.asarray(v5e_2x2.devices), ("data",))
    Y = jax.ShapeDtypeStruct((27_000, 64), jnp.float32,
                             sharding=NamedSharding(mesh, P(*spec)))
    host = [jax.ShapeDtypeStruct((8, 64), d)
            for d in (jnp.int32, jnp.float32, jnp.float32)]
    kw = dict(lam=0.01, alpha=1.0, implicit=True, precision="fp32",
              refine=False)
    assert als._resolve_spd_solver(64, (Y, *host)) == ("lanes", True)
    als._get_fold_in_jit().lower(Y, *host, solver="lanes", **kw).compile()
    with pytest.raises(Exception, match="shard_map"):
        als._get_fold_in_jit().lower(Y, *host, solver="pallas",
                                     **kw).compile()


# ---------------------------------------------------------------------------
# the session lane's extend program on the described chip (PR 31; kept
# in this file because the topology may be described in one file only)
# ---------------------------------------------------------------------------

# temporaries of the extend program at B 8 BEFORE PR 31 (the [B, T, K,
# 640] gathered latents and their products; deviceless compiles of
# commit 3c8c469), by cached-length bucket
EXTEND_TEMP_BEFORE = {16384: 202_678_784, 65536: 215_253_504}


@pytest.mark.parametrize("S", sorted(EXTEND_TEMP_BEFORE))
def test_v5e_extend_program_gathers_a_token_row_at_a_time(S, one_chip,
                                                          monkeypatch):
    """``extend_step`` at GLM-5's published widths (the cell
    ``seqrec-glm5.sess-extend``: 8 queries x 8 events, a pool of
    458,752 rows): the latents of all 64 token rows are never gathered
    at once (168 MB a layer), a layer's attend is a loop whose step
    cuts one row (``mla.index_cut``, compiled by Mosaic here) and
    holds one row's ``[K, 640]``, no pool is copied (587 MB a layer),
    the pools stay donated, and the temporaries are under those of the
    gathered form."""
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import mla
    from predictionio_tpu.ops.seqrec import SeqRecParams
    from predictionio_tpu.ops.sessions import (
        SESS_BLOCK,
        SESS_EVENTS,
        SESS_MAX_BATCH,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = SeqRecParams(
        block="glm_moe_dsa", rank=6144, n_heads=64, n_layers=6,
        n_dense_layers=1, norm="rmsnorm", norm_eps=1e-5, positions="rope",
        rope_theta=1e6, tied=False, q_lora_rank=2048, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        index_n_heads=32, index_head_dim=128, index_topk=2048,
        dense_width=12288, n_experts=256, expert_width=2048,
        experts_per_token=8, n_shared_experts=1,
        routed_scaling_factor=2.5, experts_held=16, expert_share=0,
        compute_dtype="bfloat16", num_steps=0, seeded_weights=True)
    spec = mla.glm_spec(params)
    V, bf16 = 19_360, jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    theta = {name: sds(shape, bf16 if mla.is_low(name) else jnp.float32)
             for name, shape, _ in mla.theta_shapes(V, spec)}
    Y = theta.pop("out_emb")
    bs = SESS_BLOCK
    nb = 1 + 458_752 // bs
    lat = tuple(sds((nb, bs, spec.lat_width), bf16)
                for _ in range(spec.n_layers))
    ik = tuple(sds((nb, bs, spec.idx_dim), bf16)
               for _ in range(spec.n_layers))
    T, B, K, W = SESS_EVENTS, SESS_MAX_BATCH, spec.idx_topk, spec.lat_width
    compiled = jax.jit(functools.partial(
        mla.extend_step, spec=spec, kb=128, T=T, S=S, bs=bs, n_items=V,
        mode="bf16", mask_seen=True), donate_argnums=(1, 2, 3, 4)).lower(
        theta, sds((24, spec.width), bf16), sds((24, 640), jnp.int32),
        lat, ik, Y, sds((B, 3 + 2 * T + S // bs), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert f"bf16[{B},{T},{K},{W}]" not in hlo
    assert f"bf16[{B * T},{K},{W}]" not in hlo
    assert f"bf16[{K},{W}]" in hlo               # one token row's latents
    # the cut is the kernel's, a row a loop step (PR 34): nothing sorts
    # the bucket's index scores
    assert "dsa_index_cut" in hlo
    assert not [ln for ln in hlo.splitlines() if " sort(" in ln and (
        f"f32[{B},{T},{S}]" in ln or f"f32[{B * T},{S}]" in ln)]
    pool_rows = rf"bf16\[(?:{nb},{bs}|{nb * bs}),{W}\]"
    copies = re.findall(rf"= ({pool_rows}\S*) copy\(", hlo)
    assert not copies, f"a latent pool is copied: {copies}"
    mem = compiled.memory_analysis()
    pool = 2 * nb * bs * (W + spec.idx_dim) * spec.n_layers
    assert mem.alias_size_in_bytes >= pool      # the pools, in place
    assert mem.temp_size_in_bytes < EXTEND_TEMP_BEFORE[S]

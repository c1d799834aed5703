"""Pallas TPU kernels for ALS.

Three kernels live here, two the trainer's and one the server's:

1. ``spd_solve`` — batched symmetric positive-definite solve (Cholesky
   factorization + forward/backward triangular substitution fused in
   one kernel, batch on the lane dimension, matrices resident in VMEM
   across all R steps). STATUS — the TPU default of every ALS trainer
   entry point and of fold-in for rank <= ``SPD_MAX_RANK`` since PR 26
   (``ops.als._resolve_spd_solver``); above that rank, and in every
   program partitioned over several devices (the sharded trainers,
   fold-in against a sharded serving store: the TPU compiler refuses
   to partition a Mosaic call), the pure-XLA batch-on-lanes panel
   factorization ``ops.als.spd_solve_lanes`` runs, and off the TPU
   LAPACK's ``cho_solve``. ``PIO_ALS_SOLVER`` still forces any of the
   three. A checkpoint carries the solver's name in its fingerprint,
   so one written under ``lanes`` is refused on resume under
   ``pallas`` (``CheckpointMismatchError``). Runs through Mosaic on a
   v5e at ranks 8, 10, 20, 50, 64 and 96 and agrees with LAPACK there
   (rank 96 holds 13.8 MiB of VMEM and asks for it by name); 16,384
   rank-64 systems take 1.88 ms where ``lanes`` took 29.6 (PERF.md
   sections 5 and 6, PR 26). Until PR 49 each of the R steps updated
   the whole ``R x R`` block (3.68 ms); now a step updates the row
   blocks that still have a live row, from each block's diagonal on,
   and ``x`` is the same to the bit. What is left is half stores and
   half the serial head of a step (pivot, ``sqrt``, divide, the
   sublane shuffles): PERF.md section 7.
   ``spd_solve_batch_minor`` is the same kernel on systems that lie
   batch-minor already, which is how kernel 2 writes them.

2. ``assemble_normal_equations`` — the gather and the fp32 normal
   equations of one batch of rows (PR 44). STATUS — runs wherever
   ``spd_solve`` was resolved, in the fp32 lane
   (``ops.als.assembles_in_kernel``: the bucketed trainer, the grid
   trainer under ``vmap``, fold-in; CPU tests interpret it under
   ``PIO_ALS_SOLVER=pallas``); everything else (``lanes``, ``cho``, the
   bf16 lane, the sharded trainers) keeps XLA's einsums
   (``ops.als._assemble_fp32``). The factor table is gathered as rows
   of 128 lanes (``widen_table``), because a Mosaic DMA moves a block
   of 64-lane rows, which lie in HBM padded to 128, at 132 GB/s and a
   block of whole rows at 693, and the gather writes either in the same
   time; the kernel reads each ``[rows, slots, 128]`` block once where
   the gather wrote it (XLA's einsum wants the block re-laid slots-minor
   first: a fifth of an iteration was that copy), transposes a row's
   slots onto the lanes in VMEM, and takes eight rows a batched MXU
   product at ``Precision.HIGHEST``; ``b`` rides in the same product.
   The sums start from Gram + ridge, stay in VMEM over a row's chunks
   and leave the kernel batch-minor, ``[R, R, 128]`` a grid step, so
   nothing passes over ``A`` between the two kernels (XLA spent a tenth
   of an iteration on three such passes). On a v5e the ML-20M iteration
   went from 284 to 185 ms (PERF.md sections 5 and 6, PR 44); the kernel
   is bound by its DMA (0.8-1.2 ns a slot of 512 bytes), then by the
   batch-minor write-out of short rows (PERF.md section 7).

3. ``fused_gather_score_topk`` — the SERVING kernel (ROADMAP item 4):
   score matvec + seen-row masking + top-k selection fused into one
   program. The XLA chain dispatches gather/einsum/mask/top_k as
   separate HLOs whose ``[B, M]`` score intermediate round-trips HBM
   between the einsum and the top_k; here each ``[TM, R]`` item-factor
   tile streams HBM->VMEM exactly once (int8 tiles dequantize against
   their per-row scales in VMEM — the Tensor Casting co-design axis),
   is scored on the MXU against the whole query block, masked in
   registers from the packed seen bitmap, and folded into a running
   per-query top-k held in VMEM across the grid; only the final
   ``[B, k]`` winners ever reach HBM. The fold is a bounded merge
   (:func:`_topk_select_body`, PR 29): a tile costs as many insertion
   rounds as the query that gains most from it has scores over its
   k-th, none when no score beats any query's k-th (the early-out),
   and the pass reports the rounds it ran (``selectRounds`` on the
   dispatch record, ``pio_topk_select_rounds_total``). STATUS: the
   production device path for ``DeviceTopK`` and ``TwoStageTopK``
   (``PIO_SERVE_KERNEL=xla`` opts out; CPU serves the XLA chain and
   exercises this kernel in interpret mode, like ``spd_solve``); its
   answers equal ``lax.top_k``'s to the bit on fp32, bf16 and int8
   stores. On the v5e at 41,140 bf16 items (my chip runs, PR 29) a
   top-128 pass for 8 queries takes 0.40 ms at 1,520 rounds where the
   K-rounds-a-tile selection it replaced took 5.28 ms at 42,000 (256
   queries: 0.64 against 5.68), a top-16 pass with the seen mask 0.25
   against 0.41, top-64 0.29 against 1.97; scores that RISE with the
   item id, the worst case, cost min(K, 128) rounds on every tile, the
   old kernel's count: 4.61 ms against its 5.34 (256 queries: 6.44
   against 5.69; K = 16: 0.54, 0.57). The two-stage cell's median query
   went from 20.0 to 9.1 ms (PERF.md 5-6; ROADMAP Speed 3). The batch sits
   on the lane axis, so 8 queries use 8 lanes of 128.

Run on CPU (tests) via interpret mode — semantics identical, speed not.
"""

from __future__ import annotations

import functools
from typing import Optional


# ---------------------------------------------------------------------------
# Batched SPD solve (the production kernel)
# ---------------------------------------------------------------------------

# systems per grid step == the lane width: each per-step scalar (pivot,
# reciprocal sqrt, substitution coefficient) is a [BB]-lane vector
_SPD_BB = 128
# room beside the kernel's own buffers for Mosaic's internal scratch
_SPD_VMEM_MARGIN = 4 << 20
# rows of a block of the trailing update: whole sublane tiles, so that a
# block's columns start on one. Blocks of 8 store a quarter less and
# take the same time on a v5e (twice the predicates) for 44 more
# equations a kernel to trace and lower (PERF.md section 6, PR 49)
_SPD_ROWS = 16


def _spd_solve_kernel(a_ref, b_ref, x_ref, awork, ywork, bwork):
    """Solve ``A x = b`` for one block of ``BB`` SPD systems.

    Layout is the whole trick: the batch lives on the LANE dimension
    (``a_ref [R, R, BB]``), so every step of the non-pivoted
    right-looking Cholesky — pivot extraction, column scaling, rank-1
    trailing update — is a full-width VPU op over BB systems at once,
    and row/column extraction is leading-dim indexing (sublane), never
    dynamic lane slicing. The matrices stay in VMEM scratch across all
    R steps; HBM sees each system exactly once in and once out. (XLA's
    batched Cholesky/triangular ops round-trip HBM per step — the
    measured ALS bottleneck this kernel replaces.)

    The trailing update uses the symmetry of A: column k == row k, so
    the pivot column is ``awork[k]`` directly, and only the upper
    triangle is ever read. What step ``k`` writes (PR 49): the rows are
    cut into blocks of ``_SPD_ROWS``, a block that still has a row
    ``i > k`` gets ``a[i, j] -= u_i u_j`` on its rows and on the
    columns from its own first row on (the upper triangle by block,
    the diagonal block whole; ``u`` is zero up to ``k``, so the rows
    ``<= k`` of the block the step stands in are rewritten as they
    were), and a block the steps have passed is skipped (but the last,
    which only the last step has passed). Then row
    ``k``, dead from here on, takes ``L``'s column ``k``, zeros above
    the diagonal: the substitutions read ``L^T`` out of ``awork``.
    Every entry that is read has seen the arithmetic of the whole-block
    update this replaces, in its order: ``x`` is that kernel's to the
    bit (``tests/als_reference.py::spd_solve_whole_block``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    R = a_ref.shape[0]
    awork[:] = a_ref[:]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)   # [R, 1]

    def fact_step(k, _):
        c = awork[k]                                # [R, BB] column k
        d = jnp.maximum(awork[k, k], 1e-30)         # [BB] pivot (ref load)
        inv = 1.0 / jnp.sqrt(d)
        ge = (iota_r >= k).astype(jnp.float32)
        lcol = c * inv[None, :] * ge                # L[:, k], rows >= k
        u = lcol * (iota_r > k).astype(jnp.float32)
        for lo in range(0, R, _SPD_ROWS):
            hi = min(lo + _SPD_ROWS, R)

            def update():                           # traced in this pass
                awork[lo:hi, lo:, :] = (
                    awork[lo:hi, lo:, :]
                    - u[lo:][None, :, :] * u[lo:hi][:, None, :])

            # the last block is dead at the last step alone, where u is
            # zero: no predicate (and none at all up to _SPD_ROWS ranks)
            if hi == R:
                update()
            else:
                pl.when(k < hi - 1)(update)
        awork[k] = lcol                             # Lt row k == L col k
        return 0

    jax.lax.fori_loop(0, R, fact_step, 0)

    # forward substitution L y = b, column sweep: rows < k of Lt's row k
    # are zero, so the update never touches already-solved entries
    bwork[:] = b_ref[:]

    def fwd_step(k, _):
        yk = bwork[k] / awork[k, k]
        ywork[k] = yk
        bwork[:] = bwork[:] - awork[k] * yk[None, :]
        return 0

    jax.lax.fori_loop(0, R, fwd_step, 0)

    # backward substitution Lt x = y, row sweep from the bottom
    x_ref[:] = jnp.zeros_like(b_ref[:])

    def bwd_step(i, _):
        k = R - 1 - i
        ltk = awork[k]                              # Lt row k over j >= k
        s = jnp.sum(ltk * x_ref[:], axis=0)         # x[k] still 0
        x_ref[k] = (ywork[k] - s) / awork[k, k]
        return 0

    jax.lax.fori_loop(0, R, bwd_step, 0)


@functools.lru_cache(maxsize=32)
def _build_spd(B: int, R: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert B % _SPD_BB == 0
    # what the kernel holds in VMEM, to the byte: the [R, R, BB] input
    # block twice (the pipeline double-buffers it), awork once, b and x
    # twice, ywork and bwork once: 13.8 MiB at rank 96
    vmem_bytes = 4 * _SPD_BB * (3 * R * R + 6 * R)
    fn = pl.pallas_call(
        _spd_solve_kernel,
        grid=(B // _SPD_BB,),
        in_specs=[
            pl.BlockSpec((R, R, _SPD_BB), lambda i: (0, 0, i)),
            pl.BlockSpec((R, _SPD_BB), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((R, _SPD_BB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((R, B), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((R, R, _SPD_BB), jnp.float32),   # awork
            pltpu.VMEM((R, _SPD_BB), jnp.float32),      # ywork
            pltpu.VMEM((R, _SPD_BB), jnp.float32),      # bwork
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes + _SPD_VMEM_MARGIN),
        interpret=interpret,
        name="spd_solve",
    )
    return fn


# above this rank ops.als._resolve_spd_solver names spd_solve_lanes: the
# line ISSUE 26 drew (the kernel's three [R, R, BB] buffers are 24 MiB
# of VMEM at rank 128; PERF.md section 7)
SPD_MAX_RANK = 96


def spd_solve(A, b, interpret: Optional[bool] = None):
    """Batched SPD solve ``x: A @ x = b`` with ``A [B, R, R]``,
    ``b [B, R]`` — the Pallas replacement for
    ``cho_solve(cho_factor(A), b)``. Same math (non-pivoted Cholesky,
    fp32); agreement asserted against scipy in tests and in the bench's
    finiteness checks. The batch is padded to the kernel's lane-block
    size with identity systems internally; inputs are transposed to the
    kernel's batch-on-lanes layout (XLA fuses the transpose into the
    producing einsum)."""
    import jax.numpy as jnp

    B, R = b.shape
    At = jnp.transpose(A.astype(jnp.float32), (1, 2, 0))   # [R, R, B]
    bt = b.astype(jnp.float32).T                           # [R, B]
    pad = (-B) % _SPD_BB
    if pad:
        eye = jnp.broadcast_to(jnp.eye(R, dtype=jnp.float32)[:, :, None],
                               (R, R, pad))
        At = jnp.concatenate([At, eye], axis=2)
        bt = jnp.concatenate([bt, jnp.zeros((R, pad), jnp.float32)],
                             axis=1)
    return spd_solve_batch_minor(At, bt, interpret)[:, :B].T


def spd_solve_batch_minor(At, bt, interpret: Optional[bool] = None):
    """:func:`spd_solve` on systems that are batch-minor already:
    ``At [R, R, Bq]``, ``bt [R, Bq]`` with ``Bq`` whole blocks of 128
    systems (what :func:`assemble_normal_equations` writes); ``x^T [R,
    Bq]``."""
    import jax

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    R, Bq = bt.shape
    return _build_spd(Bq, R, bool(interpret))(At, bt)


# ---------------------------------------------------------------------------
# Normal-equation assembly from the gathered block (the trainer's second
# kernel)
# ---------------------------------------------------------------------------

# the gathered rows are whole lane tiles: a [*, 64] float32 array lies
# in HBM as rows of 128 lanes with half of each row padding, and a DMA
# of such a block moves 256-byte pieces at a fifth of the rate it moves
# whole rows (132 against 693 GB/s on a v5e); the gather itself writes a
# 128-lane row as fast as the padded 64-lane one (PERF.md 6, PR 44)
ASM_LANES = 128
# rows a batched product takes: one sublane tile of the weights
_ASM_G = 8
# the most slots a grid step holds of one row, and of all its rows: a
# [TB, Lc, 128] block of at most 8 MiB, double-buffered
_ASM_LC = 512
_ASM_SLOTS = 16384
# entries of the batch-minor write-out unrolled in a loop step (rolled
# up, a strided read waits for the one before)
_ASM_EMIT = 4


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _assemble_kernel(g0_ref, aw_ref, bw_ref, z_ref, at_ref, bt_ref,
                     acc, bacc, *, B: int):
    """``A_b = G0 + sum_l aw[b, l] z_l z_l^T`` and ``b_b = sum_l
    bw[b, l] z_l`` for the ``_SPD_BB`` rows of grid step ``i``, taken
    ``TB`` rows (axis 1) and ``Lc`` slots (axis 2) at a time and
    written batch-minor at the block's last step, as the solver reads
    them.

    ``z_ref [TB, Lc, 128]`` is the gathered block as the gather wrote it:
    a slot a sublane, the factor on the lanes. A row's product contracts
    over its slots, so the row's block is transposed once in VMEM (slots
    to the lanes), scaled there by the row's weights, which lie along
    the lanes as they came, and multiplied on the MXU against the block
    itself: ``[Rp, Lc] @ [Lc, 128]``, fp32 operands at
    ``Precision.HIGHEST``, eight rows a batched product. The ``b``
    weights of the eight rows ride as eight more rows of every left
    operand, so ``b`` costs no second read. Row
    ``b``'s sums live in ``acc[b * Rp:(b + 1) * Rp]``; rows at and
    beyond ``B`` are never touched and leave the kernel as ``G0`` and a
    zero right-hand side, systems the solver can take."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    TB, Lc, W = z_ref.shape
    R = at_ref.shape[0]
    Rp = g0_ref.shape[0]
    G = _ASM_G
    i, s, j = (pl.program_id(a) for a in range(3))
    first = jnp.logical_and(s == 0, j == 0)
    last = jnp.logical_and(s == pl.num_programs(1) - 1,
                           j == pl.num_programs(2) - 1)

    @pl.when(first)
    def _():
        def fill(b, _):
            acc[pl.ds(pl.multiple_of(b * Rp, 8), Rp), :] = g0_ref[...]
            return 0

        jax.lax.fori_loop(0, _SPD_BB, fill, 0)
        bacc[...] = jnp.zeros_like(bacc)

    # the rows of this block that exist, whole sublane tiles of them
    # (lax and not jnp for the index arithmetic: every jnp call is a
    # nested jit to trace and lower, in 21 kernels a program)
    n_rows = jax.lax.clamp(0, B - (i * _SPD_BB + s * TB), TB)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (G, G, 1), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (G, G, 1), 1)
           ).astype(jnp.float32)

    def group(k, _):
        # eight rows in one batched product: as fast as eight unrolled
        # products (rolled up, a row waits for the one before: 2.4 times
        # the time at L 128) for an eighth of the ops to trace and lower
        r0 = pl.multiple_of(k * G, G)             # in this block of rows
        b0 = s * TB + r0                          # in the step's 128
        z = z_ref[pl.ds(r0, G)]                           # [G, Lc, 128]
        lhs = jnp.concatenate(
            [jnp.swapaxes(z, 1, 2)[:, :Rp]
             * aw_ref[pl.ds(r0, G), :].reshape(G, 1, Lc),
             jnp.broadcast_to(bw_ref[pl.ds(r0, G), :][None], (G, G, Lc))],
            axis=1)                                       # [G, Rp + G, Lc]
        out = jax.lax.dot_general(
            lhs, z, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)           # [G, Rp + G, 128]
        acc[pl.ds(pl.multiple_of(b0 * Rp, 8), G * Rp), :] += \
            out[:, :Rp].reshape(G * Rp, W)
        # row g's b is its own weights' product: [g, Rp + g, :]
        bacc[pl.ds(pl.multiple_of(b0, G), G), :] += \
            jnp.sum(out[:, Rp:] * eye, axis=1)
        return 0

    jax.lax.fori_loop(0, jax.lax.div(n_rows, G), group, 0)

    @pl.when(last)
    def _():
        # entry (r, :) of every row's matrix, one strided read: [128
        # rows, 128 lanes] -> transposed, the rows on the lanes
        def emit(r):
            at_ref[r] = acc[pl.ds(r, _SPD_BB, stride=Rp), :].T[:R]

        def emit_some(k, _):
            jax.lax.fori_loop(
                0, _ASM_EMIT, lambda u, _: emit(k * _ASM_EMIT + u), None,
                unroll=True)
            return 0

        jax.lax.fori_loop(0, R // _ASM_EMIT, emit_some, 0)
        for r in range(R // _ASM_EMIT * _ASM_EMIT, R):
            emit(r)
        bt_ref[...] = bacc[...].T[:R]


def _assemble_blocks(B: int, L: int):
    """``(TB, Lc, Lp)``: rows and slots of a block, and ``L`` padded to
    whole blocks (to the sublane tile under ``_ASM_LC`` slots). ``TB``
    divides the 128 rows of a grid step."""
    Lp = _ceil_to(L, 8 if L <= _ASM_LC else _ASM_LC)
    Lc = min(Lp, _ASM_LC)
    TB = _ASM_G
    while 2 * TB * Lc <= _ASM_SLOTS and 2 * TB <= min(_SPD_BB, B):
        TB *= 2
    return TB, Lc, Lp


@functools.lru_cache(maxsize=64)
def _build_assemble(B: int, Lp: int, R: int, TB: int, Lc: int,
                    interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W, BB = ASM_LANES, _SPD_BB
    Rp = _ceil_to(R, 8)
    n_blocks = -(-B // TB)                  # blocks of rows that exist
    steps = -(-B // BB)
    per_step = -(-min(B, BB) // TB)

    def rows(i, s, j):
        # the last step's blocks past B do not exist: they re-read the
        # last that does, and the body skips them
        return jnp.minimum(i * (BB // TB) + s, n_blocks - 1)

    # the gathered block, the weights' blocks and both outputs twice
    # (the pipeline double-buffers them), the accumulators once
    vmem_bytes = 4 * (2 * TB * Lc * W + 4 * TB * Lc + 2 * Rp * W
                      + BB * Rp * W + BB * W + 2 * R * R * BB + 2 * R * BB)
    return pl.pallas_call(
        functools.partial(_assemble_kernel, B=B),
        grid=(steps, per_step, Lp // Lc),
        in_specs=[
            pl.BlockSpec((Rp, W), lambda i, s, j: (0, 0)),
            pl.BlockSpec((TB, Lc), lambda i, s, j: (rows(i, s, j), j)),
            pl.BlockSpec((TB, Lc), lambda i, s, j: (rows(i, s, j), j)),
            pl.BlockSpec((TB, Lc, W),
                         lambda i, s, j: (rows(i, s, j), j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((R, R, BB), lambda i, s, j: (0, 0, i)),
            pl.BlockSpec((R, BB), lambda i, s, j: (0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((R, R, steps * BB), jnp.float32),
                   jax.ShapeDtypeStruct((R, steps * BB), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((BB * Rp, W), jnp.float32),    # acc
                        pltpu.VMEM((BB, W), jnp.float32)],        # bacc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes + _SPD_VMEM_MARGIN),
        interpret=interpret,
        name="als_assemble",
    )


def widen_table(Y):
    """``Y [M, R]`` as the ``[M, 128]`` float32 table the assembly
    gathers from: zeros beyond ``R``. Behind a barrier, or XLA moves
    the padding behind the gather and pads every gathered block in a
    pass of its own."""
    import jax
    import jax.numpy as jnp

    R = Y.shape[1]
    if R > ASM_LANES:
        raise ValueError(
            f"the assembly kernel takes rank <= {ASM_LANES}, got {R}")
    return jax.lax.optimization_barrier(
        jnp.pad(Y.astype(jnp.float32), ((0, 0), (0, ASM_LANES - R))))


def widen_start(g0):
    """``g0 [R, R]``, what every row's ``A`` starts from, as the kernel
    takes it: ``[R, 128]`` float32, zeros beyond ``R``."""
    import jax.numpy as jnp

    return jnp.pad(g0.astype(jnp.float32),
                   ((0, 0), (0, ASM_LANES - g0.shape[0])))


def assemble_normal_equations(Yw, cols, aw, bw, g0,
                              interpret: Optional[bool] = None):
    """``A_b = g0 + sum_l aw[b, l] y_l y_l^T`` and ``b_b = sum_l
    bw[b, l] y_l`` with ``y_l = Yw[cols[b, l]]``: the gather and the
    normal equations of one batch of rows, fp32 throughout, the block
    read once where the gather wrote it. Returned BATCH-MINOR, as
    :func:`spd_solve_batch_minor` takes them: ``At [R, R, Bq]`` and
    ``bt [R, Bq]`` with ``Bq`` the rows rounded up to whole solver
    blocks, the rows past ``B`` holding ``g0`` and zeros.

    ``Yw`` is :func:`widen_table`'s and ``g0 [R, 128]``
    :func:`widen_start`'s: rows of 128 lanes, so that both the gather
    and the kernel's DMA move whole rows. The slot axis is padded to
    whole blocks, and the rows (``g0``'s too) to the sublane tile, with
    slots of weight zero."""
    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, L = cols.shape
    R = g0.shape[0]
    if R % 8:
        g0 = jnp.pad(g0, ((0, (-R) % 8), (0, 0)))
    B8 = _ceil_to(B, _ASM_G)
    TB, Lc, Lp = _assemble_blocks(B8, L)
    if (B8, Lp) != (B, L):
        grow = ((0, B8 - B), (0, Lp - L))
        cols, aw, bw = (jnp.pad(a, grow) for a in (cols, aw, bw))
    with jax.named_scope("gather"):
        # one gather primitive (jnp.take wraps it in a jit of its own
        # to trace and lower, once a bucket); the columns are in bounds
        z = jax.lax.gather(                               # [B8, Lp, 128]
            Yw, cols[..., None],
            jax.lax.GatherDimensionNumbers(
                offset_dims=(2,), collapsed_slice_dims=(0,),
                start_index_map=(0,)),
            slice_sizes=(1, ASM_LANES), mode="clip")
    with jax.named_scope("assemble"):
        return _build_assemble(B8, Lp, R, TB, Lc, bool(interpret))(
            g0, aw, bw, z)


# ---------------------------------------------------------------------------
# Fused serving kernel: score matvec + seen mask + top-k in one program
# ---------------------------------------------------------------------------

# item rows per grid step: one f32 tile of the streamed factor table.
# DeviceTopK pads its item store to this multiple ONCE at construction
# so dispatches never pay a per-call pad copy. With the selection
# rounds bounded (PR 29) a tile of 512 rows was 7% (K = 128) to 29%
# (K = 16) faster on a v5e at rank 64, and does not fit VMEM at rank
# 2048 with 256 queries (the sequence lane's store; the [TM, R] tile is
# held twice and once more as f32): 128 fits every store served today
# (tests/test_seen_bitmap_layout.py compiles the widest for a v5e).
TOPK_TILE_M = 128

# query block rounds up to a lane-friendly multiple (scores sit [TM, B]
# with the batch on the lane dimension)
_TOPK_B_ALIGN = 8

# seen items travel as a packed bitmap: one int32 word per 32 store
# positions (see pack_seen_bits)
SEEN_WORD_BITS = 32


def pack_seen_bits(hit):
    """``[..., P]`` boolean hit mask -> ``[..., ceil(P / 32)]`` int32
    words, bit ``j`` of word ``w`` = position ``32 * w + j`` (the layout
    the kernel unpacks per tile and ``ops.serving.seen_bitmap`` builds
    on host)."""
    import jax.numpy as jnp

    P = hit.shape[-1]
    pad = (-P) % SEEN_WORD_BITS
    if pad:
        hit = jnp.pad(hit, [(0, 0)] * (hit.ndim - 1) + [(0, pad)])
    h = hit.reshape(hit.shape[:-1] + (-1, SEEN_WORD_BITS))
    shifts = jnp.arange(SEEN_WORD_BITS, dtype=jnp.int32)
    # distinct bits: the int32 sum wraps exactly like a bitwise OR
    return jnp.sum(jnp.left_shift(h.astype(jnp.int32), shifts),
                   axis=-1, dtype=jnp.int32)


def pack_seen_ids(ids, live, n_pos: int):
    """``[B, L]`` position lists (``live`` marks the real slots) ->
    the ``[B, ceil(n_pos / 32)]`` packed bitmap. For SHORT lists — a
    similarity query masking its own query items; a user's history
    lives in the store's bitmap already."""
    import jax.numpy as jnp

    ids = jnp.asarray(ids, dtype=jnp.int32)
    live = jnp.asarray(live) & (ids >= 0) & (ids < n_pos)
    hit = jnp.zeros((ids.shape[0], n_pos), dtype=jnp.bool_).at[
        jnp.arange(ids.shape[0])[:, None],
        jnp.where(live, ids, n_pos)].set(True, mode="drop")
    return pack_seen_bits(hit)


def unpack_seen_bits(words, n_pos: int):
    """Inverse of :func:`pack_seen_bits`: ``[..., W]`` int32 words ->
    ``[..., n_pos]`` boolean hit mask."""
    import jax.numpy as jnp

    shifts = jnp.arange(SEEN_WORD_BITS, dtype=jnp.int32)
    # (arithmetic shift: the sign fill never reaches bit 0)
    h = jnp.right_shift(words[..., None], shifts) & 1
    return h.reshape(words.shape[:-1] + (-1,))[..., :n_pos] > 0


def _topk_select_body(scores, off, rounds, run_v, run_i, work, K):
    """Fold one ``[TM, B]`` score tile into the running per-query
    top-K (``run_v``/``run_i`` [K, B], value-sorted descending) in
    ``rounds`` selection rounds (int32 scalar, :func:`_topk_rounds`).

    A round takes each query's best REMAINING tile score (max + first
    match over the tile alone) and, for the queries where it beats
    their current k-th, inserts it into the sorted list by rank: the
    rows from the rank down shift by one, the last falls off. A query
    that is done sits the remaining rounds out. Every per-round op is
    a full-width VPU select or sublane reduction, nothing indexes a
    lane dynamically.
    Tie-breaking matches ``jax.lax.top_k`` (lowest index wins): a
    newcomer's rank counts the running values ``>=`` it, which are
    earlier tiles' (lower ids) or this tile's earlier rows (first
    match), and a score equal to the k-th never enters."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    TM = scores.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (TM, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
    work[:] = scores

    def insert(_, m):                                 # m [1, B]
        w = work[:]
        am = jnp.min(jnp.where(w == m, pos, TM), axis=0, keepdims=True)
        rv, ri = run_v[:], run_i[:]
        rank = jnp.sum((rv >= m).astype(jnp.int32), axis=0, keepdims=True)
        rank = jnp.where(m > rv[K - 1:K], rank, K)    # done: no row
        run_v[:] = jnp.where(row == rank, m,
                             jnp.where(row > rank, pltpu.roll(rv, 1, 0),
                                       rv))
        run_i[:] = jnp.where(row == rank, am + off,
                             jnp.where(row > rank, pltpu.roll(ri, 1, 0),
                                       ri))
        w = jnp.where(pos == am, -jnp.inf, w)
        work[:] = w
        return jnp.max(w, axis=0, keepdims=True)

    jax.lax.fori_loop(0, rounds, insert,
                      jnp.max(scores, axis=0, keepdims=True))


def _topk_rounds(scores, kth, K):
    """The selection rounds one ``[TM, B]`` tile earns against the
    queries' current k-th scores ``kth [1, B]``: best first against a
    rising k-th, a query inserts at most as many scores as beat its
    k-th when the tile arrives, so the count is that number for the
    query with most of them, ``min(K, TM)`` at most, taken in one pass
    before the loop: none for a tile no query gains from, 3-5 of
    K = 128 in the middle of a pass, every row when the scores rise
    with the item id. (A ``while`` on "any query still gains" runs a
    tenth fewer rounds and each costs twice as much on a v5e: the
    vector-to-scalar hop of its condition; my chip run, PR 28, call 1.)"""
    import jax.numpy as jnp

    newcomers = jnp.sum((scores > kth).astype(jnp.int32), axis=0)
    return jnp.minimum(jnp.max(newcomers), min(K, scores.shape[0]))


def _fused_topk_body(q_ref, yd_ref, ys_ref, rv_ref, sb_ref,
                     vals_ref, idx_ref, rounds_ref, run_v, run_i, work,
                     *, K, n_items, n_tiles):
    """One grid step = one ``[TM, R]`` item tile scored, masked, and
    merged (see module docstring). ``ys_ref`` is None for dense f32/
    bf16 stores; for int8 stores it carries the tile's per-row fp32
    scales and the dequantize happens here in VMEM — HBM only ever
    streams the int8 bytes. ``sb_ref`` (None = no seen mask) is the
    tile's ``[TM // 32, B]`` slice of the packed seen bitmap: bit ``j``
    of word ``w`` masks tile row ``32 * w + j``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    TM = yd_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        run_v[:] = jnp.full(run_v.shape, -jnp.inf, run_v.dtype)
        run_i[:] = jnp.zeros(run_i.shape, run_i.dtype)
        rounds_ref[0, 0] = 0

    off = t * TM
    y = yd_ref[:].astype(jnp.float32)
    if ys_ref is not None:
        y = y * ys_ref[:]                             # [TM, R] * [TM, 1]
    # [TM, B] tile scores on the MXU, fp32 accumulate (HIGHEST matches
    # the XLA chain's fp32 einsum passes)
    scores = jax.lax.dot_general(
        y, q_ref[:], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    item_ids = jax.lax.broadcasted_iota(jnp.int32, (TM, 1), 0) + off
    # padded factor rows (index >= n_items) never reach the top-k
    scores = jnp.where(item_ids < n_items, scores, -jnp.inf)
    if rv_ref is not None:
        # per-row validity column (density-sharded stores: a shard's
        # real items are bin-packed, not a contiguous prefix, so a
        # static n_items bound cannot express them)
        scores = jnp.where(rv_ref[:] > 0, scores, -jnp.inf)
    if sb_ref is not None:
        # unpack the tile's seen words into a [TM, B] hit mask: one
        # variable right-shift per 32-row group, no loop over a seen
        # LIST — the mask costs the same whether a user has seen ten
        # items or ten thousand
        words = sb_ref[0]                             # [TM // 32, B]
        Bq = words.shape[1]
        bit = jax.lax.broadcasted_iota(jnp.int32, (SEEN_WORD_BITS, Bq), 0)
        hit = jnp.concatenate([
            jax.lax.shift_right_logical(
                jnp.broadcast_to(words[j:j + 1, :],
                                 (SEEN_WORD_BITS, Bq)), bit) & 1
            for j in range(TM // SEEN_WORD_BITS)], axis=0)
        scores = jnp.where(hit > 0, -jnp.inf, scores)

    # the early-out is the zero-round case: a tile with no score over
    # any query's current k-th never changes the list (ties lose to the
    # running entry, which is always an earlier == lower item id)
    rounds = _topk_rounds(scores, run_v[K - 1:K], K)

    @pl.when(rounds > 0)
    def _merge():
        rounds_ref[0, 0] += rounds
        _topk_select_body(scores, off, rounds, run_v, run_i, work, K)

    @pl.when(t == n_tiles - 1)
    def _out():
        vals_ref[:] = run_v[:]
        idx_ref[:] = run_i[:]


def fused_gather_score_topk(Q, Y, seen_bits=None, *,
                            k: int, n_items: int, mask_seen: bool = True,
                            row_valid=None,
                            interpret: Optional[bool] = None,
                            tile_m: Optional[int] = None):
    """The fused serving program: ``top_k(mask(Y @ Q^T))`` with the
    item table streamed HBM->VMEM exactly once.

    ``Q [B, R]`` fp32 query rows (gathered + dequantized user factors,
    or summed similarity-query rows — the gather lowers into the same
    jitted program as this call); ``Y`` the item store — a dense
    ``[M, R]`` fp32/bf16 table or an int8
    :class:`~predictionio_tpu.ops.quantize.QuantFactors` whose per-row
    scales dequantize in VMEM. With ``mask_seen`` the masked rows come
    as ``seen_bits`` — ``[B, >= ceil(M / 32)]`` int32 words of the
    packed bitmap (:func:`pack_seen_bits`; what ``DeviceTopK`` keeps
    per user, so a dispatch moves ``M / 8`` bytes per query however
    long the user's history; a similarity query packs its own query
    items with :func:`pack_seen_ids`). Rows may be WIDER than
    ``ceil(M / 32)``: the store's are a whole number of 128-word lane
    tiles (``ops.serving.seen_row_words``), the words past the last
    item tile are cut off here and never read as items.
    ``row_valid`` is an optional ``[M]`` per-row validity vector (>0 =
    real item) for stores whose real rows are not a contiguous prefix
    — the density-sharded per-shard lane.

    Returns ``(vals [B, k] f32, idx [B, k] i32, rounds)``: rows
    descending, -inf past the valid candidates — the same contract as
    the XLA ``top_k`` chain, tie-broken identically (lowest item id
    first) — and the int32 count of selection rounds the pass ran
    (:func:`_topk_select_body`), which the serving programs pack beside
    the winners (``ops.serving._pack``) for the dispatch record's
    ``selectRounds``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from predictionio_tpu.ops.quantize import is_quantized

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quant = is_quantized(Y)
    Yd = Y.data if quant else Y
    M, R = Yd.shape
    B = Q.shape[0]
    K = int(k)
    TM = int(tile_m) if tile_m else TOPK_TILE_M
    if TM % SEEN_WORD_BITS:
        raise ValueError(f"tile_m={TM} must be a multiple of "
                         f"{SEEN_WORD_BITS} (the seen-bitmap word)")
    padM = (-M) % TM
    if padM:  # DeviceTopK pre-pads its store; direct callers pay once
        Yd = jnp.pad(Yd, ((0, padM), (0, 0)))
    n_tiles = (M + padM) // TM
    padB = (-B) % _TOPK_B_ALIGN
    Bp = B + padB
    if padB:
        Q = jnp.pad(Q, ((0, padB), (0, 0)))
    Qf = Q.astype(jnp.float32)

    in_specs = [
        pl.BlockSpec((Bp, R), lambda t: (0, 0)),          # Q (resident)
        pl.BlockSpec((TM, R), lambda t: (t, 0)),          # Y tile stream
    ]
    args = [Qf, Yd]
    if quant:
        ys = Y.scale.astype(jnp.float32)[:, None]
        if padM:
            ys = jnp.pad(ys, ((0, padM), (0, 0)),
                         constant_values=1.0)
        in_specs.append(pl.BlockSpec((TM, 1), lambda t: (t, 0)))
        args.append(ys)
    has_valid = row_valid is not None
    if has_valid:
        rv = jnp.asarray(row_valid, dtype=jnp.float32)[:, None]
        if padM:
            rv = jnp.pad(rv, ((0, padM), (0, 0)))  # pad rows invalid
        in_specs.append(pl.BlockSpec((TM, 1), lambda t: (t, 0)))
        args.append(rv)
    if mask_seen:
        wt = TM // SEEN_WORD_BITS
        sb = jnp.asarray(seen_bits, dtype=jnp.int32)
        sb = sb[:, :n_tiles * wt]
        sb = jnp.pad(sb, ((0, padB), (0, n_tiles * wt - sb.shape[1])))
        # [n_tiles, TM // 32, Bp]: batch on lanes like the scores, one
        # leading-dim block per grid step
        in_specs.append(pl.BlockSpec((1, wt, Bp), lambda t: (t, 0, 0)))
        args.append(sb.T.reshape(n_tiles, wt, Bp))

    def kernel(*refs):
        qr = refs[0]
        ydr = refs[1]
        pos = 2
        ysr = None
        if quant:
            ysr = refs[pos]
            pos += 1
        rvr = None
        if has_valid:
            rvr = refs[pos]
            pos += 1
        sbr = None
        if mask_seen:
            sbr = refs[pos]
            pos += 1
        vals_ref, idx_ref, rounds_ref, run_v, run_i, work = refs[pos:]
        _fused_topk_body(qr, ydr, ysr, rvr, sbr, vals_ref, idx_ref,
                         rounds_ref, run_v, run_i, work, K=K,
                         n_items=n_items, n_tiles=n_tiles)

    vals, idx, rounds = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((K, Bp), lambda t: (0, 0)),
            pl.BlockSpec((K, Bp), lambda t: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # rounds, summed
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, Bp), jnp.float32),
            jax.ShapeDtypeStruct((K, Bp), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((K, Bp), jnp.float32),        # running top-k
            pltpu.VMEM((K, Bp), jnp.int32),
            pltpu.VMEM((TM, Bp), jnp.float32),       # the tile, picked over
        ],
        interpret=bool(interpret),
        name="fused_topk",
    )(*args)
    return vals.T[:B], idx.T[:B], rounds[0, 0]

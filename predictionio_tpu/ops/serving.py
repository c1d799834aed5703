"""Device-resident top-N serving (SURVEY hard parts #4 and #5).

The reference serves from in-memory JVM objects (`CreateServer.scala:
533-540` calls `predictBase` on a host model; the ALS template's RDD
variant even runs Spark jobs per query, `examples/.../ALSAlgorithm.scala:
77-103`). The TPU-native answer keeps the factor matrices in HBM —
replicated on one chip or sharded over the mesh — and serves each query
with an AOT-compiled gather→matmul→top_k program:

- scores = Y @ X[uid] runs on the MXU; top_k stays on device; only the
  k winners travel back over PCIe.
- already-rated items are masked on device from a packed bitmap (one
  bit per user and store position, :func:`seen_bitmap`), its rows a
  whole number of 128-word lane tiles wide (:func:`seen_row_words`) so
  that the device holds it row-major and a program gathers a batch's
  rows without first copying all of it.
- programs are compiled per top-k BUCKET (next power of two) so any
  (num, blacklist) request reuses a handful of compiled programs; the
  deploy path warms the common buckets so the first query pays no
  compile (hard part #4).
- with Y sharded over a mesh axis the same program serves a sharded
  model: XLA partitions the matmul and merges per-shard top-k — no host
  gather of the factors ever happens (hard part #5, PAlgorithm
  semantics).

Transport discipline (the reference serves from in-JVM memory with zero
device hops, `CreateServer.scala:533-540` — so every host↔device round
trip here is pure regression and is treated as such):

- each program packs (bitcast(scores), indices) into ONE flat int32
  output, so a query pays exactly one blocking device→host fetch; the
  uid travels inside the jit dispatch (no separate transfer op).
- `users_topk` vmaps the same program over a padded uid bucket: B
  concurrent queries cost the SAME single round trip (the reference's
  batch path is likewise one cluster job over the whole query set,
  `P2LAlgorithm.scala:66-68`).
"""

from __future__ import annotations

import bisect
import collections
import itertools
import threading
import time
import weakref
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FuturesTimeout
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.ops.aot import AOTCache, lower_compile
from predictionio_tpu.utils import device_telemetry as _dtel
from predictionio_tpu.utils import metrics as _metrics
from predictionio_tpu.utils import tracing as _tracing
from predictionio_tpu.utils.tracing import span as _trace_span


# the serving whitelist extends the training one with int8: a
# storage-only mode (per-row-scaled int8 factor tables, fp32 score
# accumulation) that has no training-accumulate meaning
SERVE_PRECISION_MODES = ("fp32", "bf16", "int8")


def _serve_precision_explicit() -> Optional[str]:
    """The operator's explicit ``PIO_SERVE_PRECISION`` choice, or None
    when unset. Unknown values raise (one shared canonicalizer with the
    training-side ``PIO_ALS_PRECISION`` policy; serving additionally
    accepts ``int8``)."""
    import os

    mode = os.environ.get("PIO_SERVE_PRECISION", "").strip().lower()
    if not mode:
        return None
    from predictionio_tpu.ops.als import normalize_precision

    return normalize_precision(mode, "PIO_SERVE_PRECISION",
                               allowed=SERVE_PRECISION_MODES)


def _default_serve_precision() -> str:
    """The DEVICE factor store defaults to bfloat16 on accelerators
    (the ALX storage/compute split as the serving default: half the HBM
    the model pins AND half the bytes every scoring matmul streams,
    with scores still accumulated fp32 — quality-gated by the PR-5
    Precision@10 check). CPU keeps fp32: there is no native bf16
    datapath there, so the cast costs latency and buys nothing."""
    import jax

    return "bf16" if jax.default_backend() != "cpu" else "fp32"


def _serve_precision_mode() -> str:
    """Serving factor-store precision as resolved at server
    construction: the explicit ``PIO_SERVE_PRECISION`` (``fp32`` is the
    opt-out, ``bf16`` forces the device backend), else the
    backend-aware default (bf16 on accelerators, fp32 on CPU). The
    host serving lane is unaffected either way — HostTopK always
    scores fp32."""
    explicit = _serve_precision_explicit()
    return explicit if explicit is not None else _default_serve_precision()


def _is_bf16(arr) -> bool:
    """dtype check that works for jax Arrays AND ml_dtypes-backed numpy."""
    return getattr(getattr(arr, "dtype", None), "name", "") == "bfloat16"


def _serve_kernel_mode() -> str:
    """Which program family serves device top-k: the fused Pallas
    kernel (``ops/als_pallas.py::fused_gather_score_topk`` — gather,
    score matvec, seen-mask, and top-k selection in ONE program that
    streams each item-factor tile HBM->VMEM exactly once) or the
    historical XLA gather/einsum/mask/top-k chain.

    ``PIO_SERVE_KERNEL``: ``fused`` forces the kernel (interpret mode
    off-TPU — the tests' lane), ``xla`` opts out, unset/``auto`` picks
    fused on TPU and XLA elsewhere (CPU has no Mosaic; interpret mode
    is a correctness tool, not a fast path). Unknown values raise."""
    import os

    import jax

    val = os.environ.get("PIO_SERVE_KERNEL", "").strip().lower()
    if val in ("", "auto"):
        return "fused" if jax.default_backend() == "tpu" else "xla"
    if val in ("fused", "pallas"):
        return "fused"
    if val == "xla":
        return "xla"
    raise ValueError(
        f"PIO_SERVE_KERNEL={val!r} is not a known serving kernel "
        "(expected one of: auto, fused, xla)")


def _serve_shards_env() -> int:
    """``PIO_SERVE_SHARDS`` — shard the DEVICE factor store over this
    many devices (density-aware item placement when the model carries
    interaction counts; see ``parallel.als_sharding``). 0/unset keeps
    the single-store layout; like the bf16/int8 policies it is an HBM
    policy, so any value > 1 forces the device backend in auto mode and
    conflicts loudly with an explicit host backend."""
    import os

    raw = os.environ.get("PIO_SERVE_SHARDS", "").strip()
    if not raw:
        return 0
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"PIO_SERVE_SHARDS={raw!r} is not an integer shard count")
    return max(0, n)


def foldin_enabled() -> bool:
    """``PIO_FOLDIN`` — set by ``pio deploy --foldin on`` (and readable
    directly by embedders): the deployed server runs the online fold-in
    consumer, which needs an UPDATABLE device factor store. Like the
    bf16 rule, it forces the device backend in auto mode and conflicts
    loudly with an explicit host backend."""
    import os

    return os.environ.get("PIO_FOLDIN", "").strip().lower() in (
        "1", "on", "true", "yes")


def _score_einsum(subscripts: str, *operands, mode: str):
    """Scoring matmul under the serving precision policy. ``mode`` is
    the STORE'S declared precision, threaded explicitly from the server
    that owns the factors — never sniffed from operand dtypes (a mixed
    fp32/bf16 operand pair used to silently steer the accumulate path;
    the regression test in tests/test_serving_device.py pins the fix):

    - ``fp32``: the historical full-precision MXU passes
      (``Precision.HIGHEST``);
    - ``bf16``: operands feed the MXU natively with an fp32 accumulator
      (``preferred_element_type``);
    - ``int8``: :class:`~predictionio_tpu.ops.quantize.QuantFactors`
      operands dequantize (``data * per-row scale``) INTO the fp32
      accumulate — XLA fuses the dequant into the dot's operand read,
      so HBM still streams int8 bytes.

    Either way the result is float32 (``_pack`` and the -inf masking
    depend on it)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.quantize import dequantize_rows, is_quantized

    if mode == "int8":
        ops = [dequantize_rows(op) if is_quantized(op) else
               jnp.asarray(op).astype(jnp.float32) for op in operands]
        # HIGHEST: the dequantized operands are fp32 and must stay on
        # full-precision MXU passes (TPU would otherwise bf16-truncate
        # them, stacking truncation on top of the quantization error —
        # and diverging from the fused kernel's HIGHEST dot)
        return jnp.einsum(subscripts, *ops,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if mode == "bf16":
        return jnp.einsum(subscripts, *operands,
                          preferred_element_type=jnp.float32)
    if mode == "fp32":
        return jnp.einsum(subscripts, *operands,
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"_score_einsum: unknown serving precision mode "
                     f"{mode!r} (expected one of: "
                     f"{', '.join(SERVE_PRECISION_MODES)})")


# one lane tile of int32: the minor dimension a TPU array is tiled by
# (T(8,128)); see seen_row_words
_SEEN_LANE_WORDS = 128


def seen_row_words(n_pos: int) -> int:
    """int32 words in one row of the packed seen bitmap over ``n_pos``
    store positions: ``ceil(n_pos / 32)`` rounded up to a multiple of
    128, one lane tile. The width's one owner — everything that builds
    bitmap rows (:func:`seen_bitmap`, and through it the store, growth
    and fold-in's replacement rows) takes it from here.

    Why the rounding: the TPU gives an entry parameter the COMPACT
    tiled layout of its shape. A row of 1288 words would be padded to
    1408 under ``T(8,128)``, so the compiler made the long user
    dimension minor instead (``{0,1}``), and every program that gathers
    rows then re-laid the WHOLE bitmap out row-major first: 8.0-8.5 ms
    and a bitmap-sized temporary per dispatch at 571,355 x 41,140,
    four fifths of the device time of a query (PERF.md, PR 22-24). A
    width that is already a whole number of tiles wastes nothing
    row-major, the parameter arrives ``{1,0}``, and the row gather
    reads it in place."""
    words = max(1, -(-int(n_pos) // 32))
    return -(-words // _SEEN_LANE_WORDS) * _SEEN_LANE_WORDS


def seen_bitmap(seen: Dict[int, np.ndarray], n_rows: int,
                n_pos: int) -> np.ndarray:
    """Pack a ``{user_idx: item position array}`` dict into the
    ``[n_rows, seen_row_words(n_pos)]`` int32 bitmap the device masks
    from: bit ``j`` of word ``w`` in row ``u`` = user ``u`` has seen
    position ``32 * w + j`` (positions outside ``[0, n_pos)`` carry no
    bit, so the words past ``ceil(n_pos / 32)`` are zero; every reader
    stops at ``n_pos``).

    A row costs ``n_pos / 8`` bytes rounded up to 512 (one lane tile of
    words, :func:`seen_row_words`) whatever the user's history. The
    padded id-list layout this replaces cost ``8 * longest_history``
    bytes for EVERY user: at the ML-20M shape (138k users, 27k items,
    longest history 18k) that is 20 GB against 0.49 GB here, and the
    fused kernel looped over the list per item tile. The rounding is
    resident memory paid for a bitmap no program has to copy: 2.94 ->
    3.22 GB at 571,355 x 41,140 (+9.3%), 0.47 -> 0.49 GB at ML-20M
    (+6%), +0.35% at 2M x 200k, at most 508 bytes a user for a tiny
    catalog."""
    W = seen_row_words(n_pos)
    bits = np.zeros((int(n_rows), W), dtype=np.uint32)
    if seen:
        users = np.fromiter(seen.keys(), dtype=np.int64, count=len(seen))
        lens = np.fromiter((len(v) for v in seen.values()),
                           dtype=np.int64, count=len(seen))
        if lens.sum():
            rows = np.repeat(users, lens)
            pos = np.concatenate(
                [np.asarray(v, dtype=np.int64).ravel()
                 for v in seen.values()])
            ok = (pos >= 0) & (pos < n_pos) & (rows < n_rows)
            rows, pos = rows[ok], pos[ok]
            np.bitwise_or.at(
                bits.reshape(-1), rows * W + (pos >> 5),
                np.left_shift(np.uint32(1), (pos & 31).astype(np.uint32)))
    return bits.view(np.int32)


def _mask_padding(scores, n_items: int):
    """Padded factor rows (index >= n_items) never reach the top-k: mask
    on DEVICE so the program always returns k real candidates."""
    import jax.numpy as jnp

    if n_items < scores.shape[0]:
        valid = jnp.arange(scores.shape[0]) < n_items
        scores = jnp.where(valid, scores, -jnp.inf)
    return scores


def _pack(scores, idx, rounds=None):
    """Fuse (scores [.., k] f32, idx [.., k] i32) into ONE [.., 2k]
    int32 buffer (scores bitcast, not value-cast — exact) so the host
    pays a single device→host fetch per dispatch. ``rounds``, the fused
    kernel's count of selection rounds for the dispatch (an int32
    scalar; the XLA chains have none), rides as one more column, the
    same number in every row: :func:`_packed_rounds` reads it back.

    The buffer is INTEGER on purpose. Reinterpreted as float32, an
    index below 2^23 is a denormal, and the TPU flushes denormals to
    zero wherever XLA routes the copy through a float data path: on
    the v5e a float32-packed buffer came back with the right scores
    and every index 0 (PR 21's first chip run; CPU and interpret mode
    never flush, so no test saw it). Integer lanes carry any bit
    pattern unchanged, float32 scores included."""
    import jax
    import jax.numpy as jnp

    parts = [jax.lax.bitcast_convert_type(scores, jnp.int32),
             idx.astype(jnp.int32)]
    if rounds is not None:
        parts.append(jnp.broadcast_to(rounds.astype(jnp.int32),
                                      scores.shape[:-1] + (1,)))
    return jnp.concatenate(parts, axis=-1)


def _unpack(out: np.ndarray, kb: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side inverse of `_pack` on the fetched numpy buffer."""
    return out[..., kb:2 * kb], out[..., :kb].view(np.float32)


def _packed_rounds(out: np.ndarray, kb: int) -> Optional[int]:
    """The selection rounds a fused program packed behind its ``2 kb``
    result columns; None for a program that packs none."""
    if out.shape[-1] <= 2 * kb:
        return None
    return int(out[..., 2 * kb].flat[0])


def _take_user_row_f32(X, uid, *, mode: str):
    """One user's factor row as fp32, whatever the store holds: int8
    rows dequantize with their own scale at gather time (a [R] row —
    the int8 bandwidth policy is about the ITEM table stream, not this
    single row)."""
    import jax

    from predictionio_tpu.ops.quantize import is_quantized

    if mode == "int8" and is_quantized(X):
        d = jax.lax.dynamic_index_in_dim(X.data, uid, 0, keepdims=False)
        s = jax.lax.dynamic_index_in_dim(X.scale, uid, 0, keepdims=False)
        return d.astype("float32") * s
    return jax.lax.dynamic_index_in_dim(X, uid, axis=0, keepdims=False)


def _gather_rows_f32(factors, idx, *, mode: str):
    """Factor rows gathered by index (any index shape) as fp32 — the
    ONE take-and-dequantize used by every fused-program gather; int8
    rows dequantize with their own per-row scales."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.quantize import is_quantized

    if mode == "int8" and is_quantized(factors):
        return jnp.take(factors.data, idx, axis=0).astype(jnp.float32) \
            * jnp.take(factors.scale, idx, axis=0)[..., None]
    return jnp.take(factors, idx, axis=0).astype(jnp.float32)


def _pad_item_rows_for_kernel(Y):
    """Item table padded (zeros, scale 1) to the fused kernel's tile
    multiple — one-time at store construction, so dispatches never pay
    a per-call copy. Pad rows live past ``n_items`` and are -inf-masked
    on device exactly like sharded-training padding."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import als_pallas
    from predictionio_tpu.ops.quantize import QuantFactors, is_quantized

    m = int(Y.shape[0])
    pad = (-m) % als_pallas.TOPK_TILE_M
    if not pad:
        return Y
    if is_quantized(Y):
        return QuantFactors(
            jnp.concatenate(
                [Y.data, jnp.zeros((pad, Y.data.shape[1]), Y.data.dtype)]),
            jnp.concatenate([Y.scale, jnp.ones((pad,), Y.scale.dtype)]))
    return jnp.concatenate([Y, jnp.zeros((pad, Y.shape[1]), Y.dtype)])


# ---------------------------------------------------------------------------
# Sharded serving (ISSUE 15): per-shard top-k + on-device log-tree merge
# ---------------------------------------------------------------------------


def _dim0_shard_ctx(arr) -> Optional[Tuple[Any, str]]:
    """(mesh, axis) when ``arr``'s leading dim is sharded over exactly
    one mesh axis of size > 1 — the serve-shard context a pre-sharded
    PAlgorithm store carries in its own placement; None otherwise."""
    from jax.sharding import NamedSharding

    sh = getattr(arr, "sharding", None)
    if not isinstance(sh, NamedSharding) or sh.mesh.devices.size <= 1:
        return None
    spec = sh.spec
    dim0 = spec[0] if len(spec) else None
    names = (dim0,) if isinstance(dim0, str) else tuple(dim0 or ())
    if len(names) != 1:
        return None
    axis = names[0]
    if int(sh.mesh.shape[axis]) <= 1:
        return None
    return sh.mesh, axis


def _tree_merge_topk(vals, idx, k: int, axis: str, n_sh: int):
    """Merge per-shard top-k candidate lists into the GLOBAL top-k on
    device — the PR-6 ``pio_merge_runs`` k-way-merge idiom re-expressed
    on HBM. Power-of-two shard counts run a butterfly of ``ppermute``
    exchanges (log2(n) rounds, each merging two sorted k-lists via one
    ``top_k`` over 2k candidates; the lower shard's candidates lead the
    union so score ties resolve identically on every device); other
    counts take one ``all_gather`` + top_k over n*k candidates. Either
    way the merged (vals, idx) land replicated on every shard and only
    the k winners ever travel to host."""
    import jax.numpy as jnp
    from jax import lax

    if n_sh & (n_sh - 1) == 0:
        me = lax.axis_index(axis)
        step = 1
        while step < n_sh:
            perm = [(i, i ^ step) for i in range(n_sh)]
            ov = lax.ppermute(vals, axis, perm)
            oi = lax.ppermute(idx, axis, perm)
            mine_first = (me & step) == 0
            cv = jnp.where(mine_first,
                           jnp.concatenate([vals, ov], axis=-1),
                           jnp.concatenate([ov, vals], axis=-1))
            ci = jnp.where(mine_first,
                           jnp.concatenate([idx, oi], axis=-1),
                           jnp.concatenate([oi, idx], axis=-1))
            vals, sel = lax.top_k(cv, k)
            idx = jnp.take_along_axis(ci, sel, axis=-1)
            step *= 2
        return vals, idx
    av = lax.all_gather(vals, axis, axis=0)            # [n_sh, B, k]
    ai = lax.all_gather(idx, axis, axis=0)
    av = jnp.moveaxis(av, 0, -2).reshape(
        vals.shape[:-1] + (n_sh * k,))
    ai = jnp.moveaxis(ai, 0, -2).reshape(
        idx.shape[:-1] + (n_sh * k,))
    v, sel = lax.top_k(av, k)
    return v, jnp.take_along_axis(ai, sel, axis=-1)


def _sharded_score_topk(Y, valid, Q, seen_bits, *, k: int,
                        mask_seen: bool, mode: str, mesh, axis: str,
                        fused: bool, interpret: bool):
    """Score + mask + top-k over a mesh-sharded item store, explicitly:
    ``shard_map`` gives each shard its ``[m_local, R]`` factor block,
    the shard scores it against the replicated queries (XLA chain, or
    the fused Pallas kernel running per-shard on its local tiles),
    masks invalid positions (``valid`` — the density layout's real-item
    mask) and its own slice of the seen bitmap, takes its local
    ``lax.top_k``, and the per-shard runs merge on device
    (:func:`_tree_merge_topk`).

    ``Q [B, R]`` fp32 replicated queries; ``seen_bits`` ``[B, W]`` the
    queries' packed seen bitmap over store POSITIONS (replicated;
    ignored without ``mask_seen``). Returns ``(vals [B, k] f32,
    positions [B, k] i32, rounds)`` replicated; ``rounds`` is the sum
    of the shards' kernels' selection rounds (None off the fused
    kernel), for :func:`_pack`."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.ops.als_pallas import (
        fused_gather_score_topk,
        pack_seen_bits,
        unpack_seen_bits,
    )
    from predictionio_tpu.ops.quantize import QuantFactors, is_quantized

    n_sh = int(mesh.shape[axis])
    quant = is_quantized(Y)

    def body(Yd, Ys, vl, Qb, sbq):
        m = int(Yd.shape[0])
        off = lax.axis_index(axis) * m
        hit = None
        if mask_seen:
            # this shard's [B, m] slice of the seen mask (a shard's
            # position range need not start on a word boundary, so
            # slice the unpacked mask, not the words)
            hit = lax.dynamic_slice_in_dim(
                unpack_seen_bits(sbq, n_sh * m), off, m, axis=1)
        kl = min(k, m)
        if fused:
            Yl = QuantFactors(Yd, Ys) if quant else Yd
            vals, li, rounds = fused_gather_score_topk(
                Qb, Yl, k=kl, n_items=m, mask_seen=mask_seen,
                seen_bits=pack_seen_bits(hit) if mask_seen else None,
                row_valid=vl, interpret=interpret)
        else:
            if quant:
                # dequant into the fp32 accumulate locally (the int8
                # HBM stream stays int8 per shard, like the fused tile)
                Yf = Yd.astype(jnp.float32) * Ys[:, None]
                scores = jnp.einsum(
                    "mr,br->bm", Yf, Qb,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
            else:
                scores = _score_einsum("mr,br->bm", Yd, Qb, mode=mode)
            scores = jnp.where(vl[None, :] > 0, scores, -jnp.inf)
            if mask_seen:
                scores = jnp.where(hit, -jnp.inf, scores)
            vals, li = lax.top_k(scores, kl)
        if kl < k:                      # tiny shard: pad candidates
            vals = jnp.pad(vals, ((0, 0), (0, k - kl)),
                           constant_values=-jnp.inf)
            li = jnp.pad(li, ((0, 0), (0, k - kl)))
        merged = _tree_merge_topk(vals, li + off, k, axis, n_sh)
        # the shards' kernels each count their own rounds: their sum
        return merged + (lax.psum(rounds, axis),) if fused else merged

    row, col, repl = P(axis, None), P(axis), P(None, None)
    outs = (repl, repl, P()) if fused else (repl, repl)
    if quant:
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(row, col, col, repl, repl),
                           out_specs=outs, check_vma=False)
        out = fn(Y.data, Y.scale, valid, Q, seen_bits)
    else:
        fn = jax.shard_map(
            lambda Yd, vl, Qb, sbq: body(Yd, None, vl, Qb, sbq),
            mesh=mesh, in_specs=(row, col, repl, repl),
            out_specs=outs, check_vma=False)
        out = fn(Y, valid, Q, seen_bits)
    return out if fused else out + (None,)


def _user_topk(X, Y, seen_bits, uid, *, k: int, mask_seen: bool,
               n_items: int, mode: str = "fp32"):
    """scores = Y @ X[uid], seen + padding masked to -inf, device top_k,
    packed into one flat output buffer. ``seen_bits`` is the store's
    packed seen bitmap (:func:`seen_bitmap`); ``mode`` is the store's
    declared precision, static per compiled program."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.als_pallas import unpack_seen_bits

    with jax.named_scope("gather_q"):
        u = _take_user_row_f32(X, uid, mode=mode)
    with jax.named_scope("topk"):
        scores = _score_einsum("mr,r->m", Y, u, mode=mode)
    if mask_seen:
        with jax.named_scope("seen_rows"):
            row = jax.lax.dynamic_index_in_dim(seen_bits, uid, 0,
                                               keepdims=False)
    with jax.named_scope("topk"):
        if mask_seen:
            scores = jnp.where(unpack_seen_bits(row, scores.shape[0]),
                               -jnp.inf, scores)
        vals, idx = jax.lax.top_k(_mask_padding(scores, n_items), k)
    with jax.named_scope("pack"):
        return _pack(vals, idx)


def _gather_query_rows_f32(Yn, idx, idx_mask, *, mode: str):
    """The masked query-item rows for a similarity query, in the dtype
    the scoring einsum wants: bf16 stays bf16 (an fp32 mask would
    silently promote it off the native-bf16 MXU path), int8 rows
    dequantize to fp32 (a [B, R] gather — tiny next to the item
    stream)."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.quantize import is_quantized

    if mode == "int8" and is_quantized(Yn):
        qf = jnp.take(Yn.data, idx, axis=0).astype(jnp.float32) \
            * jnp.take(Yn.scale, idx, axis=0)[:, None]
        return qf * idx_mask[:, None]
    return jnp.take(Yn, idx, axis=0) * idx_mask[:, None].astype(Yn.dtype)


def _items_topk(Yn, idx, idx_mask, *, k: int, n_items: int,
                mode: str = "fp32"):
    """Summed-cosine item-similarity scores against a padded query-item
    bucket, device top_k (cosine semantics of ALSAlgorithm.scala:121-135).
    ``Yn`` is the row-normalized item matrix (precomputed once)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("gather_q"):
        qm = _gather_query_rows_f32(Yn, idx, idx_mask, mode=mode)
    with jax.named_scope("topk"):
        scores = _score_einsum("mr,br->m", Yn, qm, mode=mode)
        # the query items themselves never recommend (mask to -inf)
        scores = scores.at[idx].add(
            jnp.where(idx_mask > 0, -jnp.inf, 0.0), mode="drop")
        vals, top = jax.lax.top_k(_mask_padding(scores, n_items), k)
    with jax.named_scope("pack"):
        return _pack(vals, top)


def _normalize_rows(Y):
    """Row-normalize, computing the norms in fp32 regardless of the
    factor storage dtype (a bf16 norm would square bf16 values); the
    result keeps Y's dtype so bf16 stores stay half-width in HBM. A
    quantized store re-quantizes the normalized rows — unit-norm rows
    have per-row absmax <= 1, so the recomputed scales keep full int8
    resolution."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.quantize import (
        dequantize_rows,
        is_quantized,
        quantize_rows_int8,
    )

    if is_quantized(Y):
        @jax.jit
        def norm_q(Yq):
            Yf = dequantize_rows(Yq)
            Yn = Yf / jnp.maximum(
                jnp.linalg.norm(Yf, axis=1, keepdims=True), 1e-12)
            return quantize_rows_int8(Yn)

        return norm_q(Y)

    @jax.jit
    def norm(Y):
        Yf = Y.astype(jnp.float32)
        return (Yf / jnp.maximum(
            jnp.linalg.norm(Yf, axis=1, keepdims=True),
            1e-12)).astype(Y.dtype)

    return norm(Y)


def bucket_size(n: int, lo: int = 16) -> int:
    """The power-of-two bucket ``n`` rounds up to (min ``lo``). Public:
    the batch-prediction chunker aligns its chunk sizes to the same
    buckets `users_topk` dispatches at, so every chunk after the first
    reuses a compiled program (jit caches stay warm across a whole
    10M-query job)."""
    b = lo
    while b < n:
        b *= 2
    return b


_bucket = bucket_size


class HostTopK:
    """Host-memory top-N server with the same interface as
    :class:`DeviceTopK` — numpy scoring + argpartition, zero device round
    trips. This is the reference's own serving shape (in-JVM predict from
    host objects, `CreateServer.scala:533-540`): for models that fit in
    host RAM the per-query matvec is microseconds, which beats any
    host↔device transport. The deploy path picks it automatically for
    small host-resident factors (see `choose_server`); device-resident /
    sharded models always serve via DeviceTopK."""

    def __init__(self, user_factors: np.ndarray, item_factors: np.ndarray,
                 seen: Optional[Dict[int, np.ndarray]] = None,
                 n_users: Optional[int] = None,
                 n_items: Optional[int] = None):
        from predictionio_tpu.ops.quantize import (
            dequantize_rows_np,
            is_quantized,
        )

        # an int8+scales store (a quantized model artifact, or a
        # device store gathered to host) serves on host in fp32 — numpy
        # has no int8 BLAS, and at host-servable sizes the memory
        # quartering buys nothing (mirror of the bf16 rule below)
        if is_quantized(user_factors):
            user_factors = dequantize_rows_np(user_factors)
        if is_quantized(item_factors):
            item_factors = dequantize_rows_np(item_factors)
        self._X = np.asarray(user_factors)
        self._Y = np.asarray(item_factors)
        if _is_bf16(self._X):
            # bf16 models (ALX-style training under PIO_ALS_PRECISION=
            # bf16, device-resident flavors gathered to host) serve on
            # host in fp32: numpy has no native bf16 BLAS, and at host-
            # servable sizes the memory halving buys nothing
            self._X = self._X.astype(np.float32)
        if _is_bf16(self._Y):
            self._Y = self._Y.astype(np.float32)
        self.n_users = int(n_users if n_users is not None
                           else self._X.shape[0])
        self.n_items = int(n_items if n_items is not None
                           else self._Y.shape[0])
        self._seen = seen or {}
        self._Yn: Optional[np.ndarray] = None

    def warmup(self, max_k: int = 128, batch_sizes: Tuple[int, ...] = ()) \
            -> None:
        """Nothing to compile host-side."""

    def close(self) -> None:
        """Interface parity with DeviceTopK; nothing to release."""

    def stats(self) -> Dict[str, Dict[str, int]]:
        """Interface parity with DeviceTopK; no batchers host-side."""
        return {}

    def _topk_row(self, scores: np.ndarray, k: int):
        k = min(k, scores.shape[0])
        top = np.argpartition(-scores, k - 1)[:k]
        top = top[np.argsort(-scores[top], kind="stable")]
        s = scores[top]
        valid = np.isfinite(s)
        return top[valid].astype(np.int32), s[valid]

    def _user_scores(self, uid: int) -> np.ndarray:
        scores = self._Y[:self.n_items] @ self._X[uid]
        s = self._seen.get(uid)
        if s is not None and len(s):
            scores[s[s < self.n_items]] = -np.inf
        return scores

    def user_topk(self, uid: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        return self._topk_row(self._user_scores(uid), k)

    def users_topk(self, uids, k: int) -> Tuple[np.ndarray, np.ndarray]:
        uids = np.asarray(uids, dtype=np.int64)
        k = min(k, self.n_items)
        idx = np.zeros((len(uids), k), dtype=np.int32)
        scores = np.full((len(uids), k), -np.inf, dtype=np.float32)
        for row, uid in enumerate(uids):
            i, s = self._topk_row(self._user_scores(int(uid)), k)
            idx[row, :len(i)] = i
            scores[row, :len(s)] = s
        return idx, scores

    def items_topk(self, idxs, k: int) -> Tuple[np.ndarray, np.ndarray]:
        if self._Yn is None:
            Y = self._Y[:self.n_items].astype(np.float32)
            norms = np.maximum(np.linalg.norm(Y, axis=1, keepdims=True),
                               1e-12)
            self._Yn = Y / norms
        idxs = np.asarray(idxs, dtype=np.int64)
        scores = self._Yn @ self._Yn[idxs].sum(axis=0)
        scores[idxs] = -np.inf
        return self._topk_row(scores, k)


# Above this many item-factor elements the score matrix stops being a
# host-trivial matvec and the MXU path wins even with transport.
HOST_SERVE_MAX_ELEMS = 1 << 22


# The serving-policy matrix (ISSUE 20 satellite): every feature that
# forces the device backend — and therefore conflicts with an explicit
# host backend — as TABLE ROWS instead of ad-hoc if-raises scattered
# through choose_server. Each row is (name, predicate over the policy
# flags, the message an explicit ``PIO_SERVING_BACKEND=host`` raises
# when the row is active). Row order is the historical raise order.
# New serving lanes (the two-stage store, the next one) land as rows.
_SERVING_POLICY_ROWS: Tuple[Tuple[str, Callable[[Dict[str, Any]], bool],
                                  str], ...] = (
    ("resident",
     lambda f: not f["host_capable"],
     "PIO_SERVING_BACKEND=host but the factors are device-resident "
     "jax Arrays"),
    ("precision",
     lambda f: f["explicit_precision"] in ("bf16", "int8"),
     "PIO_SERVE_PRECISION={explicit_precision} conflicts with "
     "PIO_SERVING_BACKEND=host: the quantized/bf16 store is a device "
     "(HBM) policy; host serving is always fp32"),
    ("foldin",
     lambda f: f["foldin"],
     "PIO_FOLDIN=on conflicts with PIO_SERVING_BACKEND=host: "
     "online fold-in patches the DEVICE factor store in place "
     "(DeviceTopK.patch_users); host serving has no updatable "
     "store"),
    ("sharded",
     lambda f: f["sharded"],
     "PIO_SERVE_SHARDS conflicts with PIO_SERVING_BACKEND="
     "host: sharding the factor store over a mesh is a "
     "device (HBM) policy; host serving has one store"),
    ("two_stage",
     lambda f: f["two_stage"],
     "two-stage serving conflicts with PIO_SERVING_BACKEND=host: the "
     "fused retrieval + re-rank top-k runs as ONE device program "
     "(TwoStageTopK); host serving has no fused candidate lane"),
)


def validate_serving_policy(backend: str, *, host_capable: bool = True,
                            explicit_precision: Optional[str] = None,
                            foldin: bool = False, sharded: bool = False,
                            two_stage: bool = False) -> str:
    """Rule on one backend/feature combination against the serving
    policy matrix (:data:`_SERVING_POLICY_ROWS`).

    Returns the backend decision: ``"host"`` (explicitly requested and
    nothing forbids it), ``"device"`` (explicitly requested, or some
    active row forces it), or ``"auto"`` (nothing decided — the caller
    applies its size heuristic). An explicit ``host`` backend raises
    loudly on the FIRST active row, with the row's message. Unknown
    backend strings fall through to ``auto`` — the historical
    choose_server behavior."""
    flags = {"host_capable": bool(host_capable),
             "explicit_precision": explicit_precision,
             "foldin": bool(foldin), "sharded": bool(sharded),
             "two_stage": bool(two_stage)}
    active = [row for row in _SERVING_POLICY_ROWS if row[1](flags)]
    if backend == "host":
        if active:
            raise ValueError(active[0][2].format(**flags))
        return "host"
    if backend == "device" or active:
        return "device"
    return "auto"


def choose_server(user_factors, item_factors,
                  seen: Optional[Dict[int, np.ndarray]] = None,
                  n_users: Optional[int] = None,
                  n_items: Optional[int] = None):
    """Serving-backend policy for host-persistable models (P2L flavors):

    - ``PIO_SERVING_BACKEND=host``   -> HostTopK always
    - ``PIO_SERVING_BACKEND=device`` -> DeviceTopK always
    - auto (default): HostTopK when the factors are host arrays small
      enough that a numpy matvec beats a device round trip
      (< HOST_SERVE_MAX_ELEMS item-factor elements); DeviceTopK otherwise.

    Device stores default to bfloat16 factors on accelerators (fp32
    score accumulation; ``PIO_SERVE_PRECISION=fp32`` opts out). An
    EXPLICIT ``PIO_SERVE_PRECISION=bf16`` or ``int8`` additionally
    forces the device backend in auto mode — both are HBM policies
    (bf16 halves, int8+per-row-scales quarters the factor stream) and
    mean nothing on host — and conflicts loudly with an explicit
    ``host`` backend. The backend-aware default never steers backend
    selection: small host-resident models still serve via HostTopK
    (always fp32; it ACCEPTS an int8+scales store by dequantizing,
    but never creates one).

    ``PIO_FOLDIN`` (set by ``pio deploy --foldin on``) likewise forces
    the device backend: online fold-in patches the live factor store in
    place (:meth:`DeviceTopK.patch_users`), which HostTopK does not
    support — the host+foldin combination raises loudly (mirror of the
    bf16 rule).

    Device-resident (sharded) models never go through this — their
    factors live only in HBM and always serve via DeviceTopK."""
    import os

    from predictionio_tpu.ops.quantize import is_quantized

    backend = os.environ.get("PIO_SERVING_BACKEND", "auto").lower()
    # only the operator's EXPLICIT bf16/int8 steers backend selection;
    # the accelerator default applies silently once a device store
    # exists
    host_capable = not (hasattr(user_factors, "sharding")
                        or hasattr(item_factors, "sharding"))
    decision = validate_serving_policy(
        backend, host_capable=host_capable,
        explicit_precision=_serve_precision_explicit(),
        foldin=foldin_enabled(), sharded=_serve_shards_env() > 1)
    if decision == "host":
        cls = HostTopK
    elif decision == "device":
        cls = DeviceTopK
    else:
        if host_capable:
            elems = (int(np.prod(item_factors.shape))
                     if is_quantized(item_factors)
                     else np.asarray(item_factors).size)
            small = elems <= HOST_SERVE_MAX_ELEMS
        else:
            small = False
        cls = HostTopK if host_capable and small else DeviceTopK
    return cls(user_factors, item_factors, seen,
               n_users=n_users, n_items=n_items)


class QueryRejectedError(RuntimeError):
    """A query waited in the micro-batcher queue past the configured
    deadline and was rejected instead of queuing indefinitely. The
    query server renders this as HTTP 503 with a ``Retry-After``
    header — under overload, shedding load fast beats building an
    unbounded queue of doomed waiters."""

    def __init__(self, msg: str, retry_after: float = 1.0):
        super().__init__(msg)
        self.retry_after = float(retry_after)


def _queue_deadline() -> Optional[float]:
    """``PIO_QUERY_QUEUE_DEADLINE`` (seconds a query may WAIT in the
    micro-batch queue before a fast 503; <= 0 disables). Default 10s:
    far above any healthy dispatch, far below a client giving up."""
    from predictionio_tpu.utils.resilience import _env_float

    val = _env_float("PIO_QUERY_QUEUE_DEADLINE", 10.0)
    return val if val > 0 else None


def _serve_aot_enabled() -> bool:
    """``PIO_SERVE_AOT`` kill switch (default on): AOT-precompile the
    serving bucket ladder at warm-up. Off, warm-up falls back to
    compiling each ladder program by executing it once — slower warm-up,
    same no-serve-time-compile contract."""
    import os

    return os.environ.get("PIO_SERVE_AOT", "1").strip().lower() \
        not in ("0", "off", "false")


class _BatchResult:
    """One batched dispatch's output, shared by every request in the
    group. Per-request rendering (row slice, clip to the request's own
    k, finite filter) happens in :meth:`render` on the WAITING thread —
    the dispatcher's serial section ends at the device fetch, so a
    hundred-query batch does not serialize a hundred numpy filters
    behind one thread."""

    __slots__ = ("idx", "scores", "telemetry", "lives", "delivered")

    def __init__(self, idx: np.ndarray, scores: np.ndarray,
                 telemetry: Optional[Dict[str, Any]] = None):
        self.idx = idx
        self.scores = scores
        # the flight-recorder record of the device dispatch that
        # produced this result (None with telemetry off): waiting
        # handler threads attach it to their device.* trace span, so a
        # slow query's exemplar names its bucket/fill/kernel/AOT fate
        self.telemetry = telemetry
        # what a batching dispatcher stamped of every delivered query
        # (:meth:`_Pending.life`, a row each) and when it resolved
        # their futures, on the span clock: the waiter's wake-up runs
        # from there (None from a direct caller or with telemetry off)
        self.lives: Optional[List[Dict[str, Any]]] = None
        self.delivered: Optional[float] = None

    def render(self, row: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        ri = self.idx[row, :k]
        rs = self.scores[row, :k]
        valid = np.isfinite(rs)
        return ri[valid], rs[valid]


class _Pending:
    """One queued query: payload (uid, or item-index tuple), its k, its
    batching deadline (the EDF sort key: its arrival, plus the window
    a caller stated for it, if any did) and the
    future the waiting thread blocks on. ``arrival`` (monotonic) feeds
    the flight recorder's queue-wait figure; ``ctx`` carries the
    submitting thread's trace context so the dispatcher thread can
    parent the ``device.execute`` span under a real query trace.

    The query's LIFE is stamped by the dispatcher thread alone, on the
    monotonic clock, with telemetry on: :meth:`claim` when a group
    takes it, :meth:`handed_back` when its lane returns it unfinished,
    :meth:`life` at delivery. ``first_wait`` (arrival to the first
    claim), ``riding`` (claim to the dispatch function's return, summed
    over its ``rounds``; to delivery in the last) and ``between``
    (hand-back to the next claim: other lanes' turns, newcomers'
    opening dispatches, a wait unopened behind an earlier query of its
    user) add up to delivery less arrival. ``claimed`` is None until a
    dispatcher claims it (a direct caller's never is)."""

    __slots__ = ("payload", "k", "deadline", "seq", "future", "arrival",
                 "ctx", "claimed", "rounds", "first_wait", "riding",
                 "between", "back_at")

    def __init__(self, payload, k: int, deadline: float, seq: int,
                 arrival: float, ctx=None):
        self.payload = payload
        self.k = k
        self.deadline = deadline
        self.seq = seq
        self.arrival = arrival
        self.ctx = ctx
        self.future: Future = Future()
        self.claimed: Optional[float] = None
        self.rounds = 0
        self.first_wait = self.riding = self.between = self.back_at = 0.0

    def __lt__(self, other: "_Pending") -> bool:
        return (self.deadline, self.seq) < (other.deadline, other.seq)

    def claim(self, now: float) -> None:
        if self.claimed is None:
            self.first_wait = now - self.arrival
        else:
            self.between += now - self.back_at
        self.claimed = now
        self.rounds += 1

    def handed_back(self, now: float) -> None:
        self.riding += now - self.claimed
        self.back_at = now

    def life(self, now: float) -> Dict[str, Any]:
        """The query's record at its delivery (``now``)."""
        return {"firstWaitUs": round(self.first_wait * 1e6, 1),
                "rounds": self.rounds,
                "ridingUs": round(
                    (self.riding + now - self.claimed) * 1e6, 1),
                "betweenUs": round(self.between * 1e6, 1)}


class _Running:
    """The future of a query its lane HANDED BACK (see
    :meth:`BatchDispatcher._carry`): the query is running already (its
    waiter can no longer shed it), so the group that takes it up again
    claims it without asking, through the calls every queued query's
    future gets."""

    __slots__ = ("_future",)

    def __init__(self, future: Future):
        self._future = future

    def set_running_or_notify_cancel(self) -> bool:
        return True

    def done(self) -> bool:
        return self._future.done()

    def set_result(self, result) -> None:
        self._future.set_result(result)

    def set_exception(self, exc) -> None:
        self._future.set_exception(exc)


class BatchLane:
    """One query kind's lane inside the shared :class:`BatchDispatcher`
    — its own EDF queue, batch cap and group-dispatch function, but the
    SAME dispatcher thread and deadline policy as every other lane.
    Exposes the submit/stats surface servers and benches use."""

    def __init__(self, dispatcher: "BatchDispatcher", name: str,
                 max_batch: int,
                 dispatch_fn: Callable[["DeviceTopK", List[_Pending]],
                                       Optional[List[_Pending]]]):
        self._d = dispatcher
        self.name = name
        self.max_batch = int(max_batch)
        self.dispatch_fn = dispatch_fn
        self.queue: List[_Pending] = []  # dispatcher-owned, EDF-sorted
        # stats (written under the dispatcher's stats lock). `pending`
        # counts queries WAITING anywhere — handoff deque or lane
        # queue — so queue-depth observability covers the window while
        # the dispatcher is blocked inside a device dispatch (the old
        # cv-based batcher counted at submit; len(queue) alone would
        # read 0 through exactly the overload the gauge exists to show)
        self.pending = 0
        self.dispatches = 0
        self.batched_queries = 0
        self.rejections = 0
        self.triggers = {"size": 0, "window": 0, "free": 0, "drain": 0}
        self.depth_samples: collections.deque = collections.deque(
            maxlen=512)

    def submit(self, payload, k: int,
               span=None) -> Tuple[np.ndarray, np.ndarray]:
        """Enqueue, block for the shared dispatch, render THIS request's
        rows on the calling thread. Raises :class:`QueryRejectedError`
        after the PR-7 queue deadline. ``span`` (a live trace
        :class:`~predictionio_tpu.utils.tracing.Span`) receives the
        dispatch's flight record as a ``dispatch`` attribute — how slow
        query exemplars get their bucket/fill/kernel/AOT context — and
        beside it the query's own ``life`` (:meth:`_Pending.life`) and
        ``wakeUs``, the time from the dispatcher resolving the future
        to this thread running again, which the span's stage summary
        reports as ``device.wake`` (a ``device.*`` name: part of the
        wait for the answer, not of the handler's own work; an
        attribute and not a child span, which cost the median of the
        busiest cell more than a percent: PERF.md section 6, PR 36)."""
        k = int(k)
        res, row = self._d.submit_wait(self, payload, k)
        if span is not None and res.telemetry is not None:
            span.attributes["dispatch"] = res.telemetry
            if res.lives is not None:
                span.attributes["life"] = res.lives[row]
                span.attributes["wakeUs"] = round(
                    (_tracing.span_now() - res.delivered) * 1e6, 1)
        return res.render(row, k)

    def submit_async(self, payload, k: int,
                     window: Optional[float] = None) -> Future:
        """Enqueue without blocking; the future resolves to
        ``(_BatchResult, row)``. ``window`` states this query's own
        batching budget (its EDF deadline is arrival + window, and a
        free dispatcher holds it until then)."""
        return self._d.enqueue(self, payload, int(k), window=window)

    def stats(self) -> Dict[str, Any]:
        """The unified ``batcher_stats`` shape (same keys for user and
        item lanes): throughput counters, dispatch-trigger breakdown,
        batch-fill ratio and queue-depth percentiles over the last 512
        dispatches."""
        with self._d._stats_lock:
            depths = list(self.depth_samples)
            st: Dict[str, Any] = {
                "batcher": self.name,
                "dispatches": self.dispatches,
                "batchedQueries": self.batched_queries,
                "queueDepth": self.pending,
                "maxBatch": self.max_batch,
                "windowSec": self._d.window,
                "dispatchTriggers": dict(self.triggers),
                "rejectedQueries": self.rejections,
                "batchFillRatio": round(
                    self.batched_queries
                    / (self.dispatches * self.max_batch), 4)
                if self.dispatches else 0.0,
            }
        if depths:
            a = np.asarray(depths)
            st["queueDepthPercentiles"] = {
                "p50": float(np.percentile(a, 50)),
                "p90": float(np.percentile(a, 90)),
                "p99": float(np.percentile(a, 99)),
                "max": int(a.max()),
            }
        else:
            st["queueDepthPercentiles"] = None
        return st


class BatchDispatcher:
    """Deadline-aware cross-request batching for device queries — the
    PR-10 replacement for the condition-variable ``_MicroBatcher`` /
    ``_ItemBatcher`` pair.

    ONE dispatcher thread serves every lane. Callers hand off through a
    deque plus an event wake; the only lock the submit path shares with
    the dispatcher (``_thread_lock``, making the closed-check + append
    atomic against ``close()``) is never held across a device dispatch
    — submits never wait on device work. The thread moves arrivals into per-lane
    queues kept sorted by DEADLINE (earliest-deadline-first; a query's
    deadline is its arrival, plus a window where a caller stated one)
    and dispatches a lane when:

    - ``size``:   the lane holds ``max_batch`` queries — a full batch
                  amortizes one device dispatch over all of them;
    - ``free``:   the dispatcher is free and nobody asked it to hold
      the lane's OLDEST query: it goes at once, with whatever queued
      up behind it while the previous dispatch was in flight;
    - ``window``: the window a caller stated for the oldest query
      (``BatchDispatcher(window=...)`` for every query,
      ``submit_async(..., window=...)`` for one) has run out — a
      contract, honoured to the letter: the query is held for company
      until then and no longer;
    - ``drain``:  the dispatcher is closing and flushes what is queued.

    Nothing is held by default, and no setting asks for it: a busy
    dispatcher batches what arrives during its dispatch in flight, and
    a free one that waited for company would charge every query the
    wait for a batch the next dispatch gathers anyway (measured at
    60-800 qps: PERF.md section 6, PR 45).

    A lane whose queries take several device rounds (the slate lane,
    ``ops/slates.py``) RETURNS from its dispatch function the queries
    it has not finished; :meth:`_carry` puts them back at the head of
    the lane's queue with the deadlines they came with, so the next
    group is those queries and then new arrivals, and a query is
    delivered (by the lane, as ever) when its own last round ends.
    Between two rounds :meth:`_pick` offers every OTHER lane one turn
    first, by the same deadline rule. A lane that returns None takes
    none of that path.

    Results travel back through per-request futures; per-request
    rendering runs on the waiting threads (:class:`_BatchResult`). The
    PR-7 queue-deadline shedding is preserved: a query still queued
    past ``PIO_QUERY_QUEUE_DEADLINE`` cancels its future and surfaces
    as a 503 + Retry-After; one already drained into an in-flight
    dispatch blocks for its imminent result instead."""

    name = "pio-microbatch-dispatcher"

    def __init__(self, server: "DeviceTopK", window: float = 0.0):
        # weakref: the dispatcher thread must not pin the server's
        # factor matrices alive after the owner drops it (model swap)
        self._srv_ref = weakref.ref(server)
        self.window = float(window)
        # queue deadline resolved ONCE (env read off the submit path);
        # a server restart picks up a changed PIO_QUERY_QUEUE_DEADLINE
        self._deadline = _queue_deadline()
        self._lanes: List[BatchLane] = []
        self._handoff: collections.deque = collections.deque()
        self._wake = threading.Event()
        self._seq = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._thread_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._closed = False
        # between two rounds of a lane that handed queries back: the
        # other lanes, each still owed one turn (see _carry, _pick)
        self._owed: List[BatchLane] = []

    def add_lane(self, name: str, max_batch: int,
                 dispatch_fn) -> BatchLane:
        lane = BatchLane(self, name, max_batch, dispatch_fn)
        self._lanes.append(lane)
        return lane

    # -- submit side -------------------------------------------------------

    def enqueue(self, lane: BatchLane, payload, k: int,
                window: Optional[float] = None) -> Future:
        if self._closed:
            raise RuntimeError("serving backend is closed")
        w = self.window if window is None else float(window)
        now = time.monotonic()
        item = _Pending(payload, k, now + w, next(self._seq),
                        arrival=now,
                        ctx=_tracing.current_trace_context())
        # the waiter's future, taken before the dispatcher can see the
        # item: a lane that hands the query back wraps ``item.future``
        # (_carry), possibly before this call returns
        future = item.future
        # pending is incremented BEFORE the item becomes visible in the
        # handoff: the dispatcher's decrement (at pop, under the stats
        # lock) can then never run before this increment, so the depth
        # gauge/samples cannot go transiently negative — the worst
        # inconsistency is a <=1 overcount for the instant an enqueue
        # is in flight
        with self._stats_lock:
            lane.pending += 1
        # the closed-check and the append are one atomic step against
        # close(): once close() flips _closed under this lock, no item
        # can slip into the handoff AFTER its final drain and strand an
        # unresolved future. (The lock is never held across a device
        # dispatch — the dispatcher takes it only for its brief
        # idle-exit check — and appending before wake/ensure means the
        # idle-exit emptiness re-check can never strand an item either.)
        try:
            with self._thread_lock:
                if self._closed:
                    raise RuntimeError("serving backend is closed")
                self._handoff.append((lane, item))
        except BaseException:
            with self._stats_lock:
                lane.pending -= 1
            raise
        self._set_queue_gauge(lane)
        self._wake.set()
        self._ensure_thread()
        return future

    def submit_wait(self, lane: BatchLane, payload,
                    k: int) -> Tuple[_BatchResult, int]:
        fut = self.enqueue(lane, payload, k)
        deadline = self._deadline
        try:
            return fut.result(timeout=deadline)
        except _FuturesTimeout:
            # queued past the deadline: cancel-if-still-queued wins a
            # fast 503; losing the race means the dispatcher already
            # owns it and the result is imminent — block for it.
            if fut.cancel():
                with self._stats_lock:
                    lane.rejections += 1
                from predictionio_tpu.utils import metrics

                metrics.MICROBATCH_REJECTIONS.inc(batcher=lane.name)
                raise QueryRejectedError(
                    f"query queued past {deadline}s without a device "
                    "dispatch slot; retry shortly",
                    retry_after=min(5.0, max(1.0, deadline / 4)))
            return fut.result()

    def _ensure_thread(self) -> None:
        t = self._thread
        if t is not None and t.is_alive():
            return
        with self._thread_lock:
            if self._closed:
                return
            if self._thread is None or not self._thread.is_alive():
                # the dispatcher may have exited through the
                # weakref-dead idle path (server briefly unreferenced);
                # restart it — queues and stats survive
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name=self.name)
                self._thread.start()

    def close(self) -> None:
        """Stop accepting queries, DRAIN what is queued (pending
        queries get their results — a graceful shutdown answers its
        stragglers), then stop the dispatcher thread. Idempotent."""
        with self._thread_lock:
            if self._closed:
                return
            self._closed = True
            thread = self._thread
        self._wake.set()
        if thread is threading.current_thread():
            # called from inside a dispatch fn: the running loop sees
            # _closed and drains after this dispatch returns
            return
        if thread is not None and thread.is_alive():
            thread.join(timeout=10.0)
            if thread.is_alive():
                # wedged inside a device dispatch past the join budget:
                # the thread OWNS the lane queues — touching them here
                # would race its pop loop (both sides claiming the same
                # futures). When the dispatch unwedges, the loop drains
                # under _closed and exits on its own.
                return
        # no dispatcher left, and enqueue can no longer append (the
        # closed flag flipped under _thread_lock): fail what remains
        with self._thread_lock:
            self._drain_handoff()
            for lane in self._lanes:
                leftover, lane.queue = lane.queue, []
                with self._stats_lock:
                    lane.pending -= len(leftover)
                for it in leftover:
                    if it.future.set_running_or_notify_cancel():
                        it.future.set_exception(
                            RuntimeError("serving backend closed"))
                self._set_queue_gauge(lane)

    # -- dispatcher thread -------------------------------------------------

    def _drain_handoff(self) -> None:
        while True:
            try:
                lane, item = self._handoff.popleft()
            except IndexError:
                return
            bisect.insort(lane.queue, item)

    def _set_queue_gauge(self, lane: BatchLane) -> None:
        from predictionio_tpu.utils import metrics

        metrics.MICROBATCH_QUEUE_DEPTH.set(lane.pending,
                                           batcher=lane.name)

    def _all_empty(self) -> bool:
        return not self._handoff and all(not ln.queue
                                         for ln in self._lanes)

    def _pick(self, now: float) -> Tuple[Optional[BatchLane],
                                         Optional[str]]:
        """The lane to dispatch NOW, with its trigger — a full lane
        first, else the lane whose earliest deadline has expired
        (earliest wins across lanes; ``window`` where that query was
        held for a stated window, ``free`` where nothing held it),
        else nothing yet. Between two rounds of a lane that handed
        queries back, the lanes still ``_owed`` a turn are asked
        first, by the same rule, once each: a long query holds no
        other lane back for longer than a round."""
        if self._owed:
            owed, self._owed = self._owed, []
            lane, trigger = self._due(owed, now)
            if lane is not None:
                self._owed = [ln for ln in owed if ln is not lane]
                return lane, trigger
        return self._due(self._lanes, now)

    def _due(self, lanes: List[BatchLane], now: float
             ) -> Tuple[Optional[BatchLane], Optional[str]]:
        best: Optional[BatchLane] = None
        head: Optional[_Pending] = None
        for lane in lanes:
            q = lane.queue
            if not q:
                continue
            if self._closed:
                return lane, "drain"
            if len(q) >= lane.max_batch:
                return lane, "size"
            it = q[0]
            if it.deadline <= now and (head is None
                                       or it.deadline < head.deadline):
                best, head = lane, it
        if head is None:
            return None, None
        return best, "window" if head.deadline > head.arrival else "free"

    def _next_delay(self, now: float) -> Optional[float]:
        earliest: Optional[float] = None
        for lane in self._lanes:
            if lane.queue:
                d = lane.queue[0].deadline
                if earliest is None or d < earliest:
                    earliest = d
        return None if earliest is None else max(0.0, earliest - now)

    def _run(self) -> None:
        _dtel.mark_ready()
        while True:
            with _dtel.stage("pickUs", "batch.pick"):
                self._wake.clear()
                self._drain_handoff()
                now = time.monotonic()
                lane, trigger = self._pick(now)
            if lane is not None:
                self._dispatch(lane, trigger)
                continue
            if self._closed:
                if self._all_empty():
                    return
                continue
            delay = self._next_delay(now)
            if delay is None:
                # idle: bounded wait, exit when the owner was dropped
                with _dtel.stage("gapIdleUs", "batch.idle"):
                    woke = self._wake.wait(1.0)
                if not woke and self._srv_ref() is None:
                    with self._thread_lock:
                        self._drain_handoff()
                        if self._all_empty():
                            self._thread = None
                            return
            elif delay > 0:
                with _dtel.stage("gapWindowUs", "batch.window"):
                    self._wake.wait(delay)

    def _dispatch(self, lane: BatchLane, trigger: str) -> None:
        q = lane.queue
        group: List[_Pending] = []
        with _dtel.stage("formUs", "batch.form"):
            with self._stats_lock:
                depth = lane.pending  # waiting anywhere, handoff included
            popped = 0
            while q and len(group) < lane.max_batch:
                it = q.pop(0)  # EDF: earliest deadline forms the batch
                popped += 1
                # a False return means the waiter shed it
                # (queue-deadline 503) — drop it from the batch
                if it.future.set_running_or_notify_cancel():
                    group.append(it)
            with self._stats_lock:
                lane.pending -= popped
            self._set_queue_gauge(lane)
        if not group:
            return
        srv = self._srv_ref()
        back = None
        try:
            if srv is None:
                raise RuntimeError("serving backend was released")
            if _dtel.enabled():
                # batching context the device dispatch site cannot see:
                # the age of the oldest grouped query (its earlier
                # rounds included, in a lane that hands queries back),
                # the group size, and a trace parent (the dispatcher
                # thread has no ambient trace of its own — borrow the
                # first traced query's so the device.execute span lands
                # in a tree); every query's own life is stamped here
                now = time.monotonic()
                for it in group:
                    it.claim(now)
                wait = max(0.0, now - min(it.arrival for it in group))
                parent = next((it.ctx for it in group
                               if it.ctx is not None), None)
                with _dtel.dispatch_scope(queue_wait_us=wait * 1e6,
                                          group=len(group),
                                          trace_parent=parent):
                    back = lane.dispatch_fn(srv, group)
                if back:
                    now = time.monotonic()
                    for it in back:
                        it.handed_back(now)
            else:
                back = lane.dispatch_fn(srv, group)
        except BaseException as e:  # propagate to every waiter
            for it in group:
                if not it.future.done():
                    it.future.set_exception(e)
        finally:
            del srv  # never hold the server across the idle wait
            for it in (group if back is None
                       else [it for it in group if it not in back]):
                if not it.future.done():
                    it.future.set_exception(RuntimeError(
                        "batch dispatch completed without a result"))
        with _dtel.stage("deliverUs", "batch.deliver", done=True):
            with self._stats_lock:
                lane.dispatches += 1
                lane.batched_queries += len(group)
                lane.triggers[trigger] += 1
                lane.depth_samples.append(depth)
            from predictionio_tpu.utils import metrics

            metrics.MICROBATCH_DISPATCHES.inc(batcher=lane.name)
            metrics.MICROBATCH_QUERIES.inc(amount=len(group),
                                           batcher=lane.name)
            metrics.MICROBATCH_BATCH_SIZE.observe(len(group),
                                                  batcher=lane.name)
            metrics.MICROBATCH_TRIGGERS.inc(batcher=lane.name,
                                            trigger=trigger)
            metrics.MICROBATCH_FILL.observe(len(group) / lane.max_batch,
                                            batcher=lane.name)
            metrics.MICROBATCH_QUEUE_AT_DISPATCH.observe(
                depth, batcher=lane.name)
        if back is not None:
            with _dtel.stage("pickUs", "batch.pick"):
                self._carry(lane, back)

    def _carry(self, lane: BatchLane, back: List[_Pending]) -> None:
        """``back``: the queries of the group just dispatched that
        their lane has not finished. They return to the head of the
        lane's queue (their deadlines are older than any arrival's;
        their futures, running already, are wrapped so that the next
        group claims them like any other), and every other lane is
        owed one turn before this lane's next round (:meth:`_pick`)."""
        for it in back:
            if type(it.future) is not _Running:
                it.future = _Running(it.future)
            bisect.insort(lane.queue, it)
        with self._stats_lock:
            lane.pending += len(back)
        self._set_queue_gauge(lane)
        self._owed = [ln for ln in self._lanes if ln is not lane]


def _deliver(group: List[_Pending], idx: np.ndarray,
             scores: np.ndarray) -> None:
    """Resolve every waiter's future with the shared result (rendering
    happens on the waiting threads). The dispatch just recorded on THIS
    thread (telemetry on) rides along, so a waiter's ``device.*`` span
    names its bucket, fill and stage stamps; the lives of the queries
    a dispatcher claimed are closed here, the one place every lane
    delivers through, and join that record's ``lives`` in the group's
    order."""
    with _dtel.stage("deliverUs", "batch.deliver", done=True):
        rec = _dtel.last_record() if _dtel.enabled() else None
        res = _BatchResult(idx, scores, telemetry=rec)
        if rec is not None and group and group[0].claimed is not None:
            now = time.monotonic()
            res.lives = [it.life(now) for it in group]
            rec.setdefault("lives", []).extend(res.lives)
            res.delivered = _tracing.span_now()
        for row, it in enumerate(group):
            if not it.future.done():
                it.future.set_result((res, row))


def _dispatch_user_group(srv: "DeviceTopK",
                         group: List[_Pending]) -> None:
    """Per-user top-k requests -> one ``users_topk`` dispatch (the
    batch pads to its power-of-two uid bucket inside ``users_topk``;
    every ladder bucket is AOT-precompiled, so arbitrary group sizes
    never pay a serve-time compile)."""
    kmax = max(it.k for it in group)
    uids = np.asarray([it.payload for it in group], dtype=np.int64)
    _deliver(group, *srv.users_topk(uids, kmax))


def _dispatch_item_group(srv: "DeviceTopK",
                         group: List[_Pending]) -> None:
    """Item-similarity requests (each a tuple of query-item indices) ->
    one vmapped ``_items_topk`` dispatch: the group pads to its
    power-of-two row bucket, each row's item list to the group's common
    power-of-two length."""
    with _dtel.stage("formUs", "batch.form"):
        kmax = max(it.k for it in group)
        n = len(group)
        B = srv.ITEM_QUERY_BUCKET
        while B < max(len(it.payload) for it in group):
            B *= 2
        G = _bucket(n, lo=8)
        idxs = np.zeros((G, B), dtype=np.int32)
        masks = np.zeros((G, B), dtype=np.float32)
        for row, it in enumerate(group):
            m = len(it.payload)
            idxs[row, :m] = np.asarray(it.payload, dtype=np.int32)
            masks[row, :m] = 1.0
    _deliver(group, *srv._items_topk_batched(idxs, masks, kmax))


_live_servers: "weakref.WeakSet[DeviceTopK]" = weakref.WeakSet()


def batcher_stats() -> List[Dict[str, Any]]:
    """Every live micro-batch lane's unified stats, process-wide — the
    ``/stats.json`` ``batchers`` surface (user and item lanes share one
    shape; see :meth:`BatchLane.stats`)."""
    out: List[Dict[str, Any]] = []
    for srv in list(_live_servers):
        try:
            out.extend(srv.stats().values())
        except Exception:  # a server mid-teardown must not 500 /stats
            continue
    return out


def _live_store_bytes() -> float:
    """Total HBM bytes pinned by live device stores (pull-gauge
    source for ``pio_device_store_bytes``)."""
    total = 0
    for srv in list(_live_servers):
        try:
            total += srv.memory_report()["totalBytes"]
        except Exception:
            continue
    return float(total)


def _live_ladder_bytes() -> float:
    """Bytes the AOT ladders' programs need for themselves (largest
    temporaries + code) across live stores (pull-gauge source for
    ``pio_aot_ladder_bytes``)."""
    total = 0
    for srv in list(_live_servers):
        try:
            total += srv._aot_programs.memory_report()["totalBytes"]
        except Exception:
            continue
    return float(total)


# pull gauges: computed at scrape time from whatever servers are live,
# so there is no per-server registration/teardown bookkeeping to leak
_metrics.DEVICE_STORE_BYTES.set_function(_live_store_bytes)
_metrics.AOT_LADDER_BYTES.set_function(_live_ladder_bytes)


def device_report() -> Dict[str, Any]:
    """The query server's ``/stats.json`` ``device`` block: per-store
    HBM accounting (factor/seen/scale bytes by dtype, live across
    fold-in growth and int8 requant), AOT ladder coverage
    (planned/compiled/warmed/hit) + executable-memory estimate, and the
    flight recorder's per-lane dispatch summary."""
    stores: List[Dict[str, Any]] = []
    store_bytes = ladder_bytes = 0
    for srv in list(_live_servers):
        try:
            mem = srv.memory_report()
            ladder = srv.ladder_report()
        except Exception:  # a server mid-teardown must not 500 /stats
            continue
        store_bytes += mem["totalBytes"]
        ladder_bytes += ladder["memory"]["totalBytes"]
        stores.append({"store": mem, "aotLadder": ladder})
    rec = _dtel.recorder()
    return {
        "telemetry": {"enabled": rec.enabled, **rec.counts()},
        "storeBytes": store_bytes,
        "aotLadderBytes": ladder_bytes,
        "stores": stores,
        "dispatch": rec.summary(),
    }


def _placement(arr) -> List[Dict[str, Any]]:
    """Where ``arr`` actually lives, read from the array itself: one
    entry per device holding a shard (rows + bytes of that shard) with
    the device's own ``bytes_in_use`` — so a report can show EVERY
    device carrying its part of the item store, not infer it from the
    shard count."""
    out = []
    for sh in sorted(arr.addressable_shards, key=lambda s: s.device.id):
        d = sh.device
        stats = d.memory_stats() or {}
        out.append({"device": int(d.id), "platform": d.platform,
                    "kind": d.device_kind,
                    "rows": int(sh.data.shape[0]),
                    "bytes": int(sh.data.nbytes),
                    "bytesInUse": stats.get("bytes_in_use")})
    return out


def _table_sig(f) -> Tuple:
    """Shape + dtype of one factor table (int8 stores: of the data)."""
    from predictionio_tpu.ops.quantize import is_quantized

    if is_quantized(f):
        return ("int8q", tuple(f.data.shape), str(f.data.dtype))
    return (tuple(f.shape), str(f.dtype))


_scatter_jits: Dict[bool, object] = {}


def _scatter_rows(table, idx, rows):
    """Jitted row scatter for live-store patches: ``table.at[idx].set``
    with the rows cast to the store dtype. On accelerators the input
    table is DONATED — the scatter reuses the store's own HBM instead
    of copying it (the PR-5 donation discipline applied to serving);
    the XLA runtime serializes the aliasing against any in-flight
    reader of the same buffer. CPU has no donation path, so there the
    program is a plain copy (and jax would warn on every patch)."""
    import jax

    donate = jax.default_backend() != "cpu"
    fn = _scatter_jits.get(donate)
    if fn is None:
        fn = jax.jit(lambda t, i, r: t.at[i].set(r.astype(t.dtype)),
                     donate_argnums=(0,) if donate else ())
        _scatter_jits[donate] = fn
    import jax.numpy as jnp

    return fn(table, jnp.asarray(idx), jnp.asarray(rows))


_quant_scatter_jits: Dict[bool, object] = {}


def _scatter_quant_rows(data, scale, idx, row_d, row_s):
    """Int8 data rows and their per-row scales scattered in ONE
    dispatch (donating both on accelerators): a quantized row is only
    meaningful WITH its scale, so the pair must land or fail together."""
    import jax

    donate = jax.default_backend() != "cpu"
    fn = _quant_scatter_jits.get(donate)
    if fn is None:
        fn = jax.jit(
            lambda d, s, i, rd, rs: (d.at[i].set(rd.astype(d.dtype)),
                                     s.at[i].set(rs.astype(s.dtype))),
            donate_argnums=(0, 1) if donate else ())
        _quant_scatter_jits[donate] = fn
    import jax.numpy as jnp

    return fn(data, scale, jnp.asarray(idx), jnp.asarray(row_d),
              jnp.asarray(row_s))


class DeviceTopK:
    """AOT-compiled top-N server over device-resident (optionally
    sharded) factor matrices.

    ``user_factors``/``item_factors`` may be host numpy (placed on the
    default device) or jax Arrays that are already sharded — they are
    used as-is, so a PAlgorithm model's HBM shards serve directly.

    Concurrent ``user_topk`` callers are micro-batched into one device
    dispatch (see :class:`BatchDispatcher`); set ``microbatch=False`` or
    ``PIO_SERVING_MICROBATCH=0`` to dispatch per call.

    The factor store's precision is the PR-5 policy extended one stop
    down the Tensor Casting axis: fp32, bf16 (the accelerator default),
    or ``PIO_SERVE_PRECISION=int8`` — int8 rows with per-row fp32
    absmax scales (:mod:`~predictionio_tpu.ops.quantize`), ~4x less
    HBM than fp32 for the model AND the per-dispatch item stream,
    scores always accumulated + returned fp32. On TPU the top-k itself
    runs as ONE fused Pallas program (gather -> score -> seen-mask ->
    top-k, item tiles streamed HBM->VMEM exactly once —
    ``ops/als_pallas.py::fused_gather_score_topk``); ``PIO_SERVE_KERNEL
    =xla`` opts back into the XLA chain, which CPU and mesh-sharded
    stores use always.

    The user factor store is LIVE-PATCHABLE (:meth:`patch_users`, the
    online fold-in write path): every device dispatch snapshots the
    store references under ``_store_lock``, and a patch swaps all of
    them under the same lock — an in-flight micro-batch therefore sees
    either the whole old store or the whole new one, never a torn mix.
    """

    ITEM_QUERY_BUCKET = 8  # padded query-item count for similarity queries

    def __init__(self, user_factors, item_factors,
                 seen: Optional[Dict[int, np.ndarray]] = None,
                 n_users: Optional[int] = None,
                 n_items: Optional[int] = None,
                 microbatch: Optional[bool] = None,
                 item_layout=None,
                 shards: Optional[int] = None):
        import os

        import jax
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
            quantize_rows_int8,
        )

        self._store_lock = threading.RLock()
        # one store WRITER at a time (fold-in patches). Queries never
        # take it: a writer holds _store_lock only to swap references,
        # so a growing patch can compile the grown store's ladder for
        # as long as it takes while the old store keeps serving
        self._write_lock = threading.RLock()
        if microbatch is None:
            microbatch = os.environ.get(
                "PIO_SERVING_MICROBATCH",
                "1").strip().lower() not in ("0", "off", "false")
        self._dispatcher: Optional[BatchDispatcher] = None
        self._batcher: Optional[BatchLane] = None
        self._item_batcher: Optional[BatchLane] = None
        if microbatch:
            self._dispatcher = BatchDispatcher(self)
            self._batcher = self._dispatcher.add_lane(
                "pio-microbatch", max_batch=256,
                dispatch_fn=_dispatch_user_group)
            self._item_batcher = self._dispatcher.add_lane(
                "pio-microbatch-items", max_batch=64,
                dispatch_fn=_dispatch_item_group)

        def to_device(f):
            if is_quantized(f):
                return QuantFactors(
                    f.data if hasattr(f.data, "sharding")
                    else jnp.asarray(f.data),
                    jnp.asarray(f.scale).astype(jnp.float32))
            return f if hasattr(f, "sharding") else jnp.asarray(f)

        # the store's declared precision, static for this server's
        # lifetime: every compiled program threads it explicitly into
        # _score_einsum (never sniffed from operand dtypes). An input
        # that is ALREADY int8+scales forces int8 — the store is what
        # it is, whatever the env says.
        mode = _serve_precision_mode()
        if is_quantized(user_factors) or is_quantized(item_factors):
            mode = "int8"
        self._mode = mode
        with _trace_span("store.upload"):
            self._X = to_device(user_factors)
            self._Y = to_device(item_factors)
            if mode == "bf16":
                # opt-in bf16 factor store: halves the HBM the model
                # holds AND the bytes every scoring matmul streams; the
                # cast preserves an existing mesh sharding (elementwise
                # program). Scores still accumulate + return fp32
                # (_score_einsum).
                if not _is_bf16(self._X):
                    self._X = self._X.astype(jnp.bfloat16)
                if not _is_bf16(self._Y):
                    self._Y = self._Y.astype(jnp.bfloat16)
            elif mode == "int8":
                # int8 store with per-row fp32 scales (symmetric
                # absmax): ~4x less HBM than fp32, ~2x less than bf16,
                # for the model AND the per-dispatch item stream; scores
                # still accumulate + return fp32. Row-wise ops preserve
                # an existing row sharding; the cast is one-time at load.
                if not is_quantized(self._X):
                    self._X = quantize_rows_int8(self._X)
                if not is_quantized(self._Y):
                    self._Y = quantize_rows_int8(self._Y)
            # the span is the transfer, not its enqueue
            jax.block_until_ready((self._X, self._Y))
        # factor tables may be padded (sharded training pads rows);
        # n_users/n_items bound the valid index range
        self.n_users = int(n_users if n_users is not None
                           else self._X.shape[0])
        self.n_items = int(n_items if n_items is not None
                           else self._Y.shape[0])
        # sharded live plane (ISSUE 15): an explicit layout / shard
        # count re-places the store density-aware over a serve mesh; a
        # pre-sharded PAlgorithm store keeps its own placement. Either
        # way every top-k dispatches per-shard + on-device merge.
        self._shard: Optional[Tuple[Any, str, int]] = None
        self._layout = None
        self._perm_np: Optional[np.ndarray] = None
        self._inv_np: Optional[np.ndarray] = None
        self._valid = None
        self._setup_sharded_store(item_layout, shards, seen)
        # which top-k program family serves: the fused Pallas kernel
        # (one program: gather -> score -> mask -> top-k, item tiles
        # stream HBM->VMEM exactly once) or the XLA chain. On a
        # mesh-sharded store both run PER SHARD under shard_map with
        # the log-tree merge on top (hard part #5).
        self._kernel = _serve_kernel_mode()
        # Pallas runs compiled (Mosaic) on TPU and interpreted anywhere
        # else — derived from the platform, never a switch; stamped
        # into every fused dispatch's flight record so "the kernel
        # ran" can be told from "the interpreter ran"
        self._interpret = jax.default_backend() != "tpu"
        if self._kernel == "fused" and self._shard is None:
            # mesh-committed factors WITHOUT a shard context (dim0
            # replicated, or sharded over >1 axis): the per-shard lane
            # cannot express them and the single-chip fused kernel must
            # not run on multi-device arrays — keep the XLA chain, as
            # before ISSUE 15
            for f in (self._X, self._Y):
                sh = getattr(f, "sharding", None)
                if sh is not None and getattr(
                        getattr(sh, "mesh", None), "devices",
                        np.empty(1)).size > 1:
                    self._kernel = "xla"
                    break
        if self._kernel == "fused" and self._shard is None:
            # pad the item table ONCE to the kernel's tile multiple so
            # no dispatch ever pays a per-call copy; padded rows sit
            # past n_items and are masked on device like any training
            # padding (sharded stores pad per shard inside the kernel
            # call — their cap is the layout's, not the tile's)
            self._Y = _pad_item_rows_for_kernel(self._Y)
        self._mask_seen = bool(seen)
        if self._mask_seen:
            # one bit per (user, store position): see seen_bitmap
            with _trace_span("store.bitmap"):
                bits = seen_bitmap(self._translate_seen(seen),
                                   int(self._X.shape[0]),
                                   int(self._Y.shape[0]))
        else:
            # a store that masks nothing: one word the programs take
            # as an argument and never gather rows from, so it needs
            # no bitmap's width (seen_row_words)
            bits = np.zeros((1, 1), dtype=np.int32)
        with _trace_span("store.upload"):
            self._seen_bits = jax.block_until_ready(
                self._replicate_like_factors(jnp.asarray(bits)))
        self._user_programs: Dict[int, object] = {}
        self._batch_programs: Dict[Tuple[int, int], object] = {}
        self._item_programs: Dict[object, object] = {}
        # fused-kernel and sharded jit programs are shape-polymorphic
        # over the uid bucket, so those lanes cache per k-bucket only
        self._fused_programs: Dict[object, object] = {}
        self._shard_programs: Dict[object, object] = {}
        # AOT-compiled ladder executables (warmup/precompile): keyed by
        # (store signature, program shape) so a store reshaped by
        # fold-in growth can never hit a stale executable — the jit
        # program caches above stay as the always-correct fallback
        self._aot_programs = AOTCache(max_entries=512,
                                      name="serve-ladder")
        # ladder observability: lookup outcomes per dispatch (ints
        # bumped under _store_lock — the lookup already holds it) and
        # the last warmup()'s coverage figures, surfaced by
        # ladder_report() / the /stats.json device block
        self._aot_hits = 0
        self._aot_misses = 0
        self._ladder: Dict[str, int] = {"planned": 0, "compiled": 0,
                                        "fallback": 0, "warmed": 0}
        # the plan the last warmup() compiled: what a growing patch
        # compiles again for the grown store BEFORE publishing it
        self._ladder_plan: List[Tuple] = []
        self._Yn = None  # normalized item matrix, built on first item query
        _live_servers.add(self)
        # (re)register the HBM pull gauges: a registry reset (test
        # isolation) drops the scrape-time children registered at
        # module import, so each new store re-pins them — idempotent
        _metrics.DEVICE_STORE_BYTES.set_function(_live_store_bytes)
        _metrics.AOT_LADDER_BYTES.set_function(_live_ladder_bytes)

    def _setup_sharded_store(self, item_layout, shards: Optional[int],
                             seen) -> None:
        """Resolve the shard context and (re)place the factor store.

        Three lanes: (1) an explicit ``item_layout`` / ``shards`` /
        ``PIO_SERVE_SHARDS`` re-places the store onto a 1-D serve mesh
        in the density-aware item order (counts derived from ``seen``
        when no layout came with the model — the seen sets ARE the
        interaction sets); (2) a store whose arrays arrive mesh-sharded
        (PAlgorithm) keeps its own placement, positions == item ids;
        (3) single-device stores leave ``self._shard`` None."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
        )

        n_req = int(shards) if shards is not None else _serve_shards_env()
        if item_layout is not None and n_req <= 1:
            n_req = item_layout.n_shards
        if n_req > 1:
            ndev = len(jax.devices())
            if ndev < n_req:
                # a 1-device smoke host still runs the sharded lane —
                # degraded to what the hardware has, loudly
                import logging

                logging.getLogger("pio.serving").warning(
                    "requested %d serve shards but only %d device(s) "
                    "are visible; clamping", n_req, ndev)
                n_req = ndev
        if n_req > 1:
            from predictionio_tpu.parallel.als_sharding import (
                density_aware_item_layout,
            )
            from predictionio_tpu.parallel.mesh import data_parallel_mesh

            layout = item_layout
            if layout is None or layout.n_shards != n_req:
                counts = np.zeros(self.n_items, dtype=np.int64)
                if seen:
                    for items in seen.values():
                        it = np.asarray(items, dtype=np.int64)
                        it = it[(it >= 0) & (it < self.n_items)]
                        np.add.at(counts, it, 1)
                layout = density_aware_item_layout(counts, n_req)
            mesh = data_parallel_mesh(layout.n_shards)
            axis = "data"
            row = NamedSharding(mesh, P(axis, None))
            col = NamedSharding(mesh, P(axis))
            put = jax.device_put

            def perm_rows(a, fill):
                a = jnp.asarray(a)
                idx = jnp.asarray(np.clip(layout.perm, 0,
                                          max(int(a.shape[0]) - 1, 0)))
                out = jnp.take(a, idx, axis=0)
                real = jnp.asarray(layout.perm >= 0)
                real = real[(slice(None),) + (None,) * (out.ndim - 1)]
                return jnp.where(real, out,
                                 jnp.asarray(fill, dtype=out.dtype))

            def pad_rows(a, fill):
                a = jnp.asarray(a)
                pad = (-int(a.shape[0])) % layout.n_shards
                if pad:
                    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                                constant_values=fill)
                return a

            if is_quantized(self._Y):
                self._Y = QuantFactors(
                    put(perm_rows(self._Y.data, 0), row),
                    put(perm_rows(self._Y.scale, 1.0), col))
            else:
                self._Y = put(perm_rows(self._Y, 0.0), row)
            if is_quantized(self._X):
                self._X = QuantFactors(
                    put(pad_rows(self._X.data, 0), row),
                    put(pad_rows(self._X.scale, 1.0), col))
            else:
                self._X = put(pad_rows(self._X, 0.0), row)
            self._shard = (mesh, axis, layout.n_shards)
            self._layout = layout
            self._perm_np = layout.perm
            self._inv_np = layout.inv
            self._valid = put(jnp.asarray(layout.valid_mask()), col)
            return
        ctx = _dim0_shard_ctx(self._Y)
        if ctx is not None:
            mesh, axis = ctx
            n_sh = int(mesh.shape[axis])
            self._shard = (mesh, axis, n_sh)
            n_pos = int(self._Y.shape[0])
            valid = (np.arange(n_pos) < self.n_items).astype(np.float32)
            self._valid = jax.device_put(
                jnp.asarray(valid), NamedSharding(mesh, P(axis)))

    def _translate_seen(self, seen):
        """Item-id seen sets -> store-position seen sets (identity
        without a density layout). Ids outside [0, n_items) are dropped
        — they carry no position."""
        if self._inv_np is None or not seen:
            return seen
        inv = self._inv_np
        out = {}
        for u, items in seen.items():
            it = np.asarray(items, dtype=np.int64)
            it = it[(it >= 0) & (it < self.n_items)]
            out[u] = inv[it]
        return out

    def _positions_to_items(self, idx: np.ndarray) -> np.ndarray:
        """Store positions (device top-k output) -> item ids, host-side
        (k elements per query — negligible next to the fetch). Pad
        positions map to -1; their scores are -inf and every caller
        filters non-finite rows."""
        if self._perm_np is None:
            return idx
        return self._perm_np[idx].astype(np.int32)

    def _items_to_positions(self, idxs: np.ndarray) -> np.ndarray:
        """Item ids (similarity-query input) -> store positions."""
        if self._inv_np is None:
            return idxs
        return self._inv_np[idxs].astype(np.int32)

    def _replicate_like_factors(self, arr):
        """When the factors are sharded over a mesh, pin auxiliary tables
        replicated on the SAME mesh so one jitted program sees consistent
        placements; single-device factors leave the array as created."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._shard is not None:
            mesh = self._shard[0]
            return jax.device_put(arr, NamedSharding(mesh, P(None, None)))
        sh = getattr(self._X, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.devices.size > 1:
            return jax.device_put(arr, NamedSharding(sh.mesh, P(None, None)))
        return arr

    # -- compilation ------------------------------------------------------

    def _fused_user_program(self, kb: int):
        """The fused-kernel serving program for one k bucket: gather,
        dequant, seen-row gather, and the Pallas score+mask+top-k
        kernel lower into ONE program. Shape-polymorphic over the uid
        bucket (scalar uid included) — jit re-specializes per shape and
        the AOT ladder pins each bucket's executable."""
        prog = self._fused_programs.get(("u", kb))
        if prog is None:
            import jax
            import jax.numpy as jnp

            from predictionio_tpu.ops.als_pallas import (
                fused_gather_score_topk,
            )

            mode, mask_seen, n_items = (self._mode, self._mask_seen,
                                        self.n_items)
            interpret = self._interpret

            @jax.jit
            def users_topk_fused(X, Y, sb, uids):
                scalar = jnp.ndim(uids) == 0
                u = uids[None] if scalar else uids
                with jax.named_scope("gather_q"):
                    Q = _gather_rows_f32(X, u, mode=mode)
                seen = None
                if mask_seen:
                    with jax.named_scope("seen_rows"):
                        seen = jnp.take(sb, u, axis=0)
                with jax.named_scope("topk"):
                    vals, idx, rounds = fused_gather_score_topk(
                        Q, Y, k=kb, n_items=n_items, mask_seen=mask_seen,
                        seen_bits=seen, interpret=interpret)
                with jax.named_scope("pack"):
                    packed = _pack(vals, idx, rounds)
                return packed[0] if scalar else packed

            prog = self._fused_programs[("u", kb)] = users_topk_fused
        return prog

    def _fused_items_program(self, kb: int):
        """Fused-kernel item-similarity program: the [G, B] query
        bucket reduces to one summed query row per group, then the SAME
        kernel scores it against every item tile with the query items
        masked (their idx/mask table plays the seen-mask role)."""
        prog = self._fused_programs.get(("i", kb))
        if prog is None:
            import jax
            import jax.numpy as jnp

            from predictionio_tpu.ops.als_pallas import (
                fused_gather_score_topk,
                pack_seen_ids,
            )

            mode, n_items = self._mode, self.n_items
            interpret = self._interpret

            @jax.jit
            def items_topk(Yn, idxs, masks):
                with jax.named_scope("gather_q"):
                    qf = _gather_rows_f32(Yn, idxs, mode=mode)  # [G, B, R]
                    Q = (qf * masks[..., None]).sum(axis=1)      # [G, R]
                with jax.named_scope("seen_rows"):
                    own = pack_seen_ids(idxs, masks > 0, int(Yn.shape[0]))
                with jax.named_scope("topk"):
                    vals, idx, rounds = fused_gather_score_topk(
                        Q, Yn, own, k=kb, n_items=n_items, mask_seen=True,
                        interpret=interpret)
                with jax.named_scope("pack"):
                    return _pack(vals, idx, rounds)

            prog = self._fused_programs[("i", kb)] = items_topk
        return prog

    def _sharded_user_program(self, kb: int):
        """User-lane serving over the sharded store: gather (sharded,
        GSPMD) the query users' fp32 rows + their seen rows, then the
        explicit per-shard score/mask/top-k + log-tree merge
        (:func:`_sharded_score_topk`). Shape-polymorphic over the uid
        bucket (scalar included), cached per k bucket."""
        prog = self._shard_programs.get(("u", kb))
        if prog is None:
            import jax
            import jax.numpy as jnp

            mode, mask_seen = self._mode, self._mask_seen
            mesh, axis, _ = self._shard
            fused = self._kernel == "fused"
            interpret = self._interpret

            @jax.jit
            def users_topk_sharded(X, Y, valid, sb, uids):
                scalar = jnp.ndim(uids) == 0
                u = uids[None] if scalar else uids
                with jax.named_scope("gather_q"):
                    Q = _gather_rows_f32(X, u, mode=mode)
                with jax.named_scope("seen_rows"):
                    seen = jnp.take(sb, u, axis=0)
                with jax.named_scope("topk"):
                    vals, pos, rounds = _sharded_score_topk(
                        Y, valid, Q, seen, k=kb,
                        mask_seen=mask_seen, mode=mode, mesh=mesh,
                        axis=axis, fused=fused, interpret=interpret)
                with jax.named_scope("pack"):
                    packed = _pack(vals, pos, rounds)
                return packed[0] if scalar else packed

            prog = self._shard_programs[("u", kb)] = users_topk_sharded
        return prog

    def _sharded_items_program(self, kb: int):
        """Item-similarity serving over the sharded store: the [G, B]
        query bucket reduces to one summed normalized row per group,
        then the same per-shard score + merge with the query items
        masked (their positions become the seen bitmap)."""
        prog = self._shard_programs.get(("i", kb))
        if prog is None:
            import jax

            mode = self._mode
            mesh, axis, _ = self._shard
            fused = self._kernel == "fused"
            interpret = self._interpret

            @jax.jit
            def items_topk(Yn, valid, idxs, masks):
                from predictionio_tpu.ops.als_pallas import pack_seen_ids

                with jax.named_scope("gather_q"):
                    qf = _gather_rows_f32(Yn, idxs, mode=mode)  # [G, B, R]
                    Q = (qf * masks[..., None]).sum(axis=1)      # [G, R]
                # the query items mask themselves: their positions as a
                # bitmap over the store (pad slots carry mask 0)
                with jax.named_scope("seen_rows"):
                    own = pack_seen_ids(idxs, masks > 0,
                                        int(valid.shape[0]))
                with jax.named_scope("topk"):
                    vals, pos, rounds = _sharded_score_topk(
                        Yn, valid, Q, own, k=kb, mask_seen=True,
                        mode=mode, mesh=mesh, axis=axis, fused=fused,
                        interpret=interpret)
                with jax.named_scope("pack"):
                    return _pack(vals, pos, rounds)

            prog = self._shard_programs[("i", kb)] = items_topk
        return prog

    def _user_program(self, k: int):
        if self._shard is not None:
            return self._sharded_user_program(k)
        if self._kernel == "fused":
            return self._fused_user_program(k)
        import jax

        prog = self._user_programs.get(k)
        if prog is None:
            prog = jax.jit(self._xla_user_fn(k))
            self._user_programs[k] = prog
        return prog

    def _xla_user_fn(self, k: int):
        """The XLA-chain user program for one k bucket under the name
        the device trace shows its module by (``jit_users_topk_xla``)."""
        mask_seen, n_items, mode = (self._mask_seen, self.n_items,
                                    self._mode)

        def users_topk_xla(X, Y, sb, uid):
            return _user_topk(X, Y, sb, uid, k=k, mask_seen=mask_seen,
                              n_items=n_items, mode=mode)

        return users_topk_xla

    def _batch_program(self, k: int, b: int):
        """vmap of the per-user program over a [b] uid vector: b queries,
        one dispatch, one packed [b, 2k] fetch."""
        if self._shard is not None:
            return self._sharded_user_program(k)
        if self._kernel == "fused":
            return self._fused_user_program(k)
        import jax

        prog = self._batch_programs.get((k, b))
        if prog is None:
            prog = jax.jit(jax.vmap(self._xla_user_fn(k),
                                    in_axes=(None, None, None, 0)))
            self._batch_programs[(k, b)] = prog
        return prog

    def _items_program(self, kb: int, B: int, G: int):
        """vmap of the item-similarity program over a [G, B] query
        bucket (or its fused / sharded equivalent)."""
        if self._shard is not None:
            return self._sharded_items_program(kb)
        if self._kernel == "fused":
            return self._fused_items_program(kb)
        import jax

        prog = self._item_programs.get((kb, B, G))
        if prog is None:
            n_items, mode = self.n_items, self._mode

            def items_topk(Yn, idx, idx_mask):
                return _items_topk(Yn, idx, idx_mask, k=kb,
                                   n_items=n_items, mode=mode)

            prog = jax.jit(jax.vmap(items_topk, in_axes=(None, 0, 0)))
            self._item_programs[(kb, B, G)] = prog
        return prog

    def _normalized_items(self):
        """Row-normalized item matrix for similarity queries, computed
        once on first use (one extra HBM buffer, saves O(M*R) per query)."""
        if self._Yn is None:
            self._Yn = _normalize_rows(self._Y)
        return self._Yn

    # -- AOT bucket ladder -------------------------------------------------

    def _store_tables_locked(self) -> Dict[str, Any]:
        """The device tables every serving program takes as arguments
        (subclasses add theirs). Caller holds ``_store_lock``."""
        return {"X": self._X, "Y": self._Y, "seen_bits": self._seen_bits,
                "valid": self._valid}

    def _store_sig(self, tables: Dict[str, Any]) -> Tuple:
        """Abstract signature of a set of store tables — what every
        serving program's compilation is keyed on. AOT executables are
        cached under it, so the ladder compiled for a grown store is
        found the moment that store is published, and a stale
        executable can never be handed a reshaped one."""
        return (_table_sig(tables["X"]), _table_sig(tables["Y"]),
                tuple(tables["seen_bits"].shape), self._mode, self._kernel,
                0 if self._shard is None else int(self._shard[2]))

    def _store_sig_locked(self) -> Tuple:
        return self._store_sig(self._store_tables_locked())

    def _aot_get_locked(self, entry: Tuple):
        return self._aot_programs.get((self._store_sig_locked(), entry))

    def aot_plan(self, max_k: int = 128,
                 batch_sizes: Tuple[int, ...] = ()) -> List[Tuple]:
        """The FULL power-of-two program ladder live traffic can
        dispatch at — the single enumeration both the AOT precompiler
        (:meth:`warmup`/:meth:`precompile`) and the deploy-time
        ``workflow.create_server.warm_up`` consult, so warm-up coverage
        and AOT coverage can never diverge.

        Entries: ``("user", kb)`` single-query programs, ``("users",
        kb, bb)`` vmapped uid-bucket programs, ``("items", kb, B, gg)``
        vmapped item-similarity programs. ``kb`` sweeps the k buckets
        16,32,... up to ``max_k`` (clipped to ``n_items``); ``bb``/
        ``gg`` sweep 8,16,... up to each lane's max batch (plus any
        requested ``batch_sizes``, bucketed)."""
        ks: List[int] = []
        k = 16
        while True:
            kb = min(k, self.n_items)
            if kb >= 1 and kb not in ks:
                ks.append(kb)
            if k >= max_k or k >= self.n_items:
                break
            k *= 2
        bmax = self._batcher.max_batch if self._batcher is not None else 8
        for b in batch_sizes:
            bmax = max(bmax, _bucket(int(b), lo=8))
        user_buckets = []
        b = 8
        while b <= bmax:
            user_buckets.append(b)
            b *= 2
        gmax = self._item_batcher.max_batch \
            if self._item_batcher is not None else 8
        item_buckets = []
        g = 8
        while g <= gmax:
            item_buckets.append(g)
            g *= 2
        plan: List[Tuple] = []
        for kb in ks:
            plan.append(("user", kb))
            for bb in user_buckets:
                plan.append(("users", kb, bb))
            for gg in item_buckets:
                plan.append(("items", kb, self.ITEM_QUERY_BUCKET, gg))
        return plan

    def precompile(self, plan: List[Tuple],
                   tables: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, int]:
        """AOT-compile every ladder program (``lower().compile()``, no
        device execution, a small thread pool hides XLA's per-program
        latency) into the executable cache the dispatch paths consult
        first, keyed on the signature of ``tables`` — the live store's
        by default; a growing patch passes the grown tables it has not
        published yet. A program the compiler refuses raises with the
        compiler's message — the deploy fails instead of serving a
        ladder with a hole in it. ``fallback`` counts only entries with
        no AOT lowering at all (``PIO_SERVE_AOT=0``, or a subclass lane
        without :meth:`_aot_lower_entry`); :meth:`warmup` compiles
        those by executing them once — still at deploy time, never on
        a query."""
        if not _serve_aot_enabled():
            return {"compiled": 0, "fallback": len(plan)}
        import jax
        import jax.numpy as jnp

        if tables is None:
            with self._store_lock:
                tables = self._store_tables_locked()
        sig = self._store_sig(tables)
        X, Y, sb = tables["X"], tables["Y"], tables["seen_bits"]
        valid = tables["valid"]
        Yn = self._normalized_items() \
            if any(e[0] == "items" for e in plan) else None
        sharded = self._shard is not None
        user_pre = (X, Y, valid, sb) if sharded else (X, Y, sb)
        items_pre = (Yn, valid) if sharded else (Yn,)

        def build(entry: Tuple):
            # the SAME builders the dispatch paths use (XLA chain,
            # fused kernel, or sharded per self._kernel/_shard), so AOT
            # executables and jit fallbacks can never encode different
            # programs
            kind = entry[0]
            if kind == "user":
                fn = self._user_program(entry[1])
                return entry, lower_compile(
                    fn, *user_pre,
                    jax.ShapeDtypeStruct((), jnp.int32))
            if kind == "users":
                _, kb, bb = entry
                fn = self._batch_program(kb, bb)
                return entry, lower_compile(
                    fn, *user_pre,
                    jax.ShapeDtypeStruct((bb,), jnp.int32))
            if kind == "items":
                _, kb, B, gg = entry
                fn = self._items_program(kb, B, gg)
                return entry, lower_compile(
                    fn, *items_pre,
                    jax.ShapeDtypeStruct((gg, B), jnp.int32),
                    jax.ShapeDtypeStruct((gg, B), jnp.float32))
            # subclass lanes (e.g. the two-stage ("two", ...) entries)
            # lower through the overridable hook
            return entry, self._aot_lower_entry(entry, tables)

        compiled = fallback = 0
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=min(4, max(1, len(plan))),
                                thread_name_prefix="pio-serve-aot") \
                as pool:
            # each worker call carries this thread's trace context, so
            # its `ladder.lower` span lands in the deploy's trace
            runs = [(_tracing.carrying_context(build), e) for e in plan]
            for entry, prog in pool.map(lambda r: r[0](r[1]), runs):
                if prog is None:
                    fallback += 1
                else:
                    compiled += 1
                    self._aot_programs.put((sig, entry), prog)
        return {"compiled": compiled, "fallback": fallback}

    def _aot_lower_entry(self, entry: Tuple, tables: Dict[str, Any]):
        """AOT-lower one ladder entry of a kind this class does not
        know, against ``tables`` — the subclass extension point through
        which new serving lanes (the two-stage ``("two", ...)``
        entries) join the SAME precompile pool, cache and coverage
        accounting. None means "no AOT" and the entry stays on its jit
        fallback, which :meth:`warmup` then compiles via
        :meth:`_warm_entry`."""
        return None

    def _warm_entry(self, entry: Tuple) -> None:
        """Execute one subclass-lane ladder entry so its jit fallback
        compiles at warm-up, never on a live query. Base class: no
        such lanes exist, nothing to warm."""

    def warmup(self, max_k: int = 128, batch_sizes: Tuple[int, ...] = ()) \
            -> Dict[str, int]:
        """Make EVERY ladder program up to ``max_k`` serve-ready at
        deploy time (SURVEY hard part #4: no live query may ever pay an
        XLA compile — asserted by ``tests/test_serving_load.py`` and by
        the benchmark's ``compiles_in_window``): AOT-precompile the full
        :meth:`aot_plan` ladder, execute the handful AOT declined so
        their jit fallbacks compile NOW, then run one sacrificial query
        per lane to pin the runtime dispatch caches. ``batch_sizes``
        extends the uid-bucket ladder for callers with known batch
        shapes (bench/batchpredict)."""
        with _trace_span("ladder.plan"):
            plan = self.aot_plan(max_k=max_k,
                                 batch_sizes=tuple(batch_sizes))
        # one parent span round the whole of compile-or-load: the
        # workers' serialized `ladder.lower` spans are its children, so
        # its self time is what is left of the wall clock (the compile
        # pipeline, executable loads, the stragglers and the
        # sacrificial queries below)
        with _trace_span("ladder.compile"):
            return self._compile_and_warm(plan)

    def _compile_and_warm(self, plan: List[Tuple]) -> Dict[str, int]:
        stats = self.precompile(plan)
        with self._store_lock:
            self._ladder_plan = plan
            missing = [e for e in plan if self._aot_get_locked(e) is None]
            # ladder coverage for the /stats.json device block: how
            # many programs the plan holds, how many AOT-compiled, how
            # many fell back and were warmed by execution instead
            self._ladder = {"planned": len(plan),
                            "compiled": stats["compiled"],
                            "fallback": stats["fallback"],
                            "warmed": len(missing)}
        for entry in missing:  # jit-compile the stragglers by running
            if entry[0] == "user":
                self._user_topk_direct(0, entry[1])
            elif entry[0] == "users":
                _, kb, bb = entry
                self.users_topk(np.zeros(bb, dtype=np.int64), kb)
            elif entry[0] == "items":
                _, kb, B, gg = entry
                self._items_topk_batched(
                    np.zeros((gg, B), dtype=np.int32),
                    np.zeros((gg, B), dtype=np.float32), kb)
            else:
                self._warm_entry(entry)
        kmin = min(16, self.n_items)
        self.user_topk(0, kmin)
        self.users_topk(np.zeros(8, dtype=np.int64), kmin)
        self.items_topk([0], kmin)
        return stats

    def close(self) -> None:
        """Release the micro-batch dispatcher (drains pending queries,
        idempotent). Dropping the last reference also stops it within
        its wait timeout."""
        if self._dispatcher is not None:
            self._dispatcher.close()

    def stats(self) -> Dict[str, Dict[str, int]]:
        """Micro-batcher counters (consistent snapshots; also exported
        process-wide as ``pio_microbatch_*`` registry metrics)."""
        out: Dict[str, Dict[str, int]] = {}
        if self._batcher is not None:
            out["users"] = self._batcher.stats()
        if self._item_batcher is not None:
            out["items"] = self._item_batcher.stats()
        return out

    # -- serving ----------------------------------------------------------

    def _dispatch_entry(self, entry: Tuple, fallback, args_fn, *,
                        batch: int, bucket: int, take=None):
        """One laddered device dispatch: AOT-executable lookup + the
        program call under ``_store_lock`` (the historical lock scope —
        the dispatch enqueues, it does not wait on the device), then,
        with telemetry on, the dispatch→``block_until_ready`` window
        timed OUTSIDE the lock on the monotonic clock, recorded into
        the flight ring with its stage stamps (lock wait, enqueue,
        device) and emitted as a ``device.execute`` child span; the
        lock wait, the program call and the block are also live
        profiler annotations (``dispatch.lock`` / ``.enqueue`` /
        ``.wait``). Telemetry off (``PIO_DEVICE_TELEMETRY=0``) is the
        killed-lane fast path: exactly the pre-telemetry dispatch, no
        clock reads. Returns the raw packed device output. ``take``
        (a lane whose program also returns new store tables, as the
        session lane's does its caches) is called with the program's
        outputs while the lock is still held, publishes the tables and
        returns the packed output."""
        if not _dtel.enabled():
            with self._store_lock:
                prog, aot = self._ladder_program_locked(entry, fallback)
                out = prog(*args_fn())
                if take is not None:
                    out = take(out)
            _metrics.AOT_CACHE_REQUESTS.inc(result=aot)
            return out
        tl = time.monotonic()
        with _tracing.annotation("dispatch.lock"):
            self._store_lock.acquire()
        try:
            t_locked = time.monotonic()
            prog, aot = self._ladder_program_locked(entry, fallback)
            args = args_fn()
            t0e = _tracing.span_now()
            with _tracing.annotation("dispatch.enqueue"):
                t0m = time.monotonic()
                out = prog(*args)
                if take is not None:
                    out = take(out)
                t1m = time.monotonic()
        finally:
            self._store_lock.release()
        _metrics.AOT_CACHE_REQUESTS.inc(result=aot)
        # block OUTSIDE the lock (a fold-in patch must not wait on a
        # query's device time); the d2h fetch the caller then pays via
        # np.asarray finds the result already materialized
        with _tracing.annotation("dispatch.wait"):
            try:
                out.block_until_ready()
            except AttributeError:  # non-jax output (host fallback paths)
                pass
            t2m = time.monotonic()
        rec = _dtel.record_dispatch(
            lane=entry[0], kernel=self._kernel, precision=self._mode,
            interpret=self._interpret if self._kernel == "fused" else None,
            aot=aot, k_bucket=int(entry[1]), batch=batch, bucket=bucket,
            host_us=(t2m - t0m) * 1e6, device_us=(t2m - t1m) * 1e6,
            lock_wait_us=(t_locked - tl) * 1e6,
            locked_us=(t0m - t_locked) * 1e6, called=t0m, ready=t2m,
            called_ts=t0e)
        with _dtel.stage("bookUs", "batch.book", done=True):
            ctx = _dtel.current_dispatch_context() or {}
            _tracing.record_completed_span(
                "device.execute", start=t0e, end=t0e + (t2m - t0m),
                attributes=None if rec is None else dict(rec),
                parent=ctx.get("traceParent"))
        return out

    def _ladder_program_locked(self, entry: Tuple, fallback):
        """(program, ``hit`` | ``miss_jit``) for one ladder entry: the
        AOT executable compiled for the live store, else the jit
        fallback. Caller holds ``_store_lock``."""
        prog = self._aot_get_locked(entry)
        if prog is not None:
            self._aot_hits += 1
            return prog, "hit"
        self._aot_misses += 1
        return fallback(), "miss_jit"

    def _fetch(self, out, kb: int, cut=np.s_[:]):
        """Device output -> host (item ids, scores) cut to the rows and
        columns asked for: the d2h copy, the unpack and the position ->
        id map, as the ``dispatch.fetch`` stage of the record just
        written."""
        with _dtel.stage("fetchUs", "dispatch.fetch", done=True):
            host = np.asarray(out)
            idx, scores = _unpack(host, kb)
            _dtel.note_select_rounds(_packed_rounds(host, kb))
            return self._positions_to_items(idx[cut]), scores[cut]

    def user_topk(self, uid: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(item indices, scores) for one user, descending; seen items
        are masked on device. With micro-batching on (the default),
        concurrent callers share ONE device dispatch; a lone caller
        still pays exactly one blocking round trip."""
        # the trace span covers submit→result, i.e. the full device
        # round trip the query waits on (micro-batched or direct)
        with _trace_span("device.user_topk",
                         attributes={"k": int(k)}) as sp:
            if self._batcher is not None:
                return self._batcher.submit(int(uid), int(k), span=sp)
            return self._user_topk_direct(uid, k)

    def _user_topk_direct(self, uid: int,
                          k: int) -> Tuple[np.ndarray, np.ndarray]:
        """The unbatched per-call program: k rounds up to the compiled
        bucket and the result is clipped, so arbitrary nums reuse
        programs; the uid rides inside the async jit dispatch."""
        kb = min(_bucket(k), self.n_items)
        out = self._dispatch_entry(
            ("user", kb), lambda: self._user_program(kb),
            lambda: self._user_args(np.int32(uid)),
            batch=1, bucket=1)
        idx, scores = self._fetch(out, kb, np.s_[:k])
        valid = np.isfinite(scores)
        return idx[valid], scores[valid]

    def _user_args(self, uids) -> Tuple:
        """The user-lane program's argument tuple for the live store
        (sharded programs additionally take the validity row)."""
        if self._shard is not None:
            return (self._X, self._Y, self._valid, self._seen_bits, uids)
        return (self._X, self._Y, self._seen_bits, uids)

    def users_topk(self, uids, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Batched top-k for a vector of user indices: ONE device dispatch
        and ONE packed fetch for the whole batch (P2LAlgorithm.scala:66-68
        batch-predict-as-one-job semantics). The batch is padded to a
        power-of-two uid bucket so arbitrary sizes reuse a handful of
        compiled programs.

        Returns ``(idx [B, kb] int32, scores [B, kb] float32)`` rows
        descending; rows may contain -inf scores past the valid
        candidates (callers filter per row, as `user_topk` does)."""
        uids = np.asarray(uids, dtype=np.int32)
        n = len(uids)
        with _trace_span("device.users_topk",
                         attributes={"batch": int(n), "k": int(k)}):
            with _dtel.stage("formUs", "batch.form"):
                bb = _bucket(max(n, 1), lo=8)
                padded = np.zeros(bb, dtype=np.int32)
                padded[:n] = uids
                kb = min(_bucket(k), self.n_items)
            out = self._dispatch_entry(
                ("users", kb, bb), lambda: self._batch_program(kb, bb),
                lambda: self._user_args(padded),
                batch=n, bucket=bb)
            return self._fetch(out, kb, np.s_[:n, :k])

    def items_topk(self, idxs, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Item-similarity top-k for a list of query item indices. With
        micro-batching on, concurrent callers share one vmapped
        dispatch (same discipline as ``user_topk``)."""
        with _trace_span("device.items_topk",
                         attributes={"items": len(idxs),
                                     "k": int(k)}) as sp:
            if self._item_batcher is not None:
                return self._item_batcher.submit(
                    tuple(int(i) for i in idxs), int(k), span=sp)
            return self._items_topk_direct(idxs, k)

    def _items_topk_direct(self, idxs,
                           k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Unbatched path: a single-row group through the same vmapped
        program family the batcher uses (one padding implementation,
        one program cache)."""
        B = self.ITEM_QUERY_BUCKET
        while B < len(idxs):
            B *= 2
        pad_idx = np.zeros((1, B), dtype=np.int32)
        pad_mask = np.zeros((1, B), dtype=np.float32)
        pad_idx[0, :len(idxs)] = np.asarray(idxs, dtype=np.int32)
        pad_mask[0, :len(idxs)] = 1.0
        idx, scores = self._items_topk_batched(pad_idx, pad_mask, k)
        idx, scores = idx[0, :k], scores[0, :k]
        valid = np.isfinite(scores)
        return idx[valid], scores[valid]

    def _items_topk_batched(self, idxs: np.ndarray, masks: np.ndarray,
                            k: int) -> Tuple[np.ndarray, np.ndarray]:
        """vmap of the item-similarity program over a [G, B] query
        bucket: G concurrent item queries, one dispatch, one fetch."""
        G, B = idxs.shape
        kb = min(_bucket(k), self.n_items)
        # out-of-range query item ids DROP from the query (mask 0):
        # on the single-store path jnp.take's NaN fill used to poison
        # the whole summed query row (one bad id emptied the result),
        # and on a density-sharded store the inv take would fault
        in_range = (idxs >= 0) & (idxs < self.n_items)
        if not in_range.all():
            masks = masks * in_range.astype(masks.dtype)
            idxs = np.where(in_range, idxs, 0).astype(idxs.dtype)
        # density-sharded stores live in position space: translate the
        # query item ids in, the winners back out (host-side, tiny)
        idxs = self._items_to_positions(idxs)
        # the [G, B] bucket is already padded — the REAL group size is
        # the dispatcher's, carried in the dispatch context (G itself
        # for direct single-row calls)
        ctx = _dtel.current_dispatch_context() or {}
        out = self._dispatch_entry(
            ("items", kb, B, G), lambda: self._items_program(kb, B, G),
            lambda: self._items_args(idxs, masks),
            batch=int(ctx.get("group") or G), bucket=G)
        return self._fetch(out, kb)

    def _items_args(self, idxs, masks) -> Tuple:
        if self._shard is not None:
            return (self._normalized_items(), self._valid, idxs, masks)
        return (self._normalized_items(), idxs, masks)

    # -- device-plane accounting (HBM + AOT ladder) ------------------------

    def memory_report(self) -> Dict[str, Any]:
        """HBM bytes this store pins, by component and dtype — factor
        tables (int8 stores split data vs per-row scales), the seen
        bitmap as it lies on the device (``seen`` rows are
        :func:`seen_row_words` wide: the lane-tile padding is resident
        and counted), and the lazily built normalized item matrix.
        Reads the LIVE references under ``_store_lock``, so the answer
        tracks fold-in growth and int8 requant as they happen."""
        from predictionio_tpu.ops.quantize import is_quantized

        with self._store_lock:
            X, Y, Yn = self._X, self._Y, self._Yn
            sb = self._seen_bits
            mode, kernel = self._mode, self._kernel
            shard, layout = self._shard, self._layout

        def comp(f) -> Optional[Dict[str, Any]]:
            if f is None:
                return None
            if is_quantized(f):
                return {"bytes": int(f.data.nbytes),
                        "scaleBytes": int(f.scale.nbytes),
                        "dtype": str(f.data.dtype),
                        "scaleDtype": str(f.scale.dtype),
                        "shape": [int(d) for d in f.data.shape]}
            return {"bytes": int(f.nbytes), "scaleBytes": 0,
                    "dtype": str(f.dtype),
                    "shape": [int(d) for d in f.shape]}

        components: Dict[str, Any] = {
            "userFactors": comp(X),
            "itemFactors": comp(Y),
            "normalizedItems": comp(Yn),
            "seen": {"bytes": int(sb.nbytes), "dtype": str(sb.dtype),
                     "shape": [int(d) for d in sb.shape]}
            if self._mask_seen else None,
        }
        total = sum(c["bytes"] + c.get("scaleBytes", 0)
                    for c in components.values() if c is not None)
        report = {
            "precision": mode,
            "kernel": kernel,
            "nUsers": self.n_users,
            "nItems": self.n_items,
            "userCapacity": int(X.shape[0]),
            "components": components,
            "totalBytes": int(total),
            "placement": _placement(Y.data if is_quantized(Y) else Y),
        }
        if shard is not None:
            # per-shard breakdown (ISSUE 15 satellite): the aggregate
            # above hides a hot shard — the exact failure density-aware
            # sharding targets, so the report names each shard's HBM
            # slice, item count, and interaction mass
            _, axis, n_sh = shard

            def per_shard(f) -> int:
                if f is None:
                    return 0
                if is_quantized(f):
                    return (int(f.data.nbytes) + int(f.scale.nbytes)) \
                        // n_sh
                return int(f.nbytes) // n_sh

            items = layout.items_per_shard if layout is not None \
                else None
            mass = layout.counts_per_shard if layout is not None \
                else None
            cap = int(Y.shape[0]) // n_sh
            shards_out = []
            for s in range(n_sh):
                ent = {
                    "shard": s,
                    "factorBytes": int(per_shard(X) + per_shard(Y)
                                       + per_shard(Yn)),
                    "items": int(items[s]) if items is not None
                    else max(0, min(self.n_items - s * cap, cap)),
                }
                if mass is not None:
                    ent["interactions"] = int(mass[s])
                shards_out.append(ent)
            report["shardAxis"] = axis
            report["nShards"] = n_sh
            report["shards"] = shards_out
            if layout is not None:
                report["shardBalance"] = layout.balance_report()
        return report

    def ladder_report(self) -> Dict[str, Any]:
        """AOT bucket-ladder coverage and footprint: the last warmup's
        planned/compiled/fallback/warmed counts, live hit/miss-to-jit
        lookup totals, cache entry/eviction counts, and what the
        compiled programs themselves need on the device
        (:meth:`AOTCache.memory_report`: temporaries and code, not the
        store they take as arguments)."""
        with self._store_lock:
            hits, misses = self._aot_hits, self._aot_misses
            coverage = dict(self._ladder)
        return {
            "coverage": coverage,
            "requests": {"hit": hits, "missJit": misses},
            "cache": self._aot_programs.stats(),
            "memory": self._aot_programs.memory_report(),
        }

    # -- live store patching (online fold-in) ------------------------------

    @property
    def item_factors(self):
        """The item-side factor store as served (possibly bf16, possibly
        sharded) — what the fold-in solve must hold fixed. An int8
        store hands out a DEQUANTIZED fp32 view — the fold-in solve is
        the training half-step and has no int8 lane, exactly as a bf16
        store casts to the training lane. The view is built per access,
        NOT cached: pinning a fp32 copy next to the int8 store would
        cost more HBM than serving fp32 outright (the catalog-capacity
        win is the whole point); fold-in reads this once per fold
        cadence, so the dequant is a transient elementwise program.
        The same tradeoff covers the density layout's id-order gather
        below — caching it would pin a second full item table in HBM
        to save one transient take per fold."""
        from predictionio_tpu.ops.quantize import (
            dequantize_rows,
            is_quantized,
        )

        with self._store_lock:
            Y = self._Y
            inv = self._inv_np
        Yf = dequantize_rows(Y) if is_quantized(Y) else Y
        if inv is not None:
            # density-sharded store: hand back ITEM-id order (the
            # fold-in solve indexes by item id, not store position)
            import jax.numpy as jnp

            Yf = jnp.take(Yf, jnp.asarray(inv), axis=0)
        return Yf

    @property
    def user_capacity(self) -> int:
        """Allocated user rows (>= ``n_users``; grows by bucket ladder)."""
        return int(self._X.shape[0])

    @property
    def shard_count(self) -> int:
        """Mesh shards the factor store spans (1 = single store)."""
        return 1 if self._shard is None else int(self._shard[2])

    @property
    def item_layout(self):
        """The density-aware :class:`~predictionio_tpu.parallel.
        als_sharding.ItemShardLayout` serving this store, or None."""
        return self._layout

    @property
    def growable(self) -> bool:
        """Whether :meth:`patch_users` can grow the user store. Always
        true since ISSUE 15: mesh-sharded stores grow by RESHARDING
        (a padded re-placement over the same mesh) instead of refusing,
        so fold-in runs against sharded deployments too."""
        return True

    def patch_users(self, uids, factors,
                    seen_items: Optional[Dict[int, np.ndarray]] = None
                    ) -> None:
        """Scatter freshly solved user rows into the LIVE factor store —
        the online fold-in write path (no ``/reload``, no retrain).

        ``uids`` may index PAST the current capacity: the store grows
        along the power-of-two bucket ladder first
        (:meth:`_reserve_users`; new rows zero until patched), so a
        stream of brand-new users costs O(log growth) reallocations.
        ``factors`` rows are cast to the store dtype
        (fp32, the bf16 serving policy, or — for an int8 store —
        re-quantized with freshly recomputed per-row absmax scales, so
        a patched row quantizes exactly as it would have at load).
        ``seen_items`` replaces the
        touched users' on-device seen-masking rows with their full item
        sets (ignored when the server was built without seen masking).

        Atomicity contract: every store reference is swapped under the
        same ``_store_lock`` each device dispatch snapshots under, so a
        concurrent query sees either the whole old store or the whole
        new one — never a torn mix. On accelerators the scatter donates
        the old buffer (in-place HBM update, the PR-5 donation
        discipline). Writers run one at a time (``_write_lock``).
        """
        uids = np.asarray(uids, dtype=np.int64)
        factors = np.asarray(factors, dtype=np.float32)
        if factors.ndim != 2 or len(uids) != factors.shape[0]:
            raise ValueError(
                f"patch_users: {len(uids)} uids vs factors "
                f"{factors.shape}")
        if not len(uids):
            return
        if uids.min() < 0:
            raise ValueError("patch_users: negative user index")
        seen_items = self._translate_seen(seen_items) if seen_items \
            else seen_items
        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
            quantize_rows_int8_np,
        )

        needed = int(uids.max()) + 1
        # everything that can FAIL comes before the first donation (the
        # seen rows are host loops), and each donating call is paired
        # with its publish in the same statement — an exception can
        # therefore never strand self._X (or the bitmap) pointing at an
        # already-donated, deleted buffer. Dispatch paths snapshot all
        # references under _store_lock, so the intermediate states are
        # invisible to queries.
        seen_prep = self._prep_seen(seen_items) \
            if self._mask_seen and seen_items else None
        with self._write_lock:
            self._reserve_users(needed)
            with self._store_lock:
                if seen_prep is not None:
                    self._seen_bits = _scatter_rows(self._seen_bits,
                                                    *seen_prep)
                X = self._X
                if is_quantized(X):
                    # fresh rows re-quantize with RECOMPUTED per-row
                    # scales (symmetric absmax, the load-time rule) so
                    # a patched row is bit-identical to
                    # quantize-from-scratch of the updated matrix;
                    # data+scale scatter in one donating dispatch so
                    # the pair can never tear
                    q = quantize_rows_int8_np(factors)
                    self._X = QuantFactors(*_scatter_quant_rows(
                        X.data, X.scale, uids, q.data, q.scale))
                else:
                    self._X = _scatter_rows(X, uids, factors)
                self.n_users = max(self.n_users, needed)

    def _reserve_users(self, needed: int) -> None:
        """Grow the user-side tables to hold ``needed`` rows, in the
        order that keeps every query on a compiled program: build the
        grown tables beside the live ones (pads COPY, so the live store
        keeps serving and stays whole if anything here raises), compile
        the warmed ladder for the grown signature, and only then swap
        the references in under ``_store_lock``. Queries therefore see
        the old store with its ladder or the grown store with its
        ladder, and never compile; the price is paid by the writer — a
        batch that grows the store lands one ladder compile later
        (once per doubling). A store that was never warmed has no plan
        and grows without compiling, as does one with ``PIO_SERVE_AOT``
        off; their queries take the jit programs as before. Caller
        holds ``_write_lock``, so no other writer touches the tables
        between the snapshot and the swap."""
        with self._store_lock:
            old = self._store_tables_locked()
            plan = list(self._ladder_plan)
        cap = int(old["X"].shape[0])
        if needed <= cap:
            return
        new_cap = _bucket(needed, lo=max(cap, 16))
        if self._shard is not None:
            # growth reshards: capacity rounds to the shard divisor
            n_sh = int(self._shard[2])
            new_cap = -(-new_cap // n_sh) * n_sh
        grown = self._grow_user_tables(old, new_cap)
        if plan:
            self.precompile(plan, grown)
        with self._store_lock:
            self._publish_user_tables_locked(grown)
        # the old shape's executables each pin device code
        old_sig = self._store_sig(old)
        self._aot_programs.discard(lambda key: key[0] == old_sig)

    def _grow_user_tables(self, tables: Dict[str, Any],
                          new_cap: int) -> Dict[str, Any]:
        """``tables`` with the user-side ones (factors, seen bitmap)
        padded to ``new_cap`` rows: zero factors (int8: zero data,
        scale 1) and all-zero "nothing seen" bitmap rows, in the
        placement the compiled programs expect. The bitmap grows WITH
        the factors even when no seen set arrives: a uid whose seen row
        does not exist would clamp into the last user's row at gather
        time and be masked with another user's history."""
        import jax.numpy as jnp

        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
        )

        X = tables["X"]
        pad = new_cap - int(X.shape[0])
        if self._shard is not None:
            # a pad program pinned to the store's own row sharding
            X = self._grow_rows_sharded(X, new_cap)
        elif is_quantized(X):
            X = QuantFactors(
                jnp.pad(X.data, ((0, pad), (0, 0))),
                jnp.pad(X.scale, ((0, pad),), constant_values=1))
        else:
            X = jnp.pad(X, ((0, pad), (0, 0)))
        grown = dict(tables, X=X)
        if self._mask_seen:
            grown["seen_bits"] = self._replicate_like_factors(
                jnp.pad(tables["seen_bits"], ((0, pad), (0, 0))))
        return grown

    def _publish_user_tables_locked(self, tables: Dict[str, Any]) -> None:
        """Swap in the user-side tables of ``tables``. The bitmap lands
        first, as in every patch. Caller holds ``_store_lock``."""
        self._seen_bits = tables["seen_bits"]
        self._X = tables["X"]

    def _grow_rows_sharded(self, X, new_cap: int):
        """Grow a mesh-sharded user store to ``new_cap`` rows by
        RESHARDING: a pad program whose output is pinned to the store's
        row sharding, so the new buffers land distributed and the old
        rows copy over ICI-local lanes. Returns the grown store (the
        caller publishes it under ``_store_lock``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from predictionio_tpu.ops.quantize import (
            QuantFactors,
            is_quantized,
        )

        mesh, axis, _ = self._shard
        row = NamedSharding(mesh, P(axis, None))
        col = NamedSharding(mesh, P(axis))

        def grow(a, sharding, fill):
            pad = new_cap - int(a.shape[0])
            fn = jax.jit(
                lambda x: jnp.pad(
                    x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill),
                out_shardings=sharding)
            return fn(a)

        if is_quantized(X):
            return QuantFactors(grow(X.data, row, 0),
                                grow(X.scale, col, 1.0))
        return grow(X, row, 0.0)

    def _prep_seen(self, seen_items: Dict[int, np.ndarray]):
        """The touched users' replacement bitmap rows (a row's width is
        fixed by the item store — :func:`seen_bitmap` gives these rows
        the store's own :func:`seen_row_words` — so a user's history
        growing never reshapes the store) — the fallible half of a seen
        patch; the caller feeds it to the donating
        :func:`_scatter_rows`."""
        sids = np.fromiter(seen_items.keys(), dtype=np.int64,
                           count=len(seen_items))
        new_rows = seen_bitmap(
            {i: seen_items[int(uid)] for i, uid in enumerate(sids)},
            len(sids), int(self._Y.shape[0]))
        return sids, new_rows

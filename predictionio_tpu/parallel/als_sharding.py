"""Sharded ALS training over a device mesh.

MLlib ALS distributes by block-partitioning both factor matrices and
shuffling ratings between executors every half-step (invoked from
``examples/.../ALSAlgorithm.scala:64-71``). The TPU-native replacement
(ALX layout): shard every bucket's padded rating table row-wise over
the mesh's ``data`` axis so each device solves its slice of users (then items);
factor matrices are kept replicated and rebuilt each half-step — XLA's
sharding propagation turns the per-slice solves + gathers into
all-gather/psum collectives over ICI, replacing the Spark shuffle.

Memory note: replicated factors cost ``(N+M) * R * 4`` bytes per device —
fine through MovieLens-20M (~165 MB at R=128). Past that,
``factor_spec=P("model", None)`` shards the factor matrices over the
mesh's ``model`` axis (per-device factor memory drops by the model-axis size;
one transient all-gather per half-step over ICI — the ALX layout).
"""

from __future__ import annotations

import dataclasses
import heapq
import logging
import os
from typing import Any, Dict, Optional, Tuple

import numpy as np

logger = logging.getLogger("predictionio_tpu.als_sharding")

from predictionio_tpu.ops.als import (
    ALSParams,
    BucketedRatings,
    RatingsBucket,
    _als_iterations_bucketed_impl,
    _als_precision_mode,
    _maybe_checkpointer,
    _objective_pack,
    _objective_statics,
    _resolve_spd_solver,
    _train_telemetry_enabled,
    checkpoint_layout_bucketed,
    init_policy_factors,
    solve_span_attributes,
)


# ---------------------------------------------------------------------------
# Density-aware item sharding (the ALX layout step the live plane uses)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ItemShardLayout:
    """How the item axis of a mesh-sharded factor store is laid out.

    ``perm[pos] -> item id`` (or -1 for an empty pad slot) over
    ``n_shards * cap`` contiguous positions — shard ``s`` owns positions
    ``[s*cap, (s+1)*cap)``; ``inv[item] -> pos`` is its inverse. The
    layout is part of the MODEL artifact: serving permutes the item
    factor rows into it, fold-in reads the item store back through it,
    and top-k results translate back to item ids on host — so every
    consumer sees one consistent placement (the contiguous-span
    alternative hot-spots the power-law head onto shard 0)."""

    perm: np.ndarray            # int64 [n_shards * cap], -1 = pad slot
    inv: np.ndarray             # int64 [n_items], item id -> position
    n_shards: int
    n_items: int
    counts_per_shard: np.ndarray  # int64 [n_shards] interaction mass

    @property
    def n_positions(self) -> int:
        return int(self.perm.shape[0])

    @property
    def cap(self) -> int:
        return self.n_positions // self.n_shards

    @property
    def items_per_shard(self) -> np.ndarray:
        """Real items each shard holds (pad slots excluded)."""
        return (self.perm.reshape(self.n_shards, self.cap)
                >= 0).sum(axis=1)

    def valid_mask(self) -> np.ndarray:
        """float32 [n_positions]: 1.0 where the position holds a real
        item — the on-device validity row the sharded top-k masks by
        (replaces the contiguous layout's ``index < n_items`` test)."""
        return (self.perm >= 0).astype(np.float32)

    def balance_report(self) -> Dict[str, Any]:
        """Interaction-mass balance across shards, with the contiguous
        baseline's imbalance alongside — the artifact line that shows
        what the bin-pack bought on power-law data."""
        c = self.counts_per_shard.astype(np.float64)
        mean = float(c.mean()) if len(c) else 0.0
        return {
            "nShards": int(self.n_shards),
            "itemsPerShard": [int(v) for v in self.items_per_shard],
            "interactionsPerShard": [int(v) for v in
                                     self.counts_per_shard],
            "maxOverMeanInteractions": round(
                float(c.max()) / mean, 4) if mean > 0 else None,
        }

    def to_json(self) -> Dict[str, Any]:
        return {"perm": self.perm.tolist(), "nShards": int(self.n_shards),
                "nItems": int(self.n_items),
                "countsPerShard": self.counts_per_shard.tolist()}

    @classmethod
    def from_json(cls, blob: Dict[str, Any]) -> "ItemShardLayout":
        perm = np.asarray(blob["perm"], dtype=np.int64)
        n_items = int(blob["nItems"])
        inv = np.full(n_items, -1, dtype=np.int64)
        real = perm >= 0
        inv[perm[real]] = np.flatnonzero(real)
        return cls(perm, inv, int(blob["nShards"]), n_items,
                   np.asarray(blob["countsPerShard"], dtype=np.int64))


def _layout_from_assignment(shards, counts: np.ndarray, n_shards: int,
                            cap: int) -> ItemShardLayout:
    n_items = int(len(counts))
    perm = np.full(n_shards * cap, -1, dtype=np.int64)
    mass = np.zeros(n_shards, dtype=np.int64)
    for s, items in enumerate(shards):
        items = np.sort(np.asarray(items, dtype=np.int64))
        perm[s * cap:s * cap + len(items)] = items
        mass[s] = int(counts[items].sum()) if len(items) else 0
    inv = np.full(n_items, -1, dtype=np.int64)
    real = perm >= 0
    inv[perm[real]] = np.flatnonzero(real)
    return ItemShardLayout(perm, inv, n_shards, n_items, mass)


def contiguous_item_layout(n_items: int, n_shards: int,
                           counts: Optional[np.ndarray] = None,
                           cap_multiple: int = 8) -> ItemShardLayout:
    """The span layout (items ``[s*cap, (s+1)*cap)`` on shard ``s``) —
    what density-aware sharding replaces, kept for stores without
    interaction counts and as the balance baseline."""
    n_shards = max(1, int(n_shards))
    cap = -(-max(int(n_items), 1) // n_shards)
    cap = -(-cap // cap_multiple) * cap_multiple
    if counts is None:
        counts = np.zeros(n_items, dtype=np.int64)
    ids = np.arange(n_items, dtype=np.int64)
    shards = [ids[s * cap:(s + 1) * cap] for s in range(n_shards)]
    return _layout_from_assignment(shards, np.asarray(counts), n_shards,
                                   cap)


def density_aware_item_layout(counts, n_shards: int,
                              cap_multiple: int = 8) -> ItemShardLayout:
    """Assign items to shards by interaction count: greedy bin-pack
    (heaviest item first onto the lightest shard with free capacity),
    so the power-law head spreads instead of hot-spotting shard 0 —
    the ALX density-aware placement. Capacity-bounded: every shard
    holds at most ``cap`` items, so the factor table still shards
    evenly over the mesh axis; within a shard items sit in ascending
    id order (deterministic layout for a given count vector)."""
    counts = np.asarray(counts, dtype=np.int64)
    n_items = int(counts.shape[0])
    n_shards = max(1, int(n_shards))
    cap = -(-max(n_items, 1) // n_shards)
    cap = -(-cap // cap_multiple) * cap_multiple
    # heaviest first; ties broken by item id for determinism
    order = np.lexsort((np.arange(n_items), -counts))
    heap = [(0, s) for s in range(n_shards)]  # (mass, shard)
    heapq.heapify(heap)
    shards = [[] for _ in range(n_shards)]
    for item in order:
        while True:
            mass, s = heapq.heappop(heap)
            if len(shards[s]) < cap:
                break
            # full shard: leaves the heap for good (total capacity
            # >= n_items, so the pop can never empty the heap early)
        shards[s].append(int(item))
        heapq.heappush(heap, (mass + int(counts[item]), s))
    return _layout_from_assignment(shards, counts, n_shards, cap)


def _multihost_checkpointer(layout, params, solver, precision, dtype,
                            multi_host: bool):
    """The crash-safe checkpointer for a sharded trainer, or None.
    Multi-host runs keep the single-scan path (a per-chunk DCN gather
    + host-0-only writes is ROADMAP item-2 territory) — but NEVER
    silently: an operator who passed the crash-safe knobs must know
    they are not protected."""
    if not multi_host:
        return _maybe_checkpointer(layout, params, solver, precision,
                                   dtype)
    if os.environ.get("PIO_CHECKPOINT_DIR", "").strip():
        logger.warning(
            "checkpointing (PIO_CHECKPOINT_DIR) is not supported on "
            "multi-host meshes yet: this training runs as ONE "
            "uninterruptible scan and writes NO checkpoints; --resume "
            "will find nothing from this run")
    return None


def _pad_rows_to(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad the leading dim to n rows with zeros."""
    if arr.shape[0] == n:
        return arr
    pad = np.zeros((n - arr.shape[0],) + arr.shape[1:], dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def train_als_device(user_side, item_side,
                     params: ALSParams, mesh=None, dtype=None):
    """Train and KEEP the factors sharded in HBM — the PAlgorithm flavor
    (PAlgorithm.scala:44-126: the model lives distributed; nothing is
    gathered to host).

    Returns ``(X, Y)`` as jax Arrays — on a 2-D mesh row-sharded over
    the 'model' axis (each device stores 1/model of each factor matrix,
    rows padded to that axis' size), on a 1-D mesh replicated.
    Serve them with :class:`predictionio_tpu.ops.serving.DeviceTopK`,
    passing the true n_users/n_items as the index bounds.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        from predictionio_tpu.parallel.distributed import host_aware_mesh

        import jax

        n = len(jax.devices())
        mesh = host_aware_mesh(model=2 if (n % 2 == 0 and n >= 4) else 1)
    spec = P("model", None) if "model" in mesh.axis_names \
        else P(None, None)
    return train_als_bucketed_sharded(
        user_side, item_side, params, mesh, dtype=dtype,
        factor_spec=spec, gather=False)


def _pad_bucket_rows(b: RatingsBucket, multiple: int,
                     sentinel: int) -> RatingsBucket:
    """Pad a bucket's row count to ``multiple`` with sentinel-id empty
    rows (dropped by the device scatter) so the table shards evenly."""
    B = int(np.asarray(b.cols).shape[0])
    pad = (-B) % multiple
    if pad == 0:
        return b

    def z(a):
        a = np.asarray(a)
        return np.concatenate(
            [a, np.zeros((pad, a.shape[1]), dtype=a.dtype)])
    rid = np.concatenate([np.asarray(b.row_ids),
                          np.full(pad, sentinel, dtype=np.int32)])
    return RatingsBucket(rid, z(b.cols), z(b.weights), z(b.mask))


def train_als_bucketed_sharded(user_side: BucketedRatings,
                               item_side: BucketedRatings,
                               params: ALSParams, mesh, dtype=None,
                               factor_spec=None, gather: bool = True
                               ) -> Tuple:
    """Length-bucketed training over a device mesh.

    Every bucket's table is row-sharded over the mesh's ``data`` axis
    (rows padded to a lane-friendly multiple of the axis size with
    sentinel ids). By default the factor matrices stay replicated, so
    each device's per-bucket solves scatter into its replica and XLA
    merges the disjoint scatters with one psum per half-step — the
    collective analog of MLlib's factor shuffle. ``factor_spec`` (e.g.
    ``P("model", None)``) shards the factor matrices instead (the ALX
    layout's memory step; factor rows pad to the sharded-dim divisor);
    ``gather=False`` returns the factors as device Arrays in that
    (row-padded) placement — the PAlgorithm flavor where the model
    never lands on host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.utils import tracing as _tracing

    ndev = int(mesh.shape.get("data", 1))
    rows_sharded = NamedSharding(mesh, P("data", None))
    ids_sharded = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, factor_spec or P(None, None))
    put = jax.device_put
    multi_host = len({d.process_index for d in mesh.devices.flat}) > 1

    def place_arr(a, sharding, spec):
        """Single-host: plain device_put; multi-host: this host
        contributes its contiguous row block (host-sharded ingest,
        parallel/distributed.py)."""
        if multi_host:
            from predictionio_tpu.parallel import distributed

            start, stop = distributed.process_row_block(a.shape[0])
            return distributed.make_global_array(mesh, spec,
                                                 np.asarray(a)[start:stop])
        return put(jnp.asarray(a), sharding)

    def place(side: BucketedRatings):
        out = []
        for b in side.buckets:
            b = _pad_bucket_rows(b, 8 * ndev, side.n_rows)
            out.append((place_arr(b.row_ids, ids_sharded, P("data")),
                        place_arr(b.cols, rows_sharded, P("data", None)),
                        place_arr(b.weights, rows_sharded,
                                  P("data", None)),
                        place_arr(b.mask, rows_sharded, P("data", None))))
        return tuple(out)

    precision = _als_precision_mode(params)  # resolved per call
    X, Y = init_policy_factors(user_side.n_rows, item_side.n_rows,
                               params.rank, params.seed, dtype, precision)
    # a sharded factor dim must split evenly: pad rows (with ZEROS — a
    # random-init pad row would pollute the first shared Gram term) to
    # the dim-0 axis product; pad rows are never scattered into by a
    # real bucket row and serving masks them via n_users/n_items
    dim0 = (factor_spec or P(None, None))[0]
    names = (dim0,) if isinstance(dim0, str) else tuple(dim0 or ())
    divisor = 1
    for a in names:
        divisor *= int(mesh.shape[a])
    n_u_pad = -(-user_side.n_rows // divisor) * divisor
    n_i_pad = -(-item_side.n_rows // divisor) * divisor
    X = _pad_rows_to(np.asarray(X), n_u_pad)
    Y = _pad_rows_to(np.asarray(Y), n_i_pad)
    if multi_host:
        from predictionio_tpu.parallel import distributed

        spec = factor_spec or P(None, None)
        X = distributed.make_global_array(mesh, spec, X)
        Y = distributed.make_global_array(mesh, spec, Y)
    else:
        X, Y = put(jnp.asarray(X), repl), put(jnp.asarray(Y), repl)
    fn = jax.jit(
        _als_iterations_bucketed_impl,
        static_argnames=("lam", "alpha", "implicit", "num_iterations",
                         "slot_budget", "solver", "precision", "refine"),
        out_shardings=(repl, repl),
        donate_argnums=(0, 1))
    u_t, i_t = place(user_side), place(item_side)
    # resolved per call; the placed tables tell the resolver how many
    # devices the program is partitioned over
    choice = _resolve_spd_solver(params.rank, (X, Y, u_t, i_t))
    kw = dict(lam=float(params.lambda_), alpha=float(params.alpha),
              implicit=bool(params.implicit_prefs),
              slot_budget=None if not params.bucket_slot_budget
              else int(params.bucket_slot_budget),
              solver=choice.name,
              precision=precision, refine=bool(params.solve_refine))

    def run_iters(Xc, Yc, n):
        return fn(Xc, Yc, u_t, i_t, num_iterations=int(n), **kw)

    # crash-safe lane (see _multihost_checkpointer: single-host only)
    ckpt = _multihost_checkpointer(
        checkpoint_layout_bucketed(user_side, item_side), params,
        kw["solver"], precision, dtype, multi_host)
    # the one-device trainer's root and span, so a trace says what this
    # mesh resolved: every padded bucket row is one system a half-step
    # (two with the refinement pass)
    systems = sum(int(t[1].shape[0]) for t in u_t + i_t) \
        * int(params.num_iterations) * (2 if kw["refine"] else 1)
    with _tracing.trace_scope("als.train", slow_exempt=True), \
            _tracing.span("als.iterations", attributes=dict(
                solve_span_attributes(choice, systems),
                devices=int(mesh.devices.size))):
        if ckpt is None:
            X, Y = run_iters(X, Y, int(params.num_iterations))
        else:
            from predictionio_tpu.workflow import checkpoint as _checkpoint
            from predictionio_tpu.workflow import runlog as _runlog

            fdt = X.dtype
            objective = None
            if _train_telemetry_enabled():
                # closure over the PLACED bucket tuples (see _objective_pack:
                # sharded inputs through the same jitted program)
                obj_kw = _objective_statics(params)

                def objective(Xc, Yc):
                    return _objective_pack(Xc, Yc, u_t, **obj_kw)

            # same run-log header as the one-device trainer, with the mesh
            # size the tables were actually sharded over
            with _runlog.run_context_scope(
                    solver=kw["solver"], precision=precision,
                    trainedPairs=user_side.nnz, devices=ndev):
                X, Y = _checkpoint.run_chunked(
                    run_iters, X, Y, int(params.num_iterations), ckpt,
                    to_host=lambda a: np.asarray(a, dtype=np.float32),
                    from_host=lambda a: put(jnp.asarray(a, dtype=fdt), repl),
                    objective=objective)
        jax.block_until_ready((X, Y))
    if not gather:
        # PAlgorithm flavor: factors stay in HBM in their sharded
        # placement (rows padded to the factor divisor, bf16 under the
        # bf16 policy); serve via ops.serving.DeviceTopK with the true
        # n_users/n_items bounds
        return X, Y
    # host factors always land fp32 (see ops.als.train_als_bucketed)
    return (np.asarray(X, dtype=np.float32)[:user_side.n_rows],
            np.asarray(Y, dtype=np.float32)[:item_side.n_rows])


def train_als_auto(user_side, item_side, params: ALSParams, dtype=None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Topology-aware trainer — what the templates call.

    Multi-host runtime (``pio train --num-hosts K``): a global host-aware
    mesh so all hosts train ONE collective program over DCN+ICI.
    Single host, multiple devices: data-parallel over the local mesh.
    One device: the plain jitted path. Numerics are identical across all
    three (same init, same solves; tested on the virtual mesh).
    """
    import jax

    from predictionio_tpu.ops.als import train_als_bucketed

    if jax.process_count() > 1:
        from predictionio_tpu.parallel import distributed

        return train_als_bucketed_sharded(
            user_side, item_side, params, distributed.host_aware_mesh(),
            dtype=dtype)
    from predictionio_tpu.parallel.mesh import data_parallel_mesh

    if len(jax.devices()) > 1:
        return train_als_bucketed_sharded(
            user_side, item_side, params, data_parallel_mesh(),
            dtype=dtype)
    return train_als_bucketed(user_side, item_side, params, dtype=dtype)

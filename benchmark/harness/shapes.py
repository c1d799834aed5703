"""Operations and bytes the algorithm needs, from shapes alone, and the
least time a chip could take for them (the roofline's denominator).

"Needs" means the mathematics, not this implementation: padding slots,
a normal-equation tensor written to HBM and read back, or a second
pass over the item table are the implementation's, and show up as a
low roofline share, which is the point of the number.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Mapping, Sequence, Tuple


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``. An unknown kind is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}; add a "
            "row with its source")
    return table[device_kind]


def least_time(flops: float, bytes_: float, peak: Mapping[str, Any],
               mxu_passes: int = 1) -> Dict[str, Any]:
    """max(compute time, memory time) and which of the two bounds."""
    t_c = flops * mxu_passes / peak["bf16_flops_per_s"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "compute_s": t_c, "memory_s": t_m,
            "bound": "compute" if t_c >= t_m else "memory"}


def als_half_step(nnz: int, n_solved: int, n_fixed: int, rank: int,
                  factor_bytes: int = 4) -> Dict[str, float]:
    """One implicit-ALS half-step: ``n_solved`` rows solved against
    ``n_fixed`` fixed rows through ``nnz`` distinct pairs.

    flops: the shared Gram ``2 * n_fixed * R^2``; per pair a rank-one
    update of A (``2 * R^2``) and of b (``2 * R``); per solved row a
    Cholesky factorisation (``R^3 / 3``) and two triangular solves
    (``2 * R^2``).
    bytes: each pair gathers one fixed row (``R * factor_bytes``) and
    reads its index and weight (8); the fixed table is read once more
    for the Gram; each solved row is written once.
    """
    R = rank
    flops = (2.0 * n_fixed * R * R
             + nnz * (2.0 * R * R + 2.0 * R)
             + n_solved * (R ** 3 / 3.0 + 2.0 * R * R))
    bytes_ = (nnz * (R * factor_bytes + 8.0)
              + n_fixed * R * factor_bytes
              + n_solved * R * factor_bytes)
    return {"flops": flops, "bytes": bytes_,
            "assembly_flops": nnz * 2.0 * R * R,
            "solve_flops": n_solved * R ** 3 / 3.0,
            "gather_bytes": nnz * R * float(factor_bytes)}


def als_iteration(nnz: int, n_users: int, n_items: int, rank: int,
                  factor_bytes: int = 4) -> Dict[str, float]:
    """Both half-steps of one iteration (users, then items)."""
    u = als_half_step(nnz, n_users, n_items, rank, factor_bytes)
    i = als_half_step(nnz, n_items, n_users, rank, factor_bytes)
    return {k: u[k] + i[k] for k in u}


def padded_slots(bucket_shapes: Sequence[Tuple[int, int]]) -> int:
    """Slots the bucketed tables really hold (rows x length summed):
    what the implementation gathers, against ``nnz`` needed."""
    return int(sum(int(b) * int(l) for b, l in bucket_shapes))


def topk_dispatch(batch: int, n_items: int, rank: int, k: int,
                  store_bytes: int = 2, stage2_width: int = 0,
                  candidates: int = 0) -> Dict[str, float]:
    """One score + seen-mask + top-k dispatch for ``batch`` users.

    flops: the score matmul ``2 * batch * n_items * R`` (the selection
    itself is comparisons and is not counted). bytes: the item table
    streamed once, the batch's user rows, one seen-bitmap row per user
    (``n_items / 8``), and the packed result (8 bytes per winner).
    Two-stage adds the gather of ``candidates`` stage-2 rows per user
    and their ``2 * candidates * width`` flops.
    """
    flops = 2.0 * batch * n_items * rank
    bytes_ = (n_items * rank * store_bytes + batch * rank * store_bytes
              + batch * n_items / 8.0 + batch * k * 8.0)
    if stage2_width:
        flops += 2.0 * batch * candidates * stage2_width
        bytes_ += batch * (candidates + 1) * stage2_width * store_bytes
    return {"flops": flops, "bytes": bytes_}

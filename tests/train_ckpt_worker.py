"""Subprocess target for the crash-safe-training chaos suite.

Runs ONE deterministic `train_als_bucketed` job (fixed seed, fixed
synthetic ratings) with checkpointing configured purely through the PIO_* env
vars the parent test sets, mimicking the `pio train` lifecycle: signal
handlers installed (SIGTERM/SIGINT -> graceful drain + clean exit 0)
and a `PIO_FAULTS` slow rule on checkpoint saves is the deterministic
window the parent uses to kill-9 or SIGTERM mid-run. On completion the
final factors land at argv[1] as an .npz so the parent can compare
byte-identity against an uninterrupted in-process run of the SAME
`build_inputs()` problem.
"""

import os
import sys

import numpy as np

N_USERS, N_ITEMS, NNZ = 60, 40, 600
SEED = 11
DEFAULT_ITERS = 8


def build_inputs(num_iterations: int = DEFAULT_ITERS):
    """The deterministic training problem shared by the worker and the
    parent test's in-process reference run."""
    from predictionio_tpu.ops.als import ALSParams, bucket_ratings_pair

    rng = np.random.default_rng(7)
    rows = rng.integers(0, N_USERS, NNZ)
    cols = rng.integers(0, N_ITEMS, NNZ)
    vals = (rng.random(NNZ).astype(np.float32) + 0.5)
    user_side, item_side = bucket_ratings_pair(rows, cols, vals,
                                               N_USERS, N_ITEMS)
    params = ALSParams(rank=8, num_iterations=num_iterations, seed=SEED)
    return user_side, item_side, params


def main(out_path: str) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from predictionio_tpu.ops.als import train_als_bucketed
    from predictionio_tpu.workflow import checkpoint

    checkpoint.install_signal_handlers()
    iters = int(os.environ.get("PIO_TEST_TRAIN_ITERS",
                               str(DEFAULT_ITERS)))
    user_side, item_side, params = build_inputs(iters)
    print("[INFO] worker: training starts", flush=True)
    try:
        X, Y = train_als_bucketed(user_side, item_side, params)
    except checkpoint.TrainingPreempted as e:
        print(f"[INFO] Training interrupted: {e}", flush=True)
        return 0
    np.savez(out_path, X=X, Y=Y)
    print("[INFO] Training completed.", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))

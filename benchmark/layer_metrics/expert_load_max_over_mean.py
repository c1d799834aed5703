"""The busiest expert's (token, expert) pairs over the average
expert's, per step, mean over the last call's steps (the program's
``pio_seq_expert_tokens_per_step`` gauge): 1.0 is a perfectly balanced
router; the grouped matmul's tiles and a later expert-parallel layout
both pay for what is over it."""

from benchmark.harness import seq_metrics as _seq


def read(r):
    w = _seq.work(r)
    load = (w or {}).get("expert_load") or {}
    if not load.get("mean"):
        return None
    return load["max"] / load["mean"]

"""Slate positions a denoising pass of a query row unmasked, mean over
the window (commit passes not counted): 1.0 under the static rule at 4
steps a block of 4; what a trained model under the dynamic rule would
move."""
from benchmark.harness import slate_metrics as _s


def read(r):
    w = _s.window(r)
    if w is None or w["passes_query"] <= w["rounds"]:
        return None
    return w["unmasked"] / float(w["passes_query"] - w["rounds"])

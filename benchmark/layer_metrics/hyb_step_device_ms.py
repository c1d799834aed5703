"""Device time of one dispatch of the hybrid-session lane: the extend
program's module time in the traced slice over its count (attention and
Mamba-2 heads over the group's new tokens in every layer, slot and
cache writes, the dense SwiGLU, scores, mask, top-k)."""
from benchmark.harness import hyb_metrics as _h


def read(r):
    got = _h.sliced(r)
    return None if got is None else 1e3 * got[0]["seconds"] / got[0]["count"]

"""Device time of one dispatch of the mixed-session lane: the extend
program's module time in the traced slice over its count (backbone over
the group's new tokens, cache writes, scores, mask, top-k)."""
from benchmark.harness import swa_metrics as _s


def read(r):
    got = _s.sliced(r)
    return None if got is None else 1e3 * got[0]["seconds"] / got[0]["count"]

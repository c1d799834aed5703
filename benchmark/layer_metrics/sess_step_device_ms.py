"""Device time of one dispatch of the session lane: the extend
program's module time in the traced slice over its count (backbone
over the group's new tokens, cache writes, scores, mask, top-k)."""
from benchmark.harness import sess_metrics as _s


def read(r):
    m = _s.module(r)
    return None if m is None else 1e3 * m["seconds"] / m["count"]

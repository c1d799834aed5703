"""Device time of one dispatch of the long-session lane: the extend
program's module time in the traced slice over its count (both kinds of
mixer over the group's new tokens, slot and cache writes, experts,
scores, mask, top-k)."""
from benchmark.harness import lin_metrics as _l


def read(r):
    got = _l.sliced(r)
    return None if got is None else 1e3 * got[0]["seconds"] / got[0]["count"]

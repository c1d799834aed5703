"""Median ``betweenUs`` over every query delivered in the window: the
sum of hand-back to next claim (the dispatcher's own bookkeeping, the
turn every other lane is owed between two rounds). 0 by construction
in a lane that answers a query in one dispatch."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.life_p50(r, "betweenUs")

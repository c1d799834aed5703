"""Of the traced slice's idle device time, the percent the dispatcher
thread spent asleep with every lane empty (``gapIdleUs``): nothing was
offered. One of four shares that add up to 100
(``harness/dispatch_account.py::idle_shares``)."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.idle_share(r, "no_work")

"""Of the traced slice's idle device time, the percent the dispatcher
thread spent in the rest of its gaps (``gapUs`` less idle and window):
fetch, deliver, book, pick, form, the store lock and the loop itself.
One of four shares that add up to 100
(``harness/dispatch_account.py::idle_shares``)."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.idle_share(r, "host")

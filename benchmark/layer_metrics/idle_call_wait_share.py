"""Of the traced slice's idle device time, the percent the dispatcher
thread spent inside a program call or waiting for its result while the
chip was not busy (``enqueueUs + deviceUs`` less the busy time): the
call, the launch, the waiter's wake-up. One of four shares that add up
to 100 (``harness/dispatch_account.py::idle_shares``)."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.idle_share(r, "call_wait")

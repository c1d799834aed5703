"""Median ``enqueueUs`` of the window's batched dispatches: the program
call itself on the dispatcher thread (argument transfer, launch; the
annotation ``dispatch.enqueue``), the largest stage of its cycle in the
two-stage cell and the second in the user-lane cell."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.stage_p50(r, "enqueueUs")

"""Median ``otherUs`` of the window's batched dispatches: what is left
of the gap before a program call once every named stage is taken off
(idle, window, pick, form, book, lock, and the previous dispatch's
fetch, deliver and book): the dispatcher's loop itself and its waits
for the interpreter lock between stages."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.stage_p50(r, "otherUs")

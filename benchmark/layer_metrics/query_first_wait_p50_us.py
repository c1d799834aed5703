"""Median ``firstWaitUs`` over every query delivered in the window
(the flight records' ``lives``): arrival to the first group that
claimed it. The query's OWN wait, where ``queue_wait_p50_us`` is the
age of a group's oldest."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.life_p50(r, "firstWaitUs")

"""Median ``ridingUs`` over every query delivered in the window: the
sum, over the groups it rode in, of claim to the dispatch function's
return (to delivery in its last): its programs, their fetches and its
lane's bookkeeping. What a faster device program can shorten."""

from benchmark.harness import dispatch_account


def read(r):
    return dispatch_account.life_p50(r, "ridingUs")

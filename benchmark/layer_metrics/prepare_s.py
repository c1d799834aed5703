"""Seconds of ``RatingsPreparator.prepare`` (index, dedupe, bucketize,
seen lists) in set-up: the harness's own span around the call."""


def read(r):
    return r["spans"].get("prepare_s")

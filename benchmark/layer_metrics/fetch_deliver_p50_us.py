"""Median ``fetchUs + deliverUs`` of the window's batched dispatches:
device output to host arrays, then futures resolved and the dispatcher's
counters bumped, all before the dispatcher can form the next batch."""

from benchmark.harness import program_spans


def _us(x):
    if x.get("fetchUs") is None or x.get("deliverUs") is None:
        return None
    return x["fetchUs"] + x["deliverUs"]


def read(r):
    return program_spans.median_of(program_spans.batched(r), _us)

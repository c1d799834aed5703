"""Median ``gapWindowUs`` of the window's batched dispatches: how long
the dispatcher slept on the batching window with a query pending."""

from benchmark.harness import program_spans


def read(r):
    return program_spans.median_of(program_spans.batched(r),
                                   lambda x: x.get("gapWindowUs"))

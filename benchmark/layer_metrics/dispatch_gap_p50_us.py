"""Median ``gapUs - gapIdleUs`` of the window's batched dispatches:
from the previous dispatch's ``block_until_ready`` return to this
dispatch's program call on the one dispatcher thread, less the time it
slept with every lane empty. Device time lost per dispatch while work
waited (fetch, deliver, the batching window, forming, the store lock)."""

from benchmark.harness import program_spans


def read(r):
    return program_spans.median_of(
        program_spans.batched(r),
        lambda x: x["gapUs"] - x.get("gapIdleUs", 0.0))

"""Median self time of ``device.wake`` over the window's query roots:
from the dispatcher resolving a query's future to its handler thread
running again (the interpreter lock, the scheduler). Part of the
``device.*`` time that ``handler_host_p50_us`` subtracts."""

from benchmark.harness import program_spans
from benchmark.layer_metrics.request_span_p50_us import ROOT


def read(r):
    return program_spans.median_of(
        program_spans.window_roots(r, ROOT),
        lambda s: s["selfUs"].get("device.wake"))

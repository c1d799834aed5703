"""Median over the window's query roots of the root's duration less
its ``device.*`` spans (submit -> result of the top-k call: queue wait,
dispatch, fetch): what the handler thread itself spends on body read,
parse, extract, supplement, template code, serve, render and write."""

from benchmark.harness import program_spans
from benchmark.layer_metrics.request_span_p50_us import ROOT


def _host_us(summary):
    device = sum(us for name, us in summary["selfUs"].items()
                 if name.startswith("device."))
    return summary["durationUs"] - device


def read(r):
    return program_spans.median_of(program_spans.window_roots(r, ROOT),
                                   _host_us)

"""Median over the window's ``als.train`` roots of the call's duration
less its ``als.iterations`` span (the program call and the block; a
first call's ``als.compile`` is its child): the table upload, the
factor initialisation and the download of the factors, which the device
trace could not show."""

from benchmark.harness import program_spans


def _outside_ms(summary):
    inside = program_spans.self_s(summary, "als.iterations", "als.compile")
    return summary["durationUs"] / 1e3 - inside * 1e3


def read(r):
    return program_spans.median_of(
        program_spans.window_roots(r, "als.train"), _outside_ms)

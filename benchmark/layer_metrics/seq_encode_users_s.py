"""Median over the window's ``seq.train`` roots of the
``seq.encode_users`` span: the forward pass over every user's packed
history that ends a call, waited for."""

from benchmark.harness import program_spans


def read(r):
    return program_spans.median_of(
        program_spans.window_roots(r, "seq.train"),
        lambda s: program_spans.self_s(s, "seq.encode_users") or None)

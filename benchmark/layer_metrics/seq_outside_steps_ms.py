"""Median over the window's ``seq.train`` roots of the call's duration
less its ``seq.steps``, ``seq.compile`` and ``seq.encode_users`` spans:
parameter initialisation and staging, the fetch of the model and the
vectors to the host, and the host work between them, which the device
trace cannot show."""

from benchmark.harness import program_spans


def _outside_ms(summary):
    inside = program_spans.self_s(summary, "seq.steps", "seq.compile",
                                  "seq.encode_users")
    return summary["durationUs"] / 1e3 - inside * 1e3


def read(r):
    return program_spans.median_of(
        program_spans.window_roots(r, "seq.train"), _outside_ms)

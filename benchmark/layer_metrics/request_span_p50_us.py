"""Median duration of the window's ``query POST /queries.json`` roots:
the server's own span round a whole request (body read, parse, extract,
serve, render, write), exact where ``server_handle_p50_ms`` interpolates
inside a histogram bucket. A server cannot take longer than its client
saw, so this stays under ``query_p50_ms`` x 1000."""

from benchmark.harness import program_spans

ROOT = "query POST /queries.json"


def read(r):
    return program_spans.median_of(program_spans.window_roots(r, ROOT),
                                   lambda s: s["durationUs"])

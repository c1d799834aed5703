"""``ladder.plan`` + ``ladder.lower`` + ``ladder.compile`` of the
run's ``pio.deploy`` root: enumerating the AOT ladder, tracing and
lowering its programs one at a time, and compiling them or loading them
from the persistent cache."""

from benchmark.harness import program_spans


def read(r):
    return program_spans.deploy_self_s("ladder.plan", "ladder.lower",
                                       "ladder.compile")

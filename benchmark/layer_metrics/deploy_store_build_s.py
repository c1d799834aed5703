"""``store.bitmap`` + ``store.upload`` of the run's ``pio.deploy``
root: the seen bitmap built on the host, then factors and bitmap moved
to the device and waited for."""

from benchmark.harness import program_spans


def read(r):
    return program_spans.deploy_self_s("store.bitmap", "store.upload")

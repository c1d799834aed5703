"""``deploy.load_models`` of the run's ``pio.deploy`` root: blob get,
``deserialize_models`` (the unpickle) and ``prepare_deploy``."""

from benchmark.harness import program_spans


def read(r):
    return program_spans.deploy_self_s("deploy.load_models")

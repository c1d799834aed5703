"""Device time of one PASS of the slate lane: the round program's module
time in the traced slice over the passes its dispatches ran (each
round's denoising passes and its commit pass:
``pio_slate_passes_total{kind="device"}``)."""
from benchmark.harness import slate_metrics as _s


def read(r):
    got = _s.sliced(r)
    if got is None:
        return None
    m, w = got
    return 1e3 * m["seconds"] / w["passes_device"]

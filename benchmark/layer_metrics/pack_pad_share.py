"""Share of the packed rows' slots that hold no token, in percent
(first-fit packing of the histories into 4,096-slot rows): tokens the
device computes and the loss ignores."""

from benchmark.harness import seq_metrics as _seq


def read(r):
    w = _seq.work(r)
    return None if w is None else 100.0 * w["pad_share"]

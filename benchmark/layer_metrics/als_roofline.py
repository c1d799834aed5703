"""Share of its roofline one ALS iteration reaches, in percent: the
least time ``shapes.py`` gives for one iteration (fp32 at
Precision.HIGHEST: six MXU passes) over ``iter_device_s``."""

from benchmark.harness import shapes
from benchmark.layer_metrics import iter_device_s


def least(r):
    w = r["work"]
    peak = shapes.peaks(r["device"]["kind"])
    need = shapes.als_iteration(w["pairs"], w["n_users"], w["n_items"],
                                w["rank"], factor_bytes=4)
    return shapes.least_time(need["flops"], need["bytes"], peak,
                             mxu_passes=peak["fp32_highest_passes"])


def read(r):
    per_iter = iter_device_s.read(r)
    if not per_iter:
        return None
    return 100.0 * least(r)["seconds"] / per_iter

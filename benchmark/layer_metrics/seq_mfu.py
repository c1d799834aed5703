"""Model FLOP/s utilisation of the traced steps, in percent: the
matmul operations their tokens need forward and backward
(``shapes_seq.model_flops``: segment-causal attention, 8 experts a
token, nothing recomputed) over the step program's device time times
the chip's bf16 peak."""

from benchmark.harness import seq_trace, shapes, shapes_seq
from benchmark.harness import seq_metrics as _seq


def read(r):
    m = _seq.module(r, seq_trace.STEP_MODULE)
    if m is None or not m["seconds"]:
        return None
    w = _seq.work(r)
    tokens, _ = _seq.traced_tokens(w)
    pairs, _ = _seq.traced_pairs(w)
    flops = shapes_seq.model_flops(
        tokens, pairs, w["traced_calls"] * w["targets_per_call"],
        w["block"], w["n_negatives"], passes=3)
    peak = shapes.peaks(r["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (m["seconds"] * peak)

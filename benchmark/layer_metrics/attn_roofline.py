"""Share of its roofline the blocked attention kernel reaches, in
percent: the least time for causal attention INSIDE the packed rows'
segments (``shapes_seq.attention`` over ``segment_pairs``) over the
device time of the Pallas kernels under ``attn/flash``. A kernel that
walks the whole causal triangle of a 4,096-token row whose segments
average 59 tokens reads low here."""

from benchmark.harness import shapes_seq
from benchmark.harness import seq_metrics as _seq


def read(r):
    seconds = _seq.kernel_seconds(r, "attn/flash")
    if not seconds:
        return None
    w = _seq.work(r)
    b = w["block"]
    need = {"flops": 0.0, "bytes": 0.0}
    for tokens, pairs, passes in zip(_seq.traced_tokens(w),
                                     _seq.traced_pairs(w), (3, 1)):
        one = shapes_seq.attention(pairs, tokens, b["n_heads"],
                                   b["head_dim"], passes=passes)
        need = {k: need[k] + b["n_layers"] * one[k] for k in need}
    return _seq.roofline_share(r, need, seconds)

"""Share of their roofline the grouped-matmul kernels reach, in
percent: the least time ``shapes_seq.moe_gmm`` gives for the traced
call's tokens (steps forward and backward, the encode forward) over
the device time of the Pallas kernels under ``moe/gmm_*``."""

from benchmark.harness import shapes_seq
from benchmark.harness import seq_metrics as _seq


def read(r):
    seconds = _seq.kernel_seconds(r, "moe/gmm_")
    if not seconds:
        return None
    w = _seq.work(r)
    b = w["block"]
    step_tokens, encode_tokens = _seq.traced_tokens(w)
    need = {"flops": 0.0, "bytes": 0.0}
    # the weights stream once a microbatch or encode call, not once a
    # token: bytes are counted per program call as the least program,
    # one pass over the weights per pass, would have them
    for tokens, passes in ((step_tokens, 3), (encode_tokens, 1)):
        one = shapes_seq.moe_gmm(tokens, b["hidden"], b["expert_width"],
                                 b["n_experts"], b["per_token"],
                                 passes=passes)
        need = {k: need[k] + one[k] for k in need}
    return _seq.roofline_share(r, need, seconds)

"""Share of the HBM roofline a dispatch of the session lane reaches, in
percent: the bytes a mean dispatch of the traced slice must stream
(``shapes_sess.dispatch_bytes``: weights touched, index keys seen,
latent rows selected, cache rows written, the output slice) at the
chip's peak bandwidth, over the extend program's device time."""
from benchmark.harness import sess_metrics as _s
from benchmark.harness import shapes_sess


def read(r):
    m, w = _s.module(r), _s.slice_work(r)
    if m is None or w is None:
        return None
    return _s.roofline(r, 0.0, shapes_sess.dispatch_bytes(w, w["block"]),
                       m["seconds"] / m["count"])

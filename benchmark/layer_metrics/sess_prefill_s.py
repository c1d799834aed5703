"""Seconds the deploy's warm-up spent building the resident sessions
(``SessionTopK.session_report()["residentSeconds"]``: the
``sess.resident`` span, one prefill program call a 2,048-event chunk
of every stored history)."""


def read(r):
    return r["spans"].get("sess_prefill_s") or None

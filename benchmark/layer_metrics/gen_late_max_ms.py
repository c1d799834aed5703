"""Largest (sent - due) of the window on the generator's own clock: a
stall of the generator's process or of the whole machine shows here as
one large value where ``gen_late_p99_ms`` stays small."""


def read(r):
    return (r.get("loadgen") or {}).get("gen_late_max_ms")

"""``JIT_COMPILES`` delta over the window plus dispatch records whose
``aot`` is not ``hit``. Must be 0: the run is not correct otherwise."""


def read(r):
    n = r["after"]["counters"]["jit_compiles"] \
        - r["before"]["counters"]["jit_compiles"]
    return n + sum(1 for x in r.get("flight") or []
                   if x.get("aot") != "hit")

"""``pio_jit_compile_seconds_total`` accumulated over the set-up's
compiling part (deploy and warm-up, or the first train() call): trace
+ lower + backend compile or cache retrieval."""


def read(r):
    return r["spans"].get("compile_s")

"""Device busy seconds per ALS iteration: the union of device-op
intervals inside the traced train() call over its iterations (the
trace holds exactly the traced calls; uploads and downloads are DMA,
not ops, and are outside the union)."""


def read(r):
    t, w = r.get("trace"), r["work"]
    if not t or w.get("kind") != "train_calls":
        return None
    return t["busy_s"] / (w["traced_calls"] * w["iterations"])

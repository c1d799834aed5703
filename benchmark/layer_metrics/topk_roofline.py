"""Share of its roofline the top-k program reaches, in percent: for
the dispatches recorded inside the traced slice, the least time
``shapes.py`` gives for each (its uid bucket and k bucket, the store's stated element size)
summed, over the device time of the serving modules in the trace
(every module that is not a store-maintenance program; the slice holds
nothing else)."""

from benchmark.harness import shapes


def read(r):
    t, w, win = r.get("trace"), r["work"], r.get("trace_window")
    if not t or not win or "n_items" not in w:
        return None
    recs = [x for x in r.get("flight") or []
            if win[0] <= x["ts"] < win[1]]
    device_s = sum(m["seconds"] for m in t["modules"].values())
    if not recs or device_s <= 0:
        return None
    peak = shapes.peaks(r["device"]["kind"])
    least = 0.0
    for x in recs:
        two = x["lane"] == "two"
        need = shapes.topk_dispatch(
            x["bucket"], w["n_items"], w["rank"], x["kBucket"],
            store_bytes=w["store_bytes"],
            stage2_width=w["stage2_width"] if two else 0,
            candidates=w["candidates"] if two else 0)
        least += shapes.least_time(need["flops"], need["bytes"],
                                   peak)["seconds"]
    return 100.0 * least / device_s

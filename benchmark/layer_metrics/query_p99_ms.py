"""99th percentile of the client-side latency from the due time. Not an
end-to-end metric: stalls of the shared chip machine (100-800 ms, in
most 45 s runs) land in it, so three runs of one cell read 58, 179 and
188 ms (chip calls E and F of PR 22; PERF.md section 6), and no bound
of at most 10% holds that."""


def read(r):
    return (r.get("loadgen") or {}).get("query_p99_ms")

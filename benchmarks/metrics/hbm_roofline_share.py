"""Kernels layer, whole query: the least device time the query could take —
the bytes of the columns its scans reference over the chips' published HBM
bandwidth — divided by the measured device-busy seconds per query, in
percent. Byte-bound and whole-query: it is no single kernel's share."""


def read(run):
    reduction = run["reduction"]
    if not reduction or not reduction["busy_s"]:
        return None
    queries = run["profiled"]
    scanned = sum(run["scan_bytes"][q["query"]] for q in queries)
    least_s = scanned / (run["chips"] * run["peaks"]["hbm_GBps"] * 1e9)
    return 100.0 * least_s / reduction["busy_s"]

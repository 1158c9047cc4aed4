"""Device layer, several chips: 100 x the least busy chip's busy seconds over
the busiest chip's, over the three profiled queries (busy = union of a
device plane's XLA-op intervals, benchmarks/trace_reduce.py). 100 = the chips
share the work evenly, 0 = some chip ran nothing. None without a device trace
or where no chip was busy."""


def read(run):
    reduction = run["reduction"]
    if not reduction:
        return None
    busy = [d["busy_s"] for d in reduction.get("per_device") or []]
    if not busy or max(busy) <= 0:
        return None
    return 100.0 * min(busy) / max(busy)

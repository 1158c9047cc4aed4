"""Scan layer: summed duration of a query's `scan_decode` spans (one per
record batch pulled from the parquet reader: read + decode), median per
query. Program spans, host clock; the pulls run on prefetch producer threads,
so this is a sum that may overlap compute, not a share of the wall. None where
the program records no such span."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or []
                if s["kind"] == "scan_decode"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

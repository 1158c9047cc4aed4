"""Scan layer: summed duration of a query's `decimal_decode` spans (one per
decimal column of a record batch: the decimal128 words the parquet reader
hands over -> the int64 planes of unscaled values that go to the device, in
columnar/arrow_io), median per query. Program spans, host clock; the step runs
inside the `h2d` span on a prefetch producer thread, so this is a sum that may
overlap compute, not a share of the wall. None where a query has no such span:
a program without the span kind, or a configuration without a decimal."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or []
                if s["kind"] == "decimal_decode"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

"""Collect layer: summed duration of a query's `collect` spans (result stage:
tasks done -> result batch: pulls, host sort, merge, re-upload) plus the `d2h`
span of the caller's final pull (marked `final`; absent where the result
already sits on the host), median per query. Program spans, host clock. None
where the program records no `collect` span."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        spans = q["spans"] or []
        durs = [s["dur"] for s in spans if s["kind"] == "collect"]
        if durs:
            durs += [s["dur"] for s in spans if s["kind"] == "d2h"
                     and s.get("attrs", {}).get("final")]
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

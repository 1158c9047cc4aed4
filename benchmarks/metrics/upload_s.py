"""Scan layer: summed duration of a query's `h2d` spans (host staging +
enqueue of each host->device transfer; the host's time in the call, not the
transfer's), median per query. Program spans, host clock. None where the
program records no such span."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or [] if s["kind"] == "h2d"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

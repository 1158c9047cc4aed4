"""Exchange + stages layer: summed duration of a query's `exchange` spans
(one per macro-batch through the in-HBM exchange: dispatch, the pull of the
partition bounds, slicing; and one per unshard of its output on a mesh),
median per query. Program spans, host clock: a sum, not a share of the wall.
None where the program records no such span."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or [] if s["kind"] == "exchange"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

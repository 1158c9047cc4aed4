"""Exchange + stages layer: summed duration of a query's `stage` spans of
kind shuffle_map, median per query. Host clock; stages may overlap under the
supervisor, so this is a sum and not a share of the wall."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or [] if s["kind"] == "stage"
                and s.get("attrs", {}).get("stage_kind") == "shuffle_map"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

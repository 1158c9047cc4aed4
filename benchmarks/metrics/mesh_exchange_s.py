"""Exchange + stages layer, on a mesh: summed duration of a query's `exchange`
spans whose transport is `mesh` (one per macro-batch or round through the
shard_map all_to_all: building the sharded input, the dispatch, the pull of
the per-partition counts, cutting each partition out on its chip), median per
query. Program spans, host clock: a sum, not a share of the wall. None where
no query has such a span (one chip, or a program without them)."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or []
                if s["kind"] == "exchange"
                and s.get("attrs", {}).get("transport") == "mesh"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

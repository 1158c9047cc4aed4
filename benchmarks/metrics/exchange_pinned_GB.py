"""Memory layer: what the in-HBM exchange holds pinned. A shuffle_map `stage`
span carries `pinned_bytes`: the high-water, over the stage, of the bytes of
the exchanged slices kept on the fullest chip (capacity-padded, validity
included: what `stage_exchange`'s budget check compares with half the memory
budget; a slice stays pinned until the consuming stage ends). The greatest
`pinned_bytes` among a query's shuffle_map stage spans / 1e9, median per
query. Program spans. None where no stage span carries the attribute: a
program from before it, or a query without an exchange."""
import statistics


def read(run):
    peaks = []
    for q in run["window"] + run["profiled"]:
        pinned = [s["attrs"]["pinned_bytes"] for s in q["spans"] or []
                  if s["kind"] == "stage"
                  and "pinned_bytes" in s.get("attrs", {})]
        if pinned:
            peaks.append(max(pinned) / 1e9)
    return statistics.median(peaks) if peaks else None

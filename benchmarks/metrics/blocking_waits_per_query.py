"""Entry layer: round trips in which the host stood still for the device. The
number of a query's `wait` spans whose `ready` is false (the device had not
finished the value when the host asked for it: the span holds the device's
work and the round trip), median per query; read beside dispatches_per_query.
Program spans. None where no query recorded a `wait` span (a program from
before them, tracing off); 0 where every pull found its value ready."""
import statistics


def read(run):
    counts = []
    for q in run["window"] + run["profiled"]:
        waits = [s for s in q["spans"] or [] if s["kind"] == "wait"]
        if waits:
            counts.append(sum(1 for s in waits
                              if not s.get("attrs", {}).get("ready")))
    return statistics.median(counts) if counts else None

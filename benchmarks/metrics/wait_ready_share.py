"""Entry layer: of the host's blocked time, the part spent fetching a value the
device had finished before it was asked for. 100 x summed duration of a
query's `wait` spans with `ready` true / summed duration of all its `wait`
spans, median per query: pure round-trip latency on a chip that was idle
already, what counts carried on the device would remove. Weighted by time, so
a repeat pull of a value the host has cached weighs nothing. Program spans,
host clock. None where no query recorded a `wait` span with a duration."""
import statistics


def read(run):
    shares = []
    for q in run["window"] + run["profiled"]:
        waits = [s for s in q["spans"] or [] if s["kind"] == "wait"]
        total = sum(s["dur"] for s in waits)
        if total:
            ready = sum(s["dur"] for s in waits
                        if s.get("attrs", {}).get("ready"))
            shares.append(100.0 * ready / total)
    return statistics.median(shares) if shares else None

"""Entry layer: how many times a query calls a cached device program: the
number of `dispatch` spans (runtime/jit_cache: one per call of a cached
executable, on the driver's thread or a task's) among a query's records,
median per query. Between two dispatches the host usually pulls a row count,
so the count paces a query of many small programs. Program spans. None where
no query recorded a dispatch span (a program from before them, tracing off)."""
import statistics


def read(run):
    counts = []
    for q in run["window"] + run["profiled"]:
        n = sum(1 for s in q["spans"] or [] if s["kind"] == "dispatch")
        if n:
            counts.append(n)
    return statistics.median(counts) if counts else None

"""Entry layer: the host's time blocked on control pulls. Summed duration of a
query's `wait` spans (columnar.batch.pull_rows / pull_array: one per pull of a
batch's rows, an exchange's bounds or counts, a join's pair total; the span is
the host standing still until the value has crossed), on every thread, median
per query. Program spans, host clock: on four chips a sum over the pool's
threads, like shuffle_map_stage_s, not a share of the wall. None where no
query recorded a `wait` span (a program from before them, tracing off)."""
import statistics


def read(run):
    sums = []
    for q in run["window"] + run["profiled"]:
        durs = [s["dur"] for s in q["spans"] or [] if s["kind"] == "wait"]
        if durs:
            sums.append(sum(durs) / 1e9)
    return statistics.median(sums) if sums else None

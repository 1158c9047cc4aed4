"""Planning layer: start of the program's `query` span to the start of its
first `stage` span (tagging, conversion, stage split), median per query over
the traced run's queries. Program spans, host clock."""
import statistics


def read(run):
    gaps = []
    for q in run["window"] + run["profiled"]:
        spans = q["spans"] or []
        began = [s["ts"] for s in spans if s["kind"] == "query"]
        stages = [s["ts"] for s in spans if s["kind"] == "stage"]
        if began and stages:
            gaps.append((min(stages) - began[0]) / 1e6)
    return statistics.median(gaps) if gaps else None

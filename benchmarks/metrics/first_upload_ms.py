"""Scan layer: start of the `query` span to the end of its first `h2d` span:
the earliest the device can have work in a query; a candidate for the
once-a-query idle gap. Median per query. Program spans, host clock. None
where the program records no `h2d` span."""
import statistics


def read(run):
    gaps = []
    for q in run["window"] + run["profiled"]:
        spans = q["spans"] or []
        began = [s["ts"] for s in spans if s["kind"] == "query"]
        uploads = sorted((s["ts"], s["ts"] + s["dur"]) for s in spans
                         if s["kind"] == "h2d")
        if began and uploads:
            gaps.append((uploads[0][1] - began[0]) / 1e6)
    return statistics.median(gaps) if gaps else None

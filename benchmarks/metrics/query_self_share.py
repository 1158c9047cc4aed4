"""Entry layer: what the spans still do not cover. 100 x (the `query` span's
duration less the union of its direct children on the driver's thread) / its
duration, median per query. A direct child names the query span's `id` as its
`parent`; children are clipped to the query's interval and may overlap.
Program spans, host clock. None where span records carry no `id` (a program
from before spans had parents)."""
import statistics


def _union(intervals):
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total, end = total + (b - a), b
        elif b > end:
            total, end = total + (b - end), b
    return total


def read(run):
    shares = []
    for q in run["window"] + run["profiled"]:
        spans = q["spans"] or []
        query = next((s for s in spans if s["kind"] == "query"), None)
        if not query or query.get("id") is None or not query.get("dur"):
            continue
        t0, t1 = query["ts"], query["ts"] + query["dur"]
        children = [(max(s["ts"], t0), min(s["ts"] + s["dur"], t1))
                    for s in spans if s.get("parent") == query["id"]
                    and s.get("thread") == query.get("thread")]
        covered = _union([c for c in children if c[1] > c[0]])
        shares.append(100.0 * (query["dur"] - covered) / query["dur"])
    return statistics.median(shares) if shares else None
